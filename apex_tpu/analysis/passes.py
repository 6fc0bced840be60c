"""The analysis passes — each one proves a structural property of a
traced/compiled step, or emits findings that say exactly where it fails.

A pass is a function ``(StepGraph) -> list[Finding]`` registered in
:data:`PASSES`.  :func:`apex_tpu.analysis.check` builds the
:class:`StepGraph` (jaxpr + compiled HLO + intent: amp policy, donation
plan, collective expectations) and runs the selected passes; the
framework is deliberately dumb — all the knowledge lives in passes, so
the next rule is a ~30-line function plus a :data:`findings.RULES`
catalog row.

Jaxpr-level passes (transfer callbacks, promotion) walk the closed
jaxpr RECURSIVELY through pjit/scan/while/cond sub-jaxprs — a transfer
buried in a scan body is still a transfer every iteration.  HLO-level
passes (host transfers, donation aliasing, collective consistency) read
the optimized module text through :mod:`apex_tpu.analysis.hlo`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.extend.core as jax_core
import jax.numpy as jnp

from apex_tpu.analysis import hlo as hlo_lib
from apex_tpu.analysis.findings import Finding, make_finding

__all__ = [
    "StepGraph",
    "PASSES",
    "iter_eqns",
    "transfer_pass",
    "promotion_pass",
    "donation_pass",
    "collective_pass",
]


@dataclasses.dataclass
class StepGraph:
    """Everything a pass may inspect about one step function.

    ``jaxpr``/``hlo_text`` may individually be None (e.g. ``lint_hlo``
    has no jaxpr); passes skip silently when their substrate is absent.
    The remaining fields carry INTENT — what the program is supposed to
    look like — without which the corresponding pass has nothing to
    prove and stays quiet.
    """

    jaxpr: Optional[Any] = None          # jax.extend.core.ClosedJaxpr
    hlo_text: Optional[str] = None
    policy: Optional[Any] = None         # amp.Policy / dtype-carrying obj
    donated: Optional[int] = None        # expected donated leaf count
    donated_argnums: tuple = ()
    compile_warnings: tuple = ()         # str(w) captured at compile()
    expect_collectives: Optional[dict] = None
    #: sharding-conformance intent: {"mesh": {axis: size}, "rules":
    #: [(regex, PartitionSpec)], "min_bytes": int} — see
    #: apex_tpu.analysis.sharding
    expect_sharding: Optional[dict] = None
    #: per-mesh-axis collective plan: {"mesh": ..., "collectives":
    #: [{kind, axis, count?, bytes?, dtypes?}], "allow_unplanned_bytes"}
    expect_plan: Optional[dict] = None
    #: static peak-HBM budget in bytes (apex_tpu.analysis.memory)
    hbm_budget: Optional[int] = None
    #: the serving KV pool this program takes: {"shapes": [(L, P, R,
    #: page, W), ...], "severity": str?} — arms the memory-pool-copy
    #: rule (apex_tpu.analysis.memory.pool_copy_findings)
    expect_pool: Optional[dict] = None
    #: source substrate for the host-side passes: [(package-relative
    #: path, source text), ...] — built by
    #: apex_tpu.analysis.purity.collect_sources; the concurrency and
    #: purity passes skip silently when this is None (graph-only runs)
    sources: Optional[list] = None


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def _sub_jaxprs(params: dict):
    for v in params.values():
        if isinstance(v, jax_core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jax_core.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for item in v:
                if isinstance(item, jax_core.ClosedJaxpr):
                    yield item.jaxpr
                elif isinstance(item, jax_core.Jaxpr):
                    yield item


def iter_eqns(jaxpr):
    """Yield every eqn of a (Closed)Jaxpr, recursing into sub-jaxprs
    (pjit, scan, while, cond branches, custom_vjp calls, ...)."""
    if isinstance(jaxpr, jax_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def _eqn_path(eqn) -> str:
    """name_stack + file:line — the op path findings point at."""
    try:
        from jax._src import source_info_util

        src = source_info_util.summarize(eqn.source_info)
    except Exception:  # pragma: no cover - jax internals moved
        src = ""
    ns = str(getattr(eqn.source_info, "name_stack", "") or "")
    if ns and src:
        return f"{ns} ({src})"
    return ns or src or str(eqn.primitive)


# ---------------------------------------------------------------------------
# transfer lint
# ---------------------------------------------------------------------------

#: primitives whose execution leaves the device for the host python
#: runtime — one round-trip per step (or per scan iteration)
_CALLBACK_PRIMITIVES = frozenset({
    "debug_print",      # jax.debug.print
    "debug_callback",   # jax.debug.callback
    "pure_callback",
    "io_callback",
    "callback",
    "outside_call",     # legacy host_callback
    "host_callback_call",
})


def transfer_pass(graph: StepGraph) -> List[Finding]:
    """No host↔device transfers inside the step.

    Jaxpr level: callback primitives (each one a device→host→device
    round-trip that serializes dispatch).  HLO level: infeed/outfeed,
    host send/recv, python-callback custom-calls that survived into the
    compiled module.
    """
    out: List[Finding] = []
    if graph.jaxpr is not None:
        for eqn in iter_eqns(graph.jaxpr):
            if eqn.primitive.name in _CALLBACK_PRIMITIVES:
                out.append(make_finding(
                    "transfer-callback",
                    path=_eqn_path(eqn),
                    message=(
                        f"'{eqn.primitive.name}' traced into the step — "
                        "a host round-trip every execution"
                    ),
                ))
    if graph.hlo_text is not None:
        for name, why in hlo_lib.host_transfer_ops(graph.hlo_text):
            out.append(make_finding(
                "transfer-hlo-host",
                path=name,
                message=f"compiled HLO op is a host transfer: {why}",
            ))
    return out


# ---------------------------------------------------------------------------
# promotion lint
# ---------------------------------------------------------------------------

_WIDE_FLOATS = {"float64", "complex128"}

#: a named_scope containing one of these tokens marks a region as
#: intentionally higher-precision (f32 accumulation, master weights) —
#: widening inside it is policy-exempt, not silent
_ALLOW_SCOPE_TOKENS = ("f32", "fp32", "master", "highp")

_FLOAT_ORDER = {"bfloat16": 2, "float16": 2, "float32": 4, "float64": 8}


def _compute_dtype(policy) -> Optional[Any]:
    if policy is None:
        return None
    dt = getattr(policy, "compute_dtype", policy)
    try:
        return jnp.dtype(dt)
    except TypeError:
        return None


def _scope_allows(eqn) -> bool:
    ns = str(getattr(eqn.source_info, "name_stack", "") or "").lower()
    return any(tok in ns for tok in _ALLOW_SCOPE_TOKENS)


#: a widening convert consumed ONLY by these primitives is jnp's own
#: accumulate-in-f32-then-narrow reduction idiom (jnp.sum on bf16
#: upcasts internally) — by-design precision, not a silent promotion
_REDUCTION_PRIMS = frozenset({
    "reduce_sum", "reduce_prod", "reduce_max", "reduce_min",
    "reduce_and", "reduce_or", "argmax", "argmin",
    "cumsum", "cumprod", "cummax", "cummin",
})


def promotion_pass(graph: StepGraph) -> List[Finding]:
    """No silent dtype widening.

    - ``promotion-f64`` (always on): any eqn producing f64/c128, or an
      f64 literal operand — TPUs emulate f64, and one literal is enough
      to drag a whole subgraph wide.
    - ``promotion-widen`` (needs a half-precision ``policy``): a value
      of the policy's compute dtype converted to a wider float OUTSIDE
      a named scope that declares the widening intentional
      (:data:`_ALLOW_SCOPE_TOKENS`).  JAX materializes silent
      promotions (bf16 array meeting a non-weak f32 array) as exactly
      such a ``convert_element_type`` eqn.  Converts whose every
      consumer is a reduction are exempt — that is jnp's internal
      accumulate-wide idiom (:data:`_REDUCTION_PRIMS`), the behavior a
      policy WANTS.

    Findings deduplicate per op path: one site widening 100 leaves in a
    tree_map is one finding (with a count), not 100.
    """
    if graph.jaxpr is None:
        return []
    compute = _compute_dtype(graph.policy)
    check_widen = compute is not None and jnp.dtype(compute).itemsize < 4
    sites: Dict[tuple, List] = {}  # (rule, path) -> [message, count]

    def visit(jaxpr):
        if isinstance(jaxpr, jax_core.ClosedJaxpr):
            jaxpr = jaxpr.jaxpr
        # per-level consumer map: var -> primitive names that read it
        consumers: Dict[Any, set] = {}
        for eqn in jaxpr.eqns:
            for v in eqn.invars:
                if not isinstance(v, jax_core.Literal):
                    consumers.setdefault(v, set()).add(eqn.primitive.name)
        escaping = set(jaxpr.outvars)
        for eqn in jaxpr.eqns:
            _check_eqn(eqn, consumers, escaping)
            for sub in _sub_jaxprs(eqn.params):
                visit(sub)

    def _check_eqn(eqn, consumers, escaping):
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is not None and str(dt) in _WIDE_FLOATS:
                key = ("promotion-f64", _eqn_path(eqn))
                rec = sites.setdefault(key, [
                    f"'{eqn.primitive.name}' produces {dt}", 0])
                rec[1] += 1
                break
        for v in eqn.invars:
            if isinstance(v, jax_core.Literal):
                dt = getattr(getattr(v, "aval", None), "dtype", None)
                if dt is not None and str(dt) in _WIDE_FLOATS:
                    key = ("promotion-f64", _eqn_path(eqn))
                    rec = sites.setdefault(key, [
                        f"f64 literal feeds '{eqn.primitive.name}'", 0])
                    rec[1] += 1
                    break
        if (
            check_widen
            and eqn.primitive.name == "convert_element_type"
            and not _scope_allows(eqn)
        ):
            src = getattr(eqn.invars[0], "aval", None)
            dst = getattr(eqn.outvars[0], "aval", None)
            src_dt = getattr(src, "dtype", None)
            dst_dt = getattr(dst, "dtype", None)
            if (
                src_dt is not None and dst_dt is not None
                and str(src_dt) == str(compute)
                and _FLOAT_ORDER.get(str(dst_dt), 0)
                > _FLOAT_ORDER.get(str(src_dt), 99)
            ):
                out_v = eqn.outvars[0]
                used_by = consumers.get(out_v, set())
                if (
                    used_by
                    and used_by <= _REDUCTION_PRIMS
                    and out_v not in escaping
                ):
                    return  # jnp's accumulate-wide reduction idiom
                key = ("promotion-widen", _eqn_path(eqn))
                rec = sites.setdefault(key, [
                    f"{src_dt} -> {dst_dt} past compute dtype "
                    f"{jnp.dtype(compute).name}", 0])
                rec[1] += 1

    visit(graph.jaxpr)
    out = []
    for (rule, path), (msg, count) in sites.items():
        if count > 1:
            msg += f" ({count} values at this site)"
        out.append(make_finding(rule, path=path, message=msg))
    return out


# ---------------------------------------------------------------------------
# donation lint
# ---------------------------------------------------------------------------


def donation_pass(graph: StepGraph) -> List[Finding]:
    """Every buffer declared in ``donate_argnums`` must be aliased in
    the compiled buffer assignment; a dropped donation means XLA kept
    BOTH copies live (for an optimizer state, that's 2x memory).

    Ground truth is the module header's ``input_output_alias`` —
    :func:`apex_tpu.analysis.hlo.input_output_aliases` — compared
    against the number of leaves in the donated arguments.  The
    "donated buffers were not usable" warning captured at compile time
    (when present) names the exact shapes for the finding.
    """
    if graph.hlo_text is None or graph.donated is None:
        return []
    aliased = hlo_lib.input_output_aliases(graph.hlo_text)
    dropped = graph.donated - len(aliased)
    if dropped <= 0:
        return []
    detail = ""
    for w in graph.compile_warnings:
        if "donated" in w:
            detail = " — " + w.splitlines()[0]
            break
    argnums = (
        f" (donate_argnums={tuple(graph.donated_argnums)})"
        if graph.donated_argnums else ""
    )
    return [make_finding(
        "donation-dropped",
        path="input_output_alias",
        message=(
            f"{dropped} of {graph.donated} donated buffers were NOT "
            f"aliased by XLA{argnums}; each holds a duplicate "
            f"allocation{detail}"
        ),
    )]


# ---------------------------------------------------------------------------
# collective consistency
# ---------------------------------------------------------------------------


def _normalize_expectation(spec) -> dict:
    if isinstance(spec, int):
        return {"count": spec}
    return dict(spec)


def collective_pass(graph: StepGraph) -> List[Finding]:
    """The compiled collective schedule matches the comm engine's
    promise: per-kind count, payload bytes, and wire dtype.

    ``expect_collectives`` maps an HLO collective kind (``all-reduce``,
    ``all-gather``, ``reduce-scatter``, ``all-to-all``,
    ``collective-permute``) to either a bare count or a dict with any
    of ``count``, ``bytes`` (exact, or ``[lo, hi]`` bounds), and
    ``dtypes`` (the complete allowed payload-dtype set, e.g.
    ``["s8", "f32"]`` for an int8 wire whose scales ride along).  Kinds
    present in the HLO but absent from the expectation are ignored —
    assert on what the engine promises, not on XLA's whole schedule.
    """
    if graph.hlo_text is None or not graph.expect_collectives:
        return []
    summary = hlo_lib.collective_summary(graph.hlo_text)
    dtypes = hlo_lib.collective_dtypes(graph.hlo_text)
    out: List[Finding] = []
    for kind, raw in graph.expect_collectives.items():
        spec = _normalize_expectation(raw)
        actual = summary.get(kind, {"count": 0, "bytes": 0})
        if "count" in spec and actual["count"] != spec["count"]:
            out.append(make_finding(
                "collective-count",
                path=kind,
                message=(
                    f"expected {spec['count']} '{kind}' collective(s), "
                    f"compiled HLO has {actual['count']}"
                ),
            ))
        if "bytes" in spec:
            want = spec["bytes"]
            lo, hi = (want, want) if isinstance(want, int) else want
            if not (lo <= actual["bytes"] <= hi):
                out.append(make_finding(
                    "collective-bytes",
                    path=kind,
                    message=(
                        f"'{kind}' moves {actual['bytes']} bytes, "
                        f"expected within [{lo}, {hi}]"
                    ),
                ))
        if "dtypes" in spec:
            allowed = set(spec["dtypes"])
            got = dtypes.get(kind, set())
            extra = got - allowed
            if extra:
                out.append(make_finding(
                    "collective-dtype",
                    path=kind,
                    message=(
                        f"'{kind}' payload carries {sorted(extra)} "
                        f"beyond the wire's allowed {sorted(allowed)}"
                    ),
                ))
    return out


from apex_tpu.analysis.concurrency import concurrency_pass  # noqa: E402
from apex_tpu.analysis.memory import memory_pass  # noqa: E402
from apex_tpu.analysis.purity import purity_pass  # noqa: E402
from apex_tpu.analysis.sharding import (  # noqa: E402
    reshard_pass,
    sharding_pass,
)

#: pass name -> implementation; ``rules=`` selects by these names (the
#: retrace rule is runtime-only — see analysis.RetraceSentinel).  The
#: sharding/reshard/memory passes live in their own modules
#: (apex_tpu/analysis/sharding.py, .../memory.py) and are quiet until
#: their intent (expect_sharding / expect_plan / hbm_budget) is given.
#: The concurrency/purity passes read the SOURCE substrate
#: (StepGraph.sources) and are quiet without it.
PASSES: Dict[str, Callable[[StepGraph], List[Finding]]] = {
    "transfer": transfer_pass,
    "promotion": promotion_pass,
    "donation": donation_pass,
    "collective": collective_pass,
    "sharding": sharding_pass,
    "reshard": reshard_pass,
    "memory": memory_pass,
    "concurrency": concurrency_pass,
    "purity": purity_pass,
}
