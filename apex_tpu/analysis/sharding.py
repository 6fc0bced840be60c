"""Sharding-conformance + resharding passes — prove the dp×tp plan
compiled, before it runs.

A declared sharding plan is a *promise*: every large param/optimizer
leaf carries its PartitionSpec in the compiled module, and the step
body contains exactly the collectives the plan predicts — no silent
full replication (GSPMD quietly replicates anything the propagation
can't decide, and a replicated optimizer state is the difference
between fitting and OOM), and no unplanned weight all-gathers (the
signature of a spec that didn't survive propagation: XLA re-gathers
the full tensor every step and the "sharded" run is secretly paying
replicated wire traffic).  These passes check both promises against
the optimized HLO:

- :func:`sharding_pass` — **spec conformance**.  Intent is a
  regex→PartitionSpec rule table (:func:`match_partition_rules`, the
  ``fmengine``/EasyLM idiom — the same tables a trainer entry point
  feeds to ``jax.jit``'s ``in_shardings``) matched against each ENTRY
  parameter's jax arg path (the ``op_name`` metadata GSPMD carries
  into the module).  A leaf above ``min_bytes`` whose intended spec is
  sharded but whose compiled sharding is ``{replicated}`` is
  ``sharding-replicated`` (ERROR); a compiled tiling that disagrees
  with the intended per-dim factors is ``sharding-mismatch``.
- :func:`reshard_pass` — **no unintended resharding**.  Intent is a
  per-mesh-axis collective plan (kind, axis, count, bytes, wire
  dtypes — what :meth:`apex_tpu.parallel.DistributedDataParallel
  .collective_plan` and the ZeRO optimizers declare); every compiled
  collective is attributed to a mesh axis by its replica groups and
  checked off against the plan.  A collective the plan doesn't
  predict (above a small latency tolerance) is ``reshard-unplanned``;
  a planned entry whose compiled count/bytes/dtypes drifted is
  ``reshard-plan``.

Both passes skip silently when their intent (``expect_sharding`` /
``expect_plan``) is absent, and the conformance pass degrades to a
``sharding-unverified`` WARNING when the module compiled single-device
(``num_partitions=1``) while the plan names a real mesh — a "clean"
verdict must never claim a property nobody could check.

Plan schema (the ``expect_sharding`` intent)::

    {
        "mesh": {"dp": 2, "tp": 4},          # axis order matters
        "rules": [                            # first match wins
            (r"embed|wte|wpe", P("tp", None)),
            (r"mlp/kernel",    P(None, "tp")),
            (r".*",            P()),          # explicit catch-all
        ],
        "min_bytes": 1 << 20,                 # ignore small leaves
    }

and the ``expect_plan`` intent::

    {
        "mesh": {"dp": 2, "tp": 4},
        "collectives": [
            {"kind": "all-reduce", "axis": "dp",
             "bytes": [0, 4 << 20], "dtypes": ["f32"]},
            {"kind": "all-to-all", "axis": "dp", "count": 2,
             "dtypes": ["s8"]},
        ],
        "allow_unplanned_bytes": 4096,        # latency-sized tolerance
    }

See ``docs/analysis.md`` "Sharding & memory passes".
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

from apex_tpu.analysis import hlo as hlo_lib
from apex_tpu.analysis.findings import Finding, make_finding

__all__ = [
    "DEFAULT_MIN_BYTES",
    "DEFAULT_UNPLANNED_TOLERANCE",
    "normalize_param_path",
    "match_partition_rules",
    "tree_paths",
    "spec_dim_factors",
    "mesh_axis_groups",
    "infer_collective_axis",
    "plan_table",
    "sharding_pass",
    "reshard_pass",
]

#: leaves under 1 MiB replicate for free — biases, LN scales, scalars;
#: the conformance gate is about the tensors that decide whether the
#: model fits
DEFAULT_MIN_BYTES = 1 << 20

#: unplanned collectives at or under this payload are latency-sized
#: bookkeeping (loss pmeans, metric rows, guard scalars), not a
#: resharded weight
DEFAULT_UNPLANNED_TOLERANCE = 4096


# ---------------------------------------------------------------------------
# rule tables (the match_partition_rules idiom)
# ---------------------------------------------------------------------------


def normalize_param_path(op_name: str) -> str:
    """GSPMD's parameter ``op_name`` metadata (``state[\\'params\\']
    [\\'w\\']``, ``batch[0]``, ``scaler_state.loss_scale``) → a
    ``/``-joined path (``state/params/w``, ``batch/0``,
    ``scaler_state/loss_scale``) that partition-rule regexes match
    against — the same separator :func:`match_partition_rules` uses on
    live pytrees, so ONE rule table serves both."""
    s = op_name.replace("\\'", "'").replace('\\"', '"')
    s = re.sub(r"\[['\"]?([^]'\"]*)['\"]?\]", r"/\1", s)
    s = s.replace(".", "/")
    return s.strip("/")


def tree_paths(tree, sep: str = "/") -> List[Tuple[str, Any]]:
    """``[(path, leaf), ...]`` with dict keys / sequence indices /
    attribute names joined by ``sep`` — the naming
    :func:`match_partition_rules` and :func:`normalize_param_path`
    share."""
    import jax

    out = []
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    for keypath, leaf in flat:
        parts = []
        for k in keypath:
            if hasattr(k, "key"):
                parts.append(str(k.key))
            elif hasattr(k, "idx"):
                parts.append(str(k.idx))
            elif hasattr(k, "name"):
                parts.append(str(k.name))
            else:  # pragma: no cover - exotic key types
                parts.append(str(k))
        out.append((sep.join(parts), leaf))
    return out


def match_partition_rules(rules, params, sep: str = "/"):
    """Pytree of PartitionSpec from regex rules — the
    ``fmengine``/EasyLM ``match_partition_rules`` idiom (SNIPPETS.md
    [2]): first rule whose regex ``re.search``-matches the leaf's
    ``/``-joined path wins; scalar and single-element leaves are never
    partitioned (spec ``P()``); a leaf no rule covers raises (a plan
    with holes is not a plan).

    The SAME table drives both surfaces: feed the result to
    ``jax.jit(in_shardings=...)`` (via ``NamedSharding``) when
    building the step, and pass the raw ``rules`` as
    ``expect_sharding["rules"]`` to :func:`apex_tpu.analysis.check` to
    prove the compiled module kept them.
    """
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec

    def pick(path: str, leaf):
        shape = getattr(leaf, "shape", ())
        if len(shape) == 0 or int(np.prod(shape)) == 1:
            return PartitionSpec()
        for rule, spec in rules:
            if re.search(rule, path) is not None:
                return spec
        raise ValueError(f"partition rule not found for param: {path}")

    flat = tree_paths(params, sep=sep)
    specs = [pick(path, leaf) for path, leaf in flat]
    treedef = jax.tree_util.tree_structure(params)
    return jax.tree_util.tree_unflatten(treedef, specs)


def spec_dim_factors(spec, mesh: Dict[str, int], rank: int) -> List[int]:
    """Shards-per-dim a PartitionSpec implies on a rank-``rank`` leaf
    under ``mesh`` (axis → size): ``P(None, "tp")`` on rank 2 with
    ``tp=4`` → ``[1, 4]``; tuple entries multiply
    (``P(("dp", "tp"))`` → ``[8]``)."""
    entries: Sequence = tuple(spec) if spec is not None else ()
    factors = []
    for d in range(rank):
        e = entries[d] if d < len(entries) else None
        if e is None:
            factors.append(1)
        elif isinstance(e, (tuple, list)):
            f = 1
            for axis in e:
                f *= int(mesh.get(axis, 1))
            factors.append(f)
        else:
            factors.append(int(mesh.get(e, 1)))
    return factors


# ---------------------------------------------------------------------------
# mesh-axis attribution of replica groups
# ---------------------------------------------------------------------------


def mesh_axis_groups(mesh: Dict[str, int]) -> Dict[str, frozenset]:
    """Canonical replica-group sets per mesh axis (+ ``"all"`` for the
    whole mesh), assuming row-major device ids over the axis order —
    jax's ``Mesh(devices.reshape(sizes), axes)`` layout.  Each value
    is a frozenset of frozensets of device ids; a collective whose
    printed ``replica_groups`` equal one of these belongs to that
    axis.  Distinguishes dp from tp even at equal sizes (dp=2×tp=2),
    where group SIZE alone is ambiguous."""
    axes = list(mesh)
    sizes = [int(mesh[a]) for a in axes]
    total = 1
    for s in sizes:
        total *= s
    out: Dict[str, frozenset] = {
        "all": frozenset([frozenset(range(total))])
    }
    if total <= 1:
        return out
    for i, axis in enumerate(axes):
        inner = 1  # product of sizes after (minor to) axis i
        for s in sizes[i + 1:]:
            inner *= s
        outer = total // (sizes[i] * inner)
        groups = []
        for o in range(outer):
            for j in range(inner):
                groups.append(frozenset(
                    o * sizes[i] * inner + k * inner + j
                    for k in range(sizes[i])
                ))
        out[axis] = frozenset(groups)
    return out


def infer_collective_axis(
    coll: dict, axis_groups: Dict[str, frozenset], mesh: Dict[str, int]
) -> Optional[str]:
    """Mesh axis a compiled collective spans, from its replica groups.
    Exact group-membership match first (unambiguous even at dp=tp);
    fall back to a unique group-size match when only the iota form
    printed; None when nothing matches (a reshard across a device set
    the mesh doesn't explain — inherently unplanned)."""
    groups = coll.get("groups")
    if groups:
        canon = frozenset(frozenset(g) for g in groups)
        # named axes take precedence: on a 1-axis mesh the axis's
        # groups EQUAL the whole-mesh groups, and the plan names the
        # axis ("dp"), not "all"
        for axis, expected in axis_groups.items():
            if axis != "all" and canon == expected:
                return axis
        if canon == axis_groups["all"]:
            return "all"
        return None
    size = coll.get("group_size")
    if size is None:
        return "all"  # no groups printed = every device participates
    by_size = [
        a for a, s in mesh.items() if int(s) == size
    ]
    total = 1
    for s in mesh.values():
        total *= int(s)
    if size == total:
        return "all"
    return by_size[0] if len(by_size) == 1 else None


# ---------------------------------------------------------------------------
# spec conformance
# ---------------------------------------------------------------------------


def _intended_spec(rules, path: str):
    for rule, spec in rules:
        if re.search(rule, path) is not None:
            return spec
    return None


def plan_table(
    hlo_text: str,
    expect_sharding: Optional[dict] = None,
) -> List[dict]:
    """The human-readable shard plan: one row per ENTRY parameter with
    its compiled sharding, global bytes, intended spec (when a rule
    table is given) and a conformance verdict — what
    ``tools/shard_report.py`` renders and the ``--json`` artifact's
    ``shard_plan`` section carries."""
    spec = expect_sharding or {}
    mesh = dict(spec.get("mesh") or {})
    rules = list(spec.get("rules") or ())
    rows = []
    for p in hlo_lib.parameter_shardings(hlo_text):
        path = normalize_param_path(p["op_name"])
        parsed = hlo_lib.parse_sharding(p["sharding"])
        intended = _intended_spec(rules, path) if path else None
        want = None
        verdict = "unchecked"
        if intended is not None:
            rank = len(hlo_lib.shape_dims(p["shape"]))
            want = spec_dim_factors(intended, mesh, rank)
            have = parsed["dims"] or [1] * rank
            have = have + [1] * (rank - len(have))
            if parsed["kind"] in ("unknown", "manual"):
                verdict = "unchecked"
            elif all(f == 1 for f in want):
                verdict = (
                    "ok" if parsed["kind"] == "replicated" else "mismatch"
                )
            elif parsed["kind"] == "replicated":
                verdict = "replicated"
            else:
                verdict = "ok" if have == want else "mismatch"
        rows.append({
            "param": p["param"],
            "name": path or p["name"],
            "shape": p["shape"],
            "global_bytes": p["global_bytes"],
            "sharding": p["sharding"] or "(none)",
            "intended": str(intended) if intended is not None else None,
            "factors": want,
            "verdict": verdict,
        })
    return rows


def sharding_pass(graph) -> List[Finding]:
    """Spec conformance: every parameter above ``min_bytes`` whose
    rule-table spec shards it must carry that tiling in the compiled
    module.  See the module docstring for the intent schema."""
    if graph.hlo_text is None or not graph.expect_sharding:
        return []
    spec = graph.expect_sharding
    mesh = dict(spec.get("mesh") or {})
    min_bytes = int(spec.get("min_bytes", DEFAULT_MIN_BYTES))
    mesh_size = 1
    for s in mesh.values():
        mesh_size *= int(s)
    npart = hlo_lib.num_partitions(graph.hlo_text)
    if mesh_size > 1 and npart < mesh_size:
        return [make_finding(
            "sharding-unverified",
            path="module header",
            message=(
                f"the plan names a {mesh_size}-device mesh "
                f"({'x'.join(f'{a}={s}' for a, s in mesh.items())}) but "
                f"the module compiled with num_partitions={npart} — "
                "sharding conformance cannot be proven on this compile"
            ),
        )]
    out: List[Finding] = []
    for row in plan_table(graph.hlo_text, spec):
        if row["verdict"] in ("ok", "unchecked"):
            continue
        if row["global_bytes"] < min_bytes:
            continue
        mb = row["global_bytes"] / (1 << 20)
        if row["verdict"] == "replicated":
            out.append(make_finding(
                "sharding-replicated",
                path=row["name"],
                message=(
                    f"{mb:.1f} MiB leaf compiled fully REPLICATED; the "
                    f"plan shards it as {row['intended']} "
                    f"(x{max(row['factors'] or [1])} memory per device "
                    "wasted)"
                ),
            ))
        else:
            out.append(make_finding(
                "sharding-mismatch",
                path=row["name"],
                message=(
                    f"compiled sharding '{row['sharding']}' disagrees "
                    f"with the declared {row['intended']} "
                    f"(want per-dim factors {row['factors']})"
                ),
            ))
    return out


# ---------------------------------------------------------------------------
# resharding (per-mesh-axis collective plan)
# ---------------------------------------------------------------------------


#: planned kinds a backend may compile as an all-reduce over the same
#: replica groups, and the factor the result grows by: a reduce-scatter
#: is an all-reduce + a local slice (result: the full buffer, axis-size
#: shards), an all-gather an all-reduce of the shard placed in zeros
#: (result: the same full buffer).  XLA:TPU does both on a 2-wide axis
#: at every buffer size tried, 0.26 MB to 16.8 MB (v5e 2x2, PR 21);
#: XLA:CPU never.
_ALL_REDUCE_FORM = {
    "reduce-scatter": lambda axis_size: axis_size,
    "all-gather": lambda axis_size: 1,
}


def _byte_bounds(entry) -> Optional[Tuple[int, int]]:
    want = entry.get("bytes")
    if want is None:
        return None
    return (want, want) if isinstance(want, int) else (want[0], want[1])


def _credit_decomposed(entries, actual, mesh) -> List[dict]:
    """The plan restated for the decompositions the compiler chose.

    A planned reduce-scatter/all-gather the module is short of, on an
    axis whose compiled all-reduce bytes exceed what the plan gives
    all-reduces, was compiled in its all-reduce form
    (:data:`_ALL_REDUCE_FORM`): its count, byte allowance and wire
    dtypes move to the axis's all-reduce entry (created when the plan
    has none).  What an axis may move stays bounded by the plan, and a
    missing op that no all-reduce stands in for is still reported."""
    entries = [dict(e) for e in entries]
    for e in list(entries):
        kind, axis = e["kind"], e.get("axis", "all")
        got_ar = actual.get(("all-reduce", axis))
        if kind not in _ALL_REDUCE_FORM or not e.get("count") or not got_ar:
            continue
        want = e["count"]
        have = actual.get((kind, axis), {"count": 0})["count"]
        if have >= want:
            continue
        ar = next((
            x for x in entries
            if x["kind"] == "all-reduce" and x.get("axis", "all") == axis
        ), None)
        if ar is None:
            ar = {"kind": "all-reduce", "axis": axis, "bytes": 0,
                  "dtypes": []}
        ar_bounds = _byte_bounds(ar)
        if ar_bounds is not None and got_ar["bytes"] <= ar_bounds[1]:
            continue  # every all-reduce is the plan's own: the op is gone
        if not any(x is ar for x in entries):
            entries.append(ar)
        e["count"] = have
        if ar.get("count") is not None:
            ar["count"] += want - have
        if ar.get("dtypes") is not None:
            ar["dtypes"] = None if e.get("dtypes") is None else sorted(
                set(ar["dtypes"]) | set(e["dtypes"])
            )
        bounds = _byte_bounds(e)
        if bounds is None:
            ar["bytes"] = None  # the plan never bounded this payload
        elif ar_bounds is not None:
            moved = -(-bounds[1] * (want - have) // want)
            e["bytes"] = [bounds[0] * have // want, bounds[1] - moved]
            grow = _ALL_REDUCE_FORM[kind](int(mesh.get(axis, 1)))
            ar["bytes"] = [ar_bounds[0], ar_bounds[1] + moved * grow]
    return entries


def reshard_pass(graph) -> List[Finding]:
    """No unintended resharding: every compiled collective must be
    predicted by the declared per-axis plan; every plan entry with
    explicit count/bytes/dtypes must match the compiled aggregate for
    its (kind, axis) — after :func:`_credit_decomposed` has moved the
    allowance of reduce-scatters/all-gathers the backend compiled as
    all-reduces.  See the module docstring for the plan schema."""
    if graph.hlo_text is None or not graph.expect_plan:
        return []
    plan = graph.expect_plan
    mesh = dict(plan.get("mesh") or {})
    entries = list(plan.get("collectives") or ())
    tol = int(plan.get(
        "allow_unplanned_bytes", DEFAULT_UNPLANNED_TOLERANCE
    ))
    axis_groups = mesh_axis_groups(mesh)
    actual: Dict[Tuple[str, Optional[str]], dict] = {}
    for coll in hlo_lib.collective_instructions(graph.hlo_text):
        axis = infer_collective_axis(coll, axis_groups, mesh)
        rec = actual.setdefault((coll["kind"], axis), {
            "count": 0, "bytes": 0, "dtypes": set(), "ops": [],
        })
        rec["count"] += 1
        rec["bytes"] += coll["bytes"]
        rec["dtypes"] |= coll["dtypes"]
        rec["ops"].append(coll["op_name"] or coll["name"])
    out: List[Finding] = []
    planned_keys = set()
    for entry in _credit_decomposed(entries, actual, mesh):
        key = (entry["kind"], entry.get("axis", "all"))
        planned_keys.add(key)
        got = actual.get(key, {
            "count": 0, "bytes": 0, "dtypes": set(), "ops": [],
        })
        loc = f"{key[0]}@{key[1]}"
        if "count" in entry and entry["count"] is not None \
                and got["count"] != entry["count"]:
            out.append(make_finding(
                "reshard-plan",
                path=loc,
                message=(
                    f"plan promises {entry['count']} '{key[0]}' on axis "
                    f"'{key[1]}', compiled HLO has {got['count']}"
                ),
            ))
        if _byte_bounds(entry) is not None:
            lo, hi = _byte_bounds(entry)
            if not (lo <= got["bytes"] <= hi):
                out.append(make_finding(
                    "reshard-plan",
                    path=loc,
                    message=(
                        f"'{key[0]}' on axis '{key[1]}' moves "
                        f"{got['bytes']} bytes, plan allows "
                        f"[{lo}, {hi}]"
                    ),
                ))
        if "dtypes" in entry and entry["dtypes"] is not None:
            allowed = set(entry["dtypes"])
            extra = got["dtypes"] - allowed
            if extra:
                out.append(make_finding(
                    "reshard-plan",
                    path=loc,
                    message=(
                        f"'{key[0]}' on axis '{key[1]}' payload carries "
                        f"{sorted(extra)} beyond the planned wire "
                        f"{sorted(allowed)}"
                    ),
                ))
    for key, got in actual.items():
        if key in planned_keys or got["bytes"] <= tol:
            continue
        ops = "; ".join(sorted(set(got["ops"]))[:3])
        out.append(make_finding(
            "reshard-unplanned",
            path=f"{key[0]}@{key[1]}",
            message=(
                f"{got['count']} '{key[0]}' collective(s) on axis "
                f"'{key[1]}' moving {got['bytes']} bytes that the "
                f"declared plan does not predict (from: {ops}) — a "
                "weight re-gather here means the sharding did not "
                "survive propagation"
            ),
        ))
    return out
