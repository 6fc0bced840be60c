"""Static peak-HBM estimate + budget gate — OOM as a lint ERROR.

An out-of-memory abort is the most expensive possible way to learn
that a plan doesn't fit: it costs a full compile, a device
allocation storm, and (on a shared pod) everyone else's queue slot.
The compiled module already contains everything needed to know
*before the first step runs*: scheduled HLO (``is_scheduled=true``)
prints instructions in execution order, every definition site carries
its result shape, and every use site names its operands — a classic
linear-scan live-range walk over that text gives a per-buffer
lifetime, and the running sum's maximum is the static peak.

The estimate is deliberately a *model*, not a byte-exact replay of
XLA's buffer assignment (which fuses allocations, colors slices, and
rematerializes): it counts

- **parameters** at their full printed (per-device shard) size, live
  from entry — params, optimizer state, the serve KV page pool
  (static shape, so the pool is budgeted exactly);
- **instruction results** (post-fusion: a fusion's interior never
  materializes, which is the point of fusing) from definition to last
  use — the activations and collective scratch;
- **zero-cost aliases** (tuples, bitcasts, get-tuple-element) at 0;
- **called computations** (while/conditional/call bodies) once,
  recursively, at their call site.

That model is an upper-ish bound on what a non-rematerializing
schedule needs and tracks XLA's own ``temp`` accounting closely
enough to gate on: the point is catching the 2x of a dropped
donation, the Nx of a silently replicated optimizer state, or a KV
pool that never fit — not the last 2%.

Surfaces: :func:`estimate_peak` (the raw estimate + top-K buffer
attribution), :func:`memory_pass` (the ``memory-budget`` lint rule —
``hbm_budget`` bytes on the :class:`~apex_tpu.analysis.passes
.StepGraph`), :func:`publish_peak` (board gauges the
:class:`~apex_tpu.observability.health.MemoryBudgetRule` watchdog
reads), ``tools/shard_report.py`` (the human-readable breakdown) and
the serve engine's build-time gate
(``ServeConfig(hbm_budget_bytes=...)``).
"""

from __future__ import annotations

import functools
import re
from typing import Dict, List, Optional

from apex_tpu.analysis import hlo as hlo_lib
from apex_tpu.analysis.findings import Finding, make_finding

__all__ = [
    "BUFFER_CATEGORIES",
    "categorize_buffer",
    "estimate_peak",
    "memory_pass",
    "pool_copy_findings",
    "publish_peak",
]

#: attribution buckets, in the order reports print them
BUFFER_CATEGORIES = (
    "params", "optimizer", "kv_cache", "inputs", "args",
    "activations", "collective", "constants",
)

#: ops whose "result" is a pointer re-labelling, not an allocation
_ALIAS_OPS = frozenset((
    "tuple", "get-tuple-element", "bitcast", "after-all", "opt-barrier",
    "domain", "parameter",  # parameters are costed separately, up front
))

_OPT_RE = re.compile(
    r"opt|adam|lamb|momentum|velocity|master|\bm\b|\bv\b|nu\b|mu\b",
    re.IGNORECASE,
)
_PARAM_RE = re.compile(
    r"param|weight|kernel|embed|wte|wpe|scale|bias|\bw\b|\bb\b",
    re.IGNORECASE,
)
_KV_RE = re.compile(r"kv|cache|pages|pool", re.IGNORECASE)
_INPUT_RE = re.compile(
    r"batch|input|tokens|ids|\bx\b|\by\b|label", re.IGNORECASE
)


def categorize_buffer(opcode: str, op_name: str) -> str:
    """One of :data:`BUFFER_CATEGORIES` for a buffer, from its opcode
    and jax path metadata.  Parameters classify by their arg-path name
    (``state/opt/...`` → optimizer, ``kv_pages`` → kv_cache, ...);
    results classify by opcode (collectives → collective scratch,
    everything else → activations)."""
    if opcode == "parameter":
        path = op_name or ""
        if _OPT_RE.search(path):
            return "optimizer"
        if _KV_RE.search(path):
            return "kv_cache"
        if _PARAM_RE.search(path):
            return "params"
        if _INPUT_RE.search(path):
            return "inputs"
        return "args"
    if opcode == "constant":
        return "constants"
    if opcode.startswith(("all-", "reduce-scatter", "collective-")):
        return "collective"
    return "activations"


def _computation_peak(comps, name, memo) -> int:
    """Peak transient bytes of one (non-entry) computation body —
    while/conditional/call interiors, recursively."""
    if name in memo:
        return memo[name]
    memo[name] = 0  # cycle guard
    instrs = comps.get(name, [])
    peak, live = 0, 0
    last_use: Dict[str, int] = {}
    for i, ins in enumerate(instrs):
        for op in ins["operand_names"]:
            last_use[op] = i
    frees: Dict[int, List[int]] = {}
    for i, ins in enumerate(instrs):
        size = 0 if ins["opcode"] in _ALIAS_OPS else \
            hlo_lib.shape_bytes(ins["shape"])
        inner = 0
        if ins["opcode"] in ("while", "conditional", "call"):
            inner = max(
                (_computation_peak(comps, c, memo) for c in ins["called"]),
                default=0,
            )
        live += size
        peak = max(peak, live + inner)
        end = last_use.get(ins["name"], i)
        frees.setdefault(end, []).append(size)
        for s in frees.pop(i, []):
            live -= s
    memo[name] = peak
    return peak


def estimate_peak(hlo_text: str, top_k: int = 10) -> dict:
    """Linear-scan live-range peak over the scheduled ENTRY computation.

    Returns ``{"peak_bytes", "peak_index", "param_bytes",
    "by_category": {category: bytes-at-peak},
    "buffers": [{"name", "bytes", "category", "op_name", "defined",
    "freed"}, ...]}`` — ``buffers`` is the top-K live AT the peak
    instruction, largest first (the attribution a budget-overflow
    finding prints).

    Memoized on the module text (small LRU): the memory pass, the
    board publish, the artifact sections, and the shard-report
    renderer all read the same compiled program — one parse serves
    them all.

    Known to over-read on TPU HLO: against XLA's own
    ``compiled.memory_analysis()`` for v5e (compile-only, ISSUE 21) it
    says 16.10 GiB where XLA says 14.43 for the BERT-Large LAMB step at
    batch 128, and 6.59 vs 5.23 GiB for the serving decode step.  The
    scan frees a buffer at its last textual use and knows nothing of
    XLA's in-place reuse inside fusions, so treat the figure as an upper
    estimate; nothing arms ``hbm_budget`` by default.
    """
    est = _estimate_peak_cached(hlo_text, top_k)
    # shallow-copy the mutable tiers so one consumer's edits can't
    # poison the cache for the next
    out = dict(est)
    out["by_category"] = dict(est["by_category"])
    out["buffers"] = [dict(b) for b in est["buffers"]]
    return out


@functools.lru_cache(maxsize=4)
def _estimate_peak_cached(hlo_text: str, top_k: int) -> dict:
    comps, entry = hlo_lib.parse_computations(hlo_text)
    instrs = comps.get(entry, [])
    aliased_params = {
        p for p, _out in hlo_lib.input_output_aliases(hlo_text)
    }
    params = {p["name"]: p for p in hlo_lib.parameter_shardings(hlo_text)}

    last_use: Dict[str, int] = {}
    for i, ins in enumerate(instrs):
        for op in ins["operand_names"]:
            last_use[op] = i
    end_idx = len(instrs) - 1

    records = []  # (name, bytes, category, op_name, defined, freed)
    for i, ins in enumerate(instrs):
        if ins["opcode"] == "parameter":
            p = params.get(ins["name"])
            size = p["bytes"] if p else hlo_lib.shape_bytes(ins["shape"])
            cat = categorize_buffer("parameter", p["op_name"] if p else "")
            # donated (aliased) parameters are reused by an output, so
            # they stay live to the end regardless of last read
            freed = end_idx if (p and p["param"] in aliased_params) \
                else last_use.get(ins["name"], end_idx)
            records.append((ins["name"], size, cat, (p or {}).get(
                "op_name", ""), i, freed))
            continue
        size = 0 if ins["opcode"] in _ALIAS_OPS else \
            hlo_lib.shape_bytes(ins["shape"])
        if size == 0 and ins["opcode"] not in (
            "while", "conditional", "call"
        ):
            continue
        freed = end_idx if ins.get("root") else \
            last_use.get(ins["name"], i)
        records.append((
            ins["name"], size, categorize_buffer(
                ins["opcode"], ins["op_name"]
            ), ins["op_name"], i, freed,
        ))

    inner_memo: Dict[str, int] = {}
    inner_at: Dict[int, int] = {}
    for i, ins in enumerate(instrs):
        if ins["opcode"] in ("while", "conditional", "call"):
            inner_at[i] = max(
                (_computation_peak(comps, c, inner_memo)
                 for c in ins["called"]),
                default=0,
            )

    allocs: Dict[int, List[int]] = {}
    frees: Dict[int, List[int]] = {}
    for ridx, (_n, size, _c, _o, defined, freed) in enumerate(records):
        allocs.setdefault(defined, []).append(ridx)
        frees.setdefault(freed, []).append(ridx)
    live_set: set = set()
    live, peak, peak_idx, peak_set = 0, 0, 0, set()
    for i in range(len(instrs)):
        for ridx in allocs.get(i, []):
            live += records[ridx][1]
            live_set.add(ridx)
        here = live + inner_at.get(i, 0)
        if here > peak:
            peak, peak_idx, peak_set = here, i, set(live_set)
        for ridx in frees.get(i, []):
            live -= records[ridx][1]
            live_set.discard(ridx)

    by_cat: Dict[str, int] = {}
    at_peak = sorted(
        (records[r] for r in peak_set), key=lambda r: -r[1]
    )
    for _n, size, cat, _o, _d, _f in at_peak:
        by_cat[cat] = by_cat.get(cat, 0) + size
    return {
        "peak_bytes": int(peak),
        "peak_index": int(peak_idx),
        "param_bytes": int(sum(p["bytes"] for p in params.values())),
        "by_category": by_cat,
        "buffers": [
            {
                "name": n, "bytes": int(s), "category": c,
                "op_name": o, "defined": d, "freed": f,
            }
            for n, s, c, o, d, f in at_peak[:top_k]
        ],
    }


def memory_pass(graph) -> List[Finding]:
    """The budget gate: when the :class:`StepGraph` carries an
    ``hbm_budget`` (bytes), a static peak above it is a
    ``memory-budget`` ERROR naming the top live buffers — OOM caught
    at lint time, with attribution, instead of at step 0 with a stack
    trace."""
    out = pool_copy_findings(graph)
    if graph.hlo_text is None or graph.hbm_budget is None:
        return out
    budget = int(graph.hbm_budget)
    est = estimate_peak(graph.hlo_text)
    if est["peak_bytes"] <= budget:
        return out
    top = ", ".join(
        f"{b['category']}:{b['name']}={b['bytes'] / (1 << 20):.1f}MiB"
        for b in est["buffers"][:5]
    )
    return out + [make_finding(
        "memory-budget",
        path=f"instruction #{est['peak_index']}",
        message=(
            f"static peak HBM {est['peak_bytes'] / (1 << 20):.1f} MiB "
            f"exceeds the {budget / (1 << 20):.1f} MiB budget "
            f"(top live buffers: {top})"
        ),
    )]


#: ops that rewrite part of their first operand where it lies — the
#: only way a serving program may produce a pool-shaped result
_IN_PLACE_OPS = frozenset(("scatter", "dynamic-update-slice"))


def _dims_key(dims) -> tuple:
    """Shape identity up to transposition and unit dims."""
    return tuple(sorted(int(d) for d in dims if int(d) != 1))


def pool_copy_findings(graph) -> List[Finding]:
    """The KV pool's one-buffer gate: a program that takes the serving
    pool (``graph.expect_pool["shapes"]``, each ``(L, P, R, page,
    W)``) may materialize nothing the shape of the pool or of one
    layer of it, except by updating it in place.

    Every pool-sized relayout (``copy``), every layer-sized slice a
    scan makes of an ``xs`` pool and the restacked ``ys`` buffer is an
    instruction of the compiled module with such a result; the
    page-granular writes of ``serve/cache.py`` are ``scatter`` /
    ``dynamic-update-slice`` (fusions), which XLA performs on the
    donated buffer.  Shapes compare up to transposition and unit
    dims, whatever the element type (a relayout may transpose, an
    upcast may widen).  XLA's total ``temp_size_in_bytes`` cannot
    stand in for this: a step legitimately holds weight casts and
    sampler activations far larger than a layer of the pool."""
    want = graph.expect_pool
    if graph.hlo_text is None or not want:
        return []
    targets = set()
    for shape in want["shapes"]:
        targets |= {_dims_key(shape), _dims_key(shape[1:])}
    comps, _entry = hlo_lib.parse_computations(graph.hlo_text)
    fused = {
        c for instrs in comps.values() for ins in instrs
        if ins["opcode"] == "fusion" for c in ins["called"]
    }

    def pool_shaped(ins):
        return _dims_key(hlo_lib.shape_dims(ins["shape"])) in targets

    def in_place(ins):
        if ins["opcode"] in _IN_PLACE_OPS:
            return True
        return ins["opcode"] == "fusion" and any(
            inner["opcode"] in _IN_PLACE_OPS and pool_shaped(inner)
            for c in ins["called"] for inner in comps.get(c, [])
        )

    held = [
        ins
        for name, instrs in comps.items() if name not in fused
        for ins in instrs
        if ins["opcode"] not in _ALIAS_OPS
        and ins["opcode"] not in ("while", "conditional", "call")
        and pool_shaped(ins) and not in_place(ins)
    ]
    if not held:
        return []
    total = sum(hlo_lib.shape_bytes(ins["shape"]) for ins in held)
    shown = ", ".join(
        f"%{ins['name']} = {ins['shape'].split('{')[0]} {ins['opcode']}"
        for ins in held[:4]
    )
    return [make_finding(
        "memory-pool-copy",
        path=f"%{held[0]['name']}",
        severity=want.get("severity"),
        message=(
            f"{len(held)} instruction(s) materialize "
            f"{total / (1 << 20):.1f} MiB shaped like the KV pool or "
            f"one layer of it: {shown}"
        ),
    )]


def publish_peak(est: dict, prefix: str = "analysis") -> None:
    """Gauge a peak estimate onto the observability board
    (``analysis/peak_hbm_bytes`` + per-category breakdown) — the
    source the :class:`~apex_tpu.observability.health
    .MemoryBudgetRule` watchdog judges, and one more section of the
    ``--metrics-out`` JSONL."""
    try:
        from apex_tpu.observability.metrics import board
    except ImportError:  # pragma: no cover - partial install
        return
    board.set(f"{prefix}/peak_hbm_bytes", est["peak_bytes"])
    for cat, size in est["by_category"].items():
        board.set(f"{prefix}/peak_hbm/{cat}", size)
