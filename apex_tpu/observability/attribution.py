"""Step-time attribution — where a compiled training step spends its
time, decomposed into compute / collective / host-stall fractions and
per-op-category buckets, from TWO sources that must agree:

1. **The compiled cost model** (:func:`attribute_cost_model`): every
   instruction of the optimized HLO text, costed by the shape
   arithmetic in :mod:`apex_tpu.analysis.hlo` (the repo's one HLO
   reader) and bucketed by :func:`~apex_tpu.observability.meter.
   categorize_op` into matmul / attention / norm-elementwise /
   collective / other.  Static: it knows FLOPs and bytes exactly but
   estimates time (a two-resource roofline per op), and it cannot see
   the host — its host-stall fraction is always 0.

2. **A measured profiler trace** (:func:`attribute_trace`): the
   trace-event JSON a :class:`~apex_tpu.observability.trace.
   TraceScheduler` window (or ``bench.py --trace``) already captures,
   parsed into the same buckets — per-op device events on TPU/GPU
   ("XLA Ops" tracks) or the per-thunk spans the CPU runtime emits.
   Measured: it knows time exactly, including the gaps no op accounts
   for (host stall: dispatch latency, blocked fetches, input waits).
   The stall is split by the innermost host annotation under each gap
   (the serving loop's ``serve/*`` / ``engine/*`` phases, which
   :meth:`~apex_tpu.observability.spans.SpanRecorder.phase` writes
   into the trace's host plane on the device trace's clock).

Where both exist, disagreement IS the finding: a measured collective
fraction far above the cost model's means the overlap the schedule
promised did not happen; a large host-stall fraction means the chip is
starving, not slow.  :func:`roofline_report` turns the merged view into
a per-bucket roofline (achieved FLOP/s vs the
:mod:`~apex_tpu.observability.meter` peak table, arithmetic intensity
vs the ridge point, compute- vs bandwidth-bound verdict), and
:func:`publish_attribution` lands the fractions on the observability
board — where :class:`~apex_tpu.observability.health.
CollectiveFractionRule` / :class:`~apex_tpu.observability.health.
HostStallRule` watch them.

Surfaces: ``tools/step_profile.py`` (the workflow entry),
``tools/trace_summary.py --attribution``, and the resilient example,
which attributes any captured trace window on exit.  See
``docs/observability.md`` ("Attribution & roofline").
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Dict, List, Mapping, NamedTuple, Optional, Sequence, Tuple

from apex_tpu.observability.meter import (
    BUCKETS,
    categorize_op,
    peak_flops_for,
    peak_hbm_bandwidth_for,
    peak_ici_bandwidth_for,
)

__all__ = [
    "OpCost",
    "CostAttribution",
    "TraceAttribution",
    "RooflineRow",
    "attribute_cost_model",
    "attribute_trace",
    "attribute_trace_dir",
    "trace_step_period",
    "hlo_bucket_map",
    "roofline_report",
    "render_roofline",
    "publish_attribution",
]

#: top-level fraction keys — always sum to 1.0 (compute aggregates the
#: non-collective busy buckets)
FRACTION_KEYS = ("compute", "collective", "host_stall")


class OpCost(NamedTuple):
    """One entry-reachable instruction's modeled cost."""

    name: str
    opcode: str
    op_name: str
    bucket: str
    flops: float
    bytes: int


# ---------------------------------------------------------------------------
# source (a): the compiled cost model
# ---------------------------------------------------------------------------


class CostAttribution:
    """Bucketed FLOPs/bytes/estimated-time from optimized HLO text."""

    def __init__(self, ops: List[OpCost], peak_flops: float,
                 hbm_bw: float, ici_bw: float):
        self.ops = ops
        self.peak_flops = peak_flops
        self.hbm_bw = hbm_bw
        self.ici_bw = ici_bw
        self.buckets: Dict[str, Dict[str, float]] = {
            b: {"flops": 0.0, "bytes": 0.0, "est_time": 0.0}
            for b in BUCKETS
        }
        for op in ops:
            rec = self.buckets[op.bucket]
            rec["flops"] += op.flops
            rec["bytes"] += op.bytes
            if op.bucket == "collective":
                rec["est_time"] += op.bytes / ici_bw
            else:
                rec["est_time"] += max(
                    op.flops / peak_flops, op.bytes / hbm_bw
                )

    @property
    def total_flops(self) -> float:
        return sum(b["flops"] for b in self.buckets.values())

    @property
    def total_bytes(self) -> float:
        return sum(b["bytes"] for b in self.buckets.values())

    @property
    def est_step_time(self) -> float:
        """Roofline lower bound on the step (serial sum of per-op
        maxima — real schedules overlap, so achieved time ≥ this)."""
        return sum(b["est_time"] for b in self.buckets.values())

    def bucket_fractions(self) -> Dict[str, float]:
        """Each bucket's share of the estimated busy time."""
        total = self.est_step_time
        if total <= 0:
            return {b: 0.0 for b in BUCKETS}
        return {b: self.buckets[b]["est_time"] / total for b in BUCKETS}

    def fractions(self) -> Dict[str, float]:
        """compute/collective/host_stall (host_stall is always 0 here:
        the compiled program cannot see the host)."""
        shares = self.bucket_fractions()
        coll = shares.get("collective", 0.0)
        return {
            "compute": 1.0 - coll if self.est_step_time > 0 else 0.0,
            "collective": coll,
            "host_stall": 0.0,
        }

    def bucket_map(self) -> Dict[str, str]:
        """Instruction name → bucket — the join map the trace parser
        uses to bucket profiler rows by op metadata the trace itself
        does not carry.  Keys are the RAW instruction names (the
        ``p<i>/``/while-path prefixes :func:`attribute_cost_model`
        stamps for display are stripped; trace events use raw names)."""
        return {op.name.rsplit("/", 1)[-1]: op.bucket for op in self.ops}


def _bucket_container(instr: dict, child_costs: List[OpCost]) -> str:
    """A fusion/call's bucket: its own metadata first (XLA stamps the
    root op's path there), else the dominant-FLOPs child, else the
    dominant-bytes child."""
    own = categorize_op(instr["opcode"], instr["op_name"])
    if own != "other":
        return own
    if child_costs:
        best = max(child_costs, key=lambda c: (c.flops, c.bytes))
        if best.flops > 0 or best.bytes > 0:
            return best.bucket
    return "other"


def _walk_computation(comps, name, out: List[OpCost], seen: set,
                      label_prefix: str = "") -> Tuple[float, int]:
    """Collect entry-reachable op costs; returns (flops, bytes) of the
    computation for container accounting.  Containers:

    - ``fusion``/``call``: ONE OpCost — FLOPs summed over the interior,
      bytes = the boundary shapes only (the interior never touches
      HBM: that is the point of fusing).
    - ``while``/``conditional``: the body's ops appended individually
      (each interior fusion is its own HBM round-trip).  Bodies count
      ONCE — trip counts are not in the text, and attribution consumes
      relative shares, which a homogeneous loop body preserves.
    """
    from apex_tpu.analysis import hlo as H

    if name in seen or name not in comps:
        return 0.0, 0
    seen = seen | {name}
    flops_total, bytes_total = 0.0, 0
    for instr in comps[name]:
        opcode = instr["opcode"]
        if opcode in ("fusion", "call"):
            sub: List[OpCost] = []
            f = 0.0
            for called in instr["called"]:
                cf, _cb = _walk_computation(
                    comps, called, sub, seen, label_prefix
                )
                f += cf
            # interior ops collapse into the one fused kernel
            boundary = H.instruction_bytes(instr)
            cost = OpCost(
                label_prefix + instr["name"], opcode, instr["op_name"],
                _bucket_container(instr, sub), f, boundary,
            )
            out.append(cost)
            flops_total += f
            bytes_total += boundary
            continue
        if opcode in ("while", "conditional"):
            for called in instr["called"]:
                cf, cb = _walk_computation(
                    comps, called, out, seen,
                    label_prefix + instr["name"] + "/",
                )
                flops_total += cf
                bytes_total += cb
            continue
        if opcode.endswith("-done"):
            continue  # async pairs cost once, at -start
        f = H.instruction_flops(instr)
        b = H.instruction_bytes(instr)
        if opcode.startswith(tuple(H.COLLECTIVE_KINDS)):
            # result shape only (the wire payload); -start tuples keep
            # the result element, matching collective_summary
            shape = instr["shape"]
            if opcode.endswith("-start"):
                shape = H.async_start_result(shape)
            b = H.shape_bytes(shape)
        if f == 0.0 and b == 0:
            continue  # parameters/constants/bookkeeping: invisible
        out.append(OpCost(
            label_prefix + instr["name"], opcode, instr["op_name"],
            categorize_op(opcode, instr["op_name"]), f, b,
        ))
        flops_total += f
        bytes_total += b
    return flops_total, bytes_total


def attribute_cost_model(
    hlo_texts,
    *,
    device_kind: Optional[str] = None,
    peak_flops: Optional[float] = None,
    hbm_bw: Optional[float] = None,
    ici_bw: Optional[float] = None,
) -> CostAttribution:
    """Bucketed cost attribution of one or more optimized-HLO texts
    (pass every program a step dispatches — e.g. the resilient
    example's ``compute_grads`` + ``apply_update`` — and their costs
    merge into one step model).  Peaks default from the
    :mod:`~apex_tpu.observability.meter` table for ``device_kind``
    (default: the first visible device); a kind the table does not
    know raises :class:`~apex_tpu.observability.meter.UnknownDeviceError`
    — off the chip, name the chip being modelled."""
    from apex_tpu.analysis import hlo as H

    if isinstance(hlo_texts, str):
        hlo_texts = [hlo_texts]
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    peak_flops = peak_flops or peak_flops_for(device_kind)
    hbm_bw = hbm_bw or peak_hbm_bandwidth_for(device_kind)
    ici_bw = ici_bw or peak_ici_bandwidth_for(device_kind)

    ops: List[OpCost] = []
    for i, text in enumerate(hlo_texts):
        comps, entry = H.parse_computations(text)
        if entry is None:
            continue
        prefix = f"p{i}/" if len(hlo_texts) > 1 else ""
        _walk_computation(comps, entry, ops, set(), prefix)
    return CostAttribution(ops, peak_flops, hbm_bw, ici_bw)


def hlo_bucket_map(hlo_texts) -> Dict[str, str]:
    """Instruction name → bucket straight from HLO text(s) — for
    callers that only hold the text (``tools/trace_summary.py
    --attribution --hlo``).  Callers that already paid
    :func:`attribute_cost_model` should use
    :meth:`CostAttribution.bucket_map` instead of re-parsing."""
    return attribute_cost_model(
        hlo_texts, device_kind="", peak_flops=1.0, hbm_bw=1.0, ici_bw=1.0
    ).bucket_map()


# ---------------------------------------------------------------------------
# source (b): the measured profiler trace
# ---------------------------------------------------------------------------

#: trace-event names that wrap whole regions (counting them would
#: double-count every child) — same exclusions tools/trace_summary.py
#: applies
_WRAPPER_PREFIXES = ("while", "jit_", "body", "condition", "region")

#: an HLO-instruction-shaped event name: "dot.4", "fusion.123",
#: "tanh.5.clone", "all-reduce-start.1", or a bare opcode like
#: "reduce-window"
_OP_EVENT_RE = re.compile(r"^[A-Za-z][\w-]*(\.\d+)+(\.clone)?$|^[a-z][a-z-]+$")

#: bookkeeping/event names on op-bearing threads that are NOT ops
_NON_OP_NAMES = (
    "ThreadpoolListener", "ThunkExecutor", "TfrtCpu", "ParseArguments",
    "Await", "start_trace", "stop_trace", "Execute", "callback",
)

#: spans that mark "the executable was running" when no per-op events
#: exist at all (last-resort busy signal; buckets then come from the
#: cost model's weights)
_EXECUTOR_NAMES = (
    "TfrtCpuExecutable::Execute", "ThunkExecutor::Execute", "ExecuteHelper",
)


class TraceAttribution:
    """Measured per-bucket time + host-stall from trace-event JSON.

    ``bucket_ms`` sums op durations per bucket (parallel tracks may
    overlap, so the sum can exceed wall coverage — fractions normalize
    by share, not by wall).  ``span_ms`` is first-op-start to
    last-op-end; ``stall_ms`` is the part of the span no op interval
    covers (merged-union gaps): dispatch latency, host sync points,
    input waits — the time the program paid that no kernel explains.
    ``stall_by_phase_ms`` splits ``stall_ms`` by the innermost host
    annotation open during each gap (``"unannotated"`` for the rest).
    """

    def __init__(self, bucket_ms: Dict[str, float], span_ms: float,
                 covered_ms: float, events: int,
                 source: str = "device-ops",
                 stall_by_phase_ms: Optional[Mapping[str, float]] = None):
        self.bucket_ms = {b: bucket_ms.get(b, 0.0) for b in BUCKETS}
        self.span_ms = span_ms
        self.covered_ms = min(covered_ms, span_ms) if span_ms > 0 else 0.0
        self.events = events
        self.source = source
        self.stall_by_phase_ms = dict(stall_by_phase_ms or {})

    @property
    def busy_ms(self) -> float:
        return sum(self.bucket_ms.values())

    @property
    def stall_ms(self) -> float:
        return max(0.0, self.span_ms - self.covered_ms)

    def bucket_fractions(self) -> Dict[str, float]:
        """Each bucket's share of measured busy time."""
        busy = self.busy_ms
        if busy <= 0:
            return {b: 0.0 for b in BUCKETS}
        return {b: t / busy for b, t in self.bucket_ms.items()}

    def fractions(self) -> Dict[str, float]:
        """compute / collective / host_stall, summing to 1.0: the stall
        share is measured from coverage gaps, and the busy remainder
        splits across buckets by their share of summed op time."""
        if self.span_ms <= 0:
            return {"compute": 0.0, "collective": 0.0, "host_stall": 0.0}
        stall = self.stall_ms / self.span_ms
        shares = self.bucket_fractions()
        coll = shares.get("collective", 0.0) * (1.0 - stall)
        return {
            "compute": max(0.0, 1.0 - stall - coll),
            "collective": coll,
            "host_stall": stall,
        }

    def bucket_time_fractions(self) -> Dict[str, float]:
        """Per-bucket share of the SPAN (busy shares scaled by
        1 − stall) — what the roofline uses to turn a measured step
        time into per-bucket seconds."""
        fr = self.fractions()
        busy_share = 1.0 - fr["host_stall"]
        return {
            b: s * busy_share for b, s in self.bucket_fractions().items()
        }


def _merged_coverage(
    intervals: List[Tuple[float, float]],
    gaps: Optional[List[Tuple[float, float]]] = None,
) -> float:
    """Total length of the union of [start, end) intervals; the
    uncovered stretches between them are appended to ``gaps``."""
    if not intervals:
        return 0.0
    intervals.sort()
    total = 0.0
    cur_s, cur_e = intervals[0]
    for s, e in intervals[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            if gaps is not None:
                gaps.append((cur_e, s))
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    total += cur_e - cur_s
    return total


#: host annotations the stall is split by — the serving loop's phases
HOST_PHASE_PREFIXES = ("serve/", "engine/")
UNANNOTATED = "unannotated"


def _innermost_segments(
    spans: List[Tuple[float, float, str]]
) -> List[Tuple[float, float, str]]:
    """Nested ``(start, end, name)`` spans flattened to disjoint
    segments, each named after the innermost span open over it (a
    span's self time).  A span that outlives the one around it is
    clipped to it."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []   # (end, name)
    cursor = 0.0

    def close_until(t: float) -> None:
        nonlocal cursor
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > cursor:
                out.append((cursor, end, name))
                cursor = end

    for start, end, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        close_until(start)
        if stack:
            if start > cursor:
                out.append((cursor, start, stack[-1][1]))
            end = min(end, stack[-1][0])
        cursor = max(cursor, start)
        stack.append((end, name))
    close_until(float("inf"))
    return out


def _stall_by_phase(
    trace: Mapping, gaps: List[Tuple[float, float]]
) -> Dict[str, float]:
    """Milliseconds of ``gaps`` (device-idle stretches, trace µs) under
    each innermost host annotation whose name starts with one of
    :data:`HOST_PHASE_PREFIXES`; what no such annotation covers is
    ``unannotated``."""
    spans = [
        (float(e["ts"]), float(e["ts"]) + float(e["dur"]), e["name"])
        for e in trace.get("traceEvents", [])
        if e.get("ph") == "X" and e.get("dur")
        and str(e.get("name", "")).startswith(HOST_PHASE_PREFIXES)
    ]
    segs = _innermost_segments(spans)
    out: Dict[str, float] = {}
    i = 0
    for g0, g1 in gaps:
        while i < len(segs) and segs[i][1] <= g0:
            i += 1
        named, j = 0.0, i
        while j < len(segs) and segs[j][0] < g1:
            over = min(g1, segs[j][1]) - max(g0, segs[j][0])
            if over > 0:
                out[segs[j][2]] = out.get(segs[j][2], 0.0) + over / 1e3
                named += over
            j += 1
        rest = (g1 - g0) - named
        if rest > 0:
            out[UNANNOTATED] = out.get(UNANNOTATED, 0.0) + rest / 1e3
    return out


def _event_is_op(name: str, hlo_map: Optional[Mapping[str, str]]) -> bool:
    if not name or name[0] in "$<" or " " in name or "::" in name:
        return False
    if name.startswith(_WRAPPER_PREFIXES) or name.isdigit():
        return False
    if any(t in name for t in _NON_OP_NAMES):
        return False
    base = name[:-6] if name.endswith(".clone") else name
    if hlo_map and (name in hlo_map or base in hlo_map):
        return True
    return bool(_OP_EVENT_RE.match(name))


def _bucket_event(name: str, hlo_map: Optional[Mapping[str, str]]) -> str:
    if hlo_map:
        hit = hlo_map.get(name) or hlo_map.get(
            name[:-6] if name.endswith(".clone") else name
        )
        if hit:
            return hit
    # heuristic: the leading token is the opcode ("dot.4"), and fused
    # kernel names carry their content ("add_multiply_fusion.78")
    lead = re.split(r"[._]", name, 1)[0]
    return categorize_op(lead, name)


def _select_op_events(
    trace: Mapping, hlo_map: Optional[Mapping[str, str]]
) -> Tuple[List[dict], str]:
    """The shared event-selection pass, in preference order:

    1. per-op events on device "XLA Ops" tracks (TPU/GPU profiles);
    2. per-op events anywhere (the CPU thunk runtime names its spans by
       HLO instruction — ``dot.4``, ``tanh.5.clone``), filtered by
       ``hlo_map`` membership or the instruction-name shape;
    3. bare executor spans (no per-op names at all).
    """
    events = trace.get("traceEvents", [])
    pnames: Dict[int, str] = {}
    tnames: Dict[Tuple[int, Optional[int]], str] = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pnames[e["pid"]] = e["args"].get("name", "")
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            tnames[(e["pid"], e.get("tid"))] = e["args"].get("name", "")
    device_pids = {
        pid for pid, name in pnames.items()
        if "TPU" in name or "GPU" in name or "device" in name.lower()
    }
    op_tids = {
        key for key, name in tnames.items()
        if key[0] in device_pids and "Ops" in name
    }

    def _select(pred):
        out = []
        for e in events:
            if e.get("ph") != "X" or not e.get("dur"):
                continue
            if not pred(e):
                continue
            out.append(e)
        return out

    selected = _select(
        lambda e: (e.get("pid"), e.get("tid")) in op_tids
        and _event_is_op(e.get("name", ""), hlo_map)
    ) if op_tids else []
    if selected:
        return selected, "device-ops"
    selected = _select(lambda e: _event_is_op(e.get("name", ""), hlo_map))
    if selected:
        return selected, "thunk-spans"
    return _select(
        lambda e: any(x in e.get("name", "") for x in _EXECUTOR_NAMES)
    ), "executor-spans"


def trace_step_period(
    trace: Mapping, *, hlo_map: Optional[Mapping[str, str]] = None
) -> float:
    """Robust per-step seconds measured from the TRACE's own clock.

    A profiled loop dispatches the same program every step, so every
    instruction's events recur once per step: the median period between
    consecutive occurrences of the same op name IS the step time —
    immune to the host clock, and (being a median over every op's every
    period) to one-off anomalies like the profiler's first-capture
    overhead.  Returns 0.0 when no op recurs (a single-step window)."""
    selected, _src = _select_op_events(trace, hlo_map)
    by_name: Dict[str, List[float]] = {}
    for e in selected:
        by_name.setdefault(e.get("name", ""), []).append(
            float(e.get("ts", 0.0))
        )
    periods: List[float] = []
    for times in by_name.values():
        if len(times) < 2:
            continue
        times.sort()
        periods.extend(b - a for a, b in zip(times, times[1:]))
    if not periods:
        return 0.0
    periods.sort()
    return periods[len(periods) // 2] / 1e6  # us -> s


def attribute_trace(
    trace: Mapping,
    *,
    hlo_map: Optional[Mapping[str, str]] = None,
    cost_weights: Optional[Mapping[str, float]] = None,
) -> TraceAttribution:
    """Bucketed time attribution of one loaded trace-event JSON dict.

    Event selection: :func:`_select_op_events` (device "XLA Ops"
    tracks, then CPU per-thunk spans, then bare executor spans).  In
    the executor-span fallback busy/stall is still measured and the
    busy split falls back to ``cost_weights`` (the cost model's bucket
    shares) — pass them whenever available so the degraded mode stays
    attributed.

    The gaps between device operations are split by the innermost host
    annotation (``serve/*``, ``engine/*``: the serving loop's phases)
    open over them: ``stall_by_phase_ms`` on the result, summing to
    ``stall_ms``.
    """
    selected, source = _select_op_events(trace, hlo_map)
    bucket_ms: Dict[str, float] = {b: 0.0 for b in BUCKETS}
    intervals: List[Tuple[float, float]] = []
    tmin, tmax = float("inf"), float("-inf")
    for e in selected:
        ts, dur = float(e.get("ts", 0.0)), float(e.get("dur", 0.0))
        intervals.append((ts, ts + dur))
        tmin, tmax = min(tmin, ts), max(tmax, ts + dur)
        if source == "executor-spans":
            continue  # bucketed below from cost weights
        bucket_ms[_bucket_event(e.get("name", ""), hlo_map)] += dur / 1e3

    span_ms = (tmax - tmin) / 1e3 if tmax > tmin else 0.0
    gaps: List[Tuple[float, float]] = []
    covered_ms = _merged_coverage(intervals, gaps) / 1e3
    if source == "executor-spans" and covered_ms > 0:
        weights = dict(cost_weights or {"other": 1.0})
        wsum = sum(weights.values()) or 1.0
        for b in BUCKETS:
            bucket_ms[b] = covered_ms * weights.get(b, 0.0) / wsum
    return TraceAttribution(
        bucket_ms, span_ms, covered_ms, len(selected), source,
        _stall_by_phase(trace, gaps),
    )


def load_trace_dir(log_dir: str) -> dict:
    """Newest ``*.trace.json.gz`` under a profile dir, parsed."""
    paths = glob.glob(
        os.path.join(log_dir, "**", "*.trace.json.gz"), recursive=True
    )
    if not paths:
        raise FileNotFoundError(f"no *.trace.json.gz under {log_dir}")
    with gzip.open(max(paths, key=os.path.getmtime), "rt") as f:
        return json.load(f)


def attribute_trace_dir(log_dir: str, **kwargs) -> TraceAttribution:
    """:func:`attribute_trace` over the newest capture in a profile
    dir (a TraceScheduler window dir or a ``--trace`` dir)."""
    return attribute_trace(load_trace_dir(log_dir), **kwargs)


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------


class RooflineRow(NamedTuple):
    bucket: str
    flops: float
    bytes: float
    time_ms: float
    achieved_tflops: float  # flops / time
    pct_peak: float  # achieved / peak
    intensity: float  # flops / byte
    bound: str  # "compute" | "bandwidth" | "comm" | "-"


def roofline_report(
    cost: CostAttribution,
    *,
    step_time_s: float,
    measured: Optional[TraceAttribution] = None,
) -> List[RooflineRow]:
    """Per-bucket roofline rows + a ``total`` row whose ``pct_peak`` is
    the step's MFU on the SAME peak table as
    :class:`~apex_tpu.observability.meter.StepMeter` (one denominator,
    by construction).  Bucket times come from the measured trace's
    shares of ``step_time_s`` when available, else from the cost
    model's estimated shares; FLOPs/bytes always come from the cost
    model (the trace cannot count them)."""
    ridge = cost.peak_flops / cost.hbm_bw  # FLOP/byte at the roof corner
    shares = (
        measured.bucket_time_fractions()
        if measured is not None and measured.busy_ms > 0
        else cost.bucket_fractions()
    )
    rows: List[RooflineRow] = []
    for b in BUCKETS:
        f = cost.buckets[b]["flops"]
        by = cost.buckets[b]["bytes"]
        t = shares.get(b, 0.0) * step_time_s
        if f == 0 and by == 0 and t == 0:
            continue
        ai = f / by if by else 0.0
        if b == "collective":
            bound = "comm"
        elif f == 0:
            bound = "bandwidth"
        else:
            bound = "compute" if ai >= ridge else "bandwidth"
        achieved = f / t if t > 0 else 0.0
        rows.append(RooflineRow(
            b, f, by, t * 1e3, achieved / 1e12,
            achieved / cost.peak_flops, ai, bound,
        ))
    total_t = step_time_s
    achieved = cost.total_flops / total_t if total_t > 0 else 0.0
    rows.append(RooflineRow(
        "total", cost.total_flops, cost.total_bytes, total_t * 1e3,
        achieved / 1e12, achieved / cost.peak_flops,
        cost.total_flops / cost.total_bytes if cost.total_bytes else 0.0,
        "-",
    ))
    return rows


def render_roofline(rows: Sequence[RooflineRow]) -> str:
    """The terminal table (ridge/bound verdicts inline)."""
    out = [
        f"{'bucket':<18} {'GFLOP':>10} {'MiB':>9} {'time_ms':>9} "
        f"{'TFLOP/s':>9} {'%peak':>7} {'FLOP/B':>8}  bound"
    ]
    for r in rows:
        out.append(
            f"{r.bucket:<18} {r.flops / 1e9:>10.2f} "
            f"{r.bytes / 2**20:>9.1f} {r.time_ms:>9.3f} "
            f"{r.achieved_tflops:>9.3f} {100 * r.pct_peak:>6.2f}% "
            f"{r.intensity:>8.1f}  {r.bound}"
        )
    return "\n".join(out)


# ---------------------------------------------------------------------------
# publication (board + Reporter sinks) — what the watchdog rules read
# ---------------------------------------------------------------------------


def publish_attribution(
    attr,
    *,
    reporter=None,
    step: int = 0,
    prefix: str = "attribution",
) -> Dict[str, float]:
    """Land an attribution's fractions on the observability board
    (``attribution/<key>_fraction``, ``attribution/bucket/<name>``) and
    — when a :class:`~apex_tpu.observability.export.Reporter` is
    passed — as bench-schema lines on its sinks.  Returns the
    fraction dict.  :class:`~apex_tpu.observability.health.
    CollectiveFractionRule` / ``HostStallRule`` read these keys."""
    from apex_tpu.observability.metrics import board

    fractions = attr.fractions() if hasattr(attr, "fractions") else dict(attr)
    records = {}
    for key in FRACTION_KEYS:
        val = float(fractions.get(key, 0.0))
        board.set(f"{prefix}/{key}_fraction", val)
        records[f"{prefix}/{key}_fraction"] = val
    if hasattr(attr, "bucket_fractions"):
        for b, share in attr.bucket_fractions().items():
            board.set(f"{prefix}/bucket/{b}", float(share))
            records[f"{prefix}/bucket/{b}"] = float(share)
    if reporter is not None:
        from apex_tpu.observability.export import bench_record

        for name, val in records.items():
            rec = bench_record(
                name, val, "fraction of step time", None, step=int(step)
            )
            for sink in reporter.sinks:
                sink.write(rec)
    return {k: records[f"{prefix}/{k}_fraction"] for k in FRACTION_KEYS}
