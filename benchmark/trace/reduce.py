"""From a profiler trace (.xplane.pb) to device busy time, per-operation
and per-program device time, and idle gaps labelled by host activity.

The benchmark's own reduction: it imports nothing from the program, so no
later PR can change how a number is read.  Times are seconds.

A TPU trace has one plane per chip (``/device:TPU:<n>``) whose lines
include ``XLA Ops`` (one event per executed HLO operation or fusion) and
``XLA Modules`` (one event per executed program), and a host plane
(``/host:CPU``) with one line per thread, where
``jax.profiler.TraceAnnotation`` spans appear under their own names.
"""

from __future__ import annotations

import dataclasses
import glob
import re
import os
from typing import Dict, List, Optional, Tuple

_OPCODE = re.compile(r" ([a-z][a-z0-9_\-]*)\(")
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_MARK = "bench/"  # the harness's own TraceAnnotation prefix


@dataclasses.dataclass
class Trace:
    #: per device: sorted [(start_s, dur_s, name)] of the operations line
    ops: Dict[str, List[Tuple[float, float, str]]]
    #: per device: [(start_s, dur_s, name)] of the programs line
    modules: Dict[str, List[Tuple[float, float, str]]]
    #: host spans the harness annotated: [(start_s, dur_s, name)]
    host: List[Tuple[float, float, str]]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                ev = sorted(
                    (e.start_ns * 1e-9, e.duration_ns * 1e-9, e.name)
                    for e in line.events
                )
                (ops if line.name == OPS_LINE else modules)[plane.name] = ev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [
                    (e.start_ns * 1e-9, e.duration_ns * 1e-9, e.name)
                    for e in line.events if e.name.startswith(HOST_MARK)
                ]
    host.sort()
    return Trace(ops=ops, modules=modules, host=host)


def _union(events) -> Tuple[float, List[Tuple[float, float]]]:
    """Total covered time and the gaps [(start, dur)] between covered
    stretches of sorted (start, dur, ...) events."""
    busy, gaps, end = 0.0, [], None
    for start, dur, *_ in events:
        stop = start + dur
        if end is None:
            busy, end = dur, stop
        elif start >= end:
            gaps.append((end, start - end))
            busy, end = busy + dur, stop
        elif stop > end:
            busy, end = busy + (stop - end), stop
    return busy, gaps


def busy_seconds(trace: Trace) -> Optional[float]:
    """Seconds in which an operation ran, averaged over the chips that
    appear in the trace.  None when no device operation was traced."""
    per = [_union(ev)[0] for ev in trace.ops.values() if ev]
    return sum(per) / len(per) if per else None


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[8,128]{...} fusion(...)`` -> ``%fusion.12 fusion
    bf16[8,128]``: the operation's own name, its opcode and its (first)
    result shape, without layouts and operands."""
    head, _, rest = name.partition(" = ")
    m, shape = _OPCODE.search(" " + rest), _SHAPE.search(rest)
    return " ".join(
        [head] + ([m.group(1)] if m else []) + ([shape.group(0)] if shape else [])
    )


def self_times(events) -> List[Tuple[float, float, str]]:
    """[(start, self_seconds, name)]: each event's duration minus the
    events nested inside it.  The operations line nests a loop's body
    inside the ``while`` that runs it, so durations alone count the body
    twice."""
    out, stack = [], []   # stack of [end, index into out]
    for start, dur, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and start >= stack[-1][0]:
            stack.pop()
        if stack:
            i = stack[-1][1]
            out[i] = (out[i][0], out[i][1] - dur, out[i][2])
        out.append((start, dur, name))
        stack.append((start + dur, len(out) - 1))
    return out


def op_seconds(trace: Trace) -> Dict[str, float]:
    """Device self-seconds by operation (short name), averaged over the
    chips."""
    out: Dict[str, float] = {}
    n = max(1, len(trace.ops))
    for ev in trace.ops.values():
        for _, dur, name in self_times(ev):
            key = short_name(name)
            out[key] = out.get(key, 0.0) + dur / n
    return out


def module_calls(trace: Trace, prefix: str) -> List[Tuple[float, float]]:
    """(start, duration) of every execution, on the first chip, of the
    programs whose name starts with ``prefix``."""
    for ev in trace.modules.values():
        return [(s, d) for s, d, name in ev if name.startswith(prefix)]
    return []


def idle_gaps(trace: Trace, top: int = 10) -> List[Tuple[str, float]]:
    """The longest gaps between device operations on the first chip, each
    named after the harness's host span that overlaps it most."""
    for ev in trace.ops.values():
        _, gaps = _union(ev)
        out = []
        for start, dur in sorted(gaps, key=lambda g: -g[1])[:top]:
            label, most = "host:unannotated", 0.0
            for hs, hd, name in trace.host:
                over = min(start + dur, hs + hd) - max(start, hs)
                if over > most:
                    label, most = name, over
            out.append((label, dur))
        return out
    return []


def top_ops(trace: Trace, top: int = 10) -> List[Tuple[str, float]]:
    return sorted(op_seconds(trace).items(), key=lambda kv: -kv[1])[:top]
