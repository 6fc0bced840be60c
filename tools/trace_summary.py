"""Summarize a jax.profiler trace directory: top device ops by total time.

Usage: python tools/trace_summary.py /tmp/trace_dir [-n 30]

Parses the Perfetto ``*.trace.json.gz`` the profiler writes and aggregates
wall time per event name on the device tracks, so the 0.4x-MFU question
("where do the milliseconds go?") has a terminal-native answer — no
TensorBoard needed in this environment.

``--flight flight_<ts>.json`` cross-references a flight-recorder dump
(``tools/flight_view.py``, ``docs/observability.md``) against the
scheduled-trace windows under the dir: it prints which windows overlap
the incident's step span and summarizes the latest overlapping one —
"was anything profiling when it died, and what did the chip do?".

``--attribution`` additionally runs the step-time attribution layer
(``apex_tpu.observability.attribution``, docs/observability.md
"Attribution & roofline") over the chosen window: bucket fractions
(matmul/attention/norm-elementwise/collective/other), the
compute/collective/host-stall split, and — with ``--hlo`` — cost-model
exact bucketing of every fused op.  ``tools/step_profile.py`` is the
full workflow (profile + roofline + watchdog); this flag answers the
same question for a trace that already exists.
"""

from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os


def list_windows(log_dir: str):
    """``[(start, end, path)]`` of the scheduled-trace windows under
    ``log_dir`` (the ``steps_<start>_<end>/`` TraceScheduler layout),
    numerically sorted."""
    import re

    windows = []
    if os.path.isdir(log_dir):
        for name in sorted(os.listdir(log_dir)):
            m = re.match(r"steps_(\d+)_(\d+)$", name)
            if m:
                windows.append(
                    (int(m.group(1)), int(m.group(2)),
                     os.path.join(log_dir, name))
                )
    windows.sort()
    return windows


def flight_step_range(path: str) -> tuple[int, int]:
    """The incident's step span from a flight-recorder dump: min..max
    over the ring frames (replay passes rewind steps, so min can sit
    well below the crash step — that is the span worth profiling)."""
    with open(path) as f:
        data = json.load(f)
    steps = [f["step"] for f in data.get("frames", ())
             if isinstance(f.get("step"), int)]
    final = data.get("final") or {}
    if isinstance(final.get("fetched_step"), int):
        steps.append(final["fetched_step"])
    if not steps:
        raise SystemExit(f"{path}: flight dump has no step frames")
    return min(steps), max(steps)


def cross_reference_flight(log_dir: str, flight_path: str) -> str | None:
    """Print which trace windows overlap the flight dump's incident
    span; returns the latest overlapping window's path (None when no
    window overlaps)."""
    lo, hi = flight_step_range(flight_path)
    windows = list_windows(log_dir)
    print(f"flight incident span: steps {lo}..{hi} ({flight_path})")
    if not windows:
        print(f"no steps_*_* trace windows under {log_dir}")
        return None
    hit = None
    for s, e, path in windows:
        overlap = s <= hi and e >= lo
        mark = "OVERLAPS incident" if overlap else "outside"
        print(f"  window {s}..{e}: {mark}")
        if overlap:
            hit = path
    if hit is None:
        print("no trace window overlaps the incident — nothing was "
              "profiling when it happened (arm APEX_TPU_TRACE_STEPS or "
              "a health-escalation window next run)")
    return hit


def resolve_window(log_dir: str, step: int | None = None) -> str:
    """Resolve a scheduled-trace base dir to one capture window.

    ``apex_tpu.observability.trace.TraceScheduler`` writes each armed
    window to ``<base>/steps_<start>_<end>/``; given the base dir this
    lists the windows and picks the one containing ``--step`` (default:
    the latest).  A dir without window children passes through
    unchanged, so plain ``bench.py --trace`` dirs keep working.
    """
    # numeric order (via list_windows) — lexicographic listdir order
    # lies once step numbers outgrow the %06d padding
    # (steps_1200000 < steps_999000)
    windows = list_windows(log_dir)
    if not windows:
        if step is not None:
            raise SystemExit(
                f"--step given but {log_dir} has no steps_*_* windows"
            )
        return log_dir
    print(
        "trace windows: "
        + ", ".join(f"{s}..{e}" for s, e, _ in windows)
    )
    if step is None:
        return windows[-1][2]
    for s, e, path in windows:
        if s <= step <= e:
            return path
    raise SystemExit(
        f"no trace window contains step {step} under {log_dir}"
    )


def load_trace(log_dir: str) -> dict:
    paths = glob.glob(
        os.path.join(log_dir, "**", "*.trace.json.gz"), recursive=True
    )
    if not paths:
        raise SystemExit(f"no *.trace.json.gz under {log_dir}")
    path = max(paths, key=os.path.getmtime)
    with gzip.open(path, "rt") as f:
        return json.load(f)


def load_hlo_metadata(path: str) -> dict:
    """op name → \"op_name (source_file:line)\" from an HLO text dump.

    Join key: XLA's op names in profiler traces ("fusion.9461",
    "add_add_fusion.78") are the HLO instruction names, so a compiled
    ``jit_fn.lower(...).compile().as_text()`` dump attributes every trace
    row to the model source that produced it — the manual step of the
    r2/r3 MFU loops, automated.
    """
    import re

    meta = {}
    pat = re.compile(
        r"%?([\w.-]+) = .*metadata=\{[^}]*?op_name=\"([^\"]+)\""
        r"(?:[^}]*?source_file=\"([^\"]+)\")?"
        r"(?:[^}]*?source_line=(\d+))?"
    )
    with open(path) as f:
        for line in f:
            m = pat.search(line)
            if not m:
                continue
            name, op, src, ln = m.groups()
            where = ""
            if src:
                base = src.rsplit("/", 1)[-1]
                where = f" ({base}:{ln})" if ln else f" ({base})"
            meta[name] = f"{op}{where}"
    return meta


def summarize(trace: dict, top: int, like: str | None, hlo_meta=None):
    events = trace.get("traceEvents", [])
    # pid -> process name; device tracks are named "/device:TPU:0" etc.
    # One device pid carries several threads (XLA Modules spanning whole
    # steps, XLA Ops with the individual kernels, …) — summing across all
    # of them double-counts nested time, so keep only the op-level threads.
    pnames = {}
    tnames = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pnames[e["pid"]] = e["args"].get("name", "")
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            tnames[(e["pid"], e.get("tid"))] = e["args"].get("name", "")
    device_pids = {
        pid
        for pid, name in pnames.items()
        if "TPU" in name or "device" in name.lower() or "GPU" in name
    }
    op_tids = {
        key
        for key, name in tnames.items()
        if key[0] in device_pids and "Ops" in name
    }
    per_op = collections.Counter()
    per_op_n = collections.Counter()
    total = 0.0
    tmin, tmax = float("inf"), 0.0
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in device_pids:
            continue
        if op_tids and (e.get("pid"), e.get("tid")) not in op_tids:
            continue
        # span covers ALL device op events (not just --like matches), so
        # util stays meaningful under filtering
        ts = e.get("ts", 0)
        tmin = min(tmin, ts)
        tmax = max(tmax, ts + e.get("dur", 0))
        name = e.get("name", "?")
        if like and like not in name:
            continue
        # control-flow wrappers (the scan While, the jit entry) span their
        # whole contents — counting them double-counts every child op
        if name.startswith(("while", "jit_", "body", "condition")) or (
            name.isdigit()
        ):
            continue
        dur = e.get("dur", 0) / 1e3  # us -> ms
        per_op[name] += dur
        per_op_n[name] += 1
        total += dur
    span = (tmax - tmin) / 1e3 if tmax > tmin else 0.0
    # busy is summed across every device op-thread; normalize the span by
    # the thread count so util is per-device average, not >100%
    n_tracks = max(1, len(op_tids) if op_tids else len(device_pids))
    print(f"device tracks: {sorted(pnames[p] for p in device_pids)}")
    print(
        f"busy={total:.1f}ms span={span:.1f}ms x{n_tracks} tracks "
        f"util={100 * total / (span * n_tracks) if span else 0:.1f}%\n"
    )
    print(f"{'total_ms':>9} {'n':>6} {'avg_us':>8}  name")
    for name, dur in per_op.most_common(top):
        n = per_op_n[name]
        attr = ""
        if hlo_meta is not None:
            attr = "  <- " + hlo_meta.get(name, "?")
        print(f"{dur:9.2f} {n:6d} {dur / n * 1e3:8.1f}  {name[:110]}{attr[:160]}")


def print_attribution(trace: dict, hlo_path: str | None) -> None:
    """Bucket fractions of one loaded trace (the --attribution block)."""
    import sys

    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from apex_tpu.observability import attribution as A

    hlo_map = None
    if hlo_path and os.path.exists(hlo_path):
        with open(hlo_path) as f:
            hlo_map = A.hlo_bucket_map(f.read())
    meas = A.attribute_trace(trace, hlo_map=hlo_map)
    fr = meas.fractions()
    print(
        "attribution (%s, %d op events): compute=%.3f collective=%.3f "
        "host_stall=%.3f"
        % (meas.source, meas.events, fr["compute"], fr["collective"],
           fr["host_stall"])
    )
    for bucket, share in sorted(
        meas.bucket_fractions().items(), key=lambda kv: -kv[1]
    ):
        if share > 0:
            print(f"  {bucket:<18} {100 * share:5.1f}% of busy "
                  f"({meas.bucket_ms[bucket]:.2f} ms)")
    print(f"  span={meas.span_ms:.1f}ms busy={meas.busy_ms:.1f}ms "
          f"stall={meas.stall_ms:.1f}ms "
          "(tools/step_profile.py adds the roofline)")
    # the stall by the innermost host phase open over each idle gap
    # (serve/* and engine/* annotations, docs/serving.md "Host phases")
    for phase, ms in sorted(
        meas.stall_by_phase_ms.items(), key=lambda kv: -kv[1]
    ):
        print(f"  stall under {phase:<18} {ms:9.2f} ms "
              f"({100 * ms / meas.span_ms:5.2f}% of span)")
    print()


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("log_dir")
    ap.add_argument("-n", type=int, default=30)
    ap.add_argument("--like", default=None, help="substring filter")
    ap.add_argument(
        "--attribution", action="store_true",
        help="print step-time attribution bucket fractions for the "
        "chosen window (docs/observability.md 'Attribution & "
        "roofline'); --hlo upgrades the bucketing to the cost model's "
        "exact per-op join",
    )
    ap.add_argument(
        "--step", type=int, default=None,
        help="pick the scheduled-trace window (steps_<start>_<end>/ "
        "subdir, APEX_TPU_TRACE_STEPS layout) containing this step; "
        "default: the latest window, or the dir itself if plain",
    )
    ap.add_argument(
        "--hlo", default=None,
        help="optimized-HLO text dump (jit_fn.lower().compile().as_text())"
        " of the traced program; attributes each op row to its op_name +"
        " source line",
    )
    ap.add_argument(
        "--flight", default=None, metavar="FILE",
        help="a flight-recorder dump (flight_<ts>.json): print which "
        "trace windows overlap the incident's step span and summarize "
        "the latest overlapping one (--step overrides the choice)",
    )
    args = ap.parse_args()
    if args.flight:
        hit = cross_reference_flight(args.log_dir, args.flight)
        if args.step is None:
            if hit is None:
                raise SystemExit(1)
            args.log_dir = hit
        else:
            args.log_dir = resolve_window(args.log_dir, args.step)
    else:
        args.log_dir = resolve_window(args.log_dir, args.step)
    meta = None
    if args.hlo:
        # Degrade, don't die: a trace may outlive its HLO dump — an
        # un-attributed summary beats no summary.
        if os.path.exists(args.hlo):
            meta = load_hlo_metadata(args.hlo)
        else:
            print(f"[trace_summary] --hlo {args.hlo} not found; "
                  "printing un-attributed summary")
    trace = load_trace(args.log_dir)
    if args.attribution:
        print_attribution(trace, args.hlo)
    summarize(trace, args.n, args.like, hlo_meta=meta)
