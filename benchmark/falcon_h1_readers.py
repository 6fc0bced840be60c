"""Arithmetic of the per-layer readers of the Falcon-H1 cells
(``benchmark/metrics/*.h1chat.py``): a decode step's and each decode
kernel's least time at the HBM peak — bytes from
``benchmark/flops_falcon_h1.py``, at the dtype the program streams, and the
riders and live context the driver recorded — over its traced device time;
the prompt recurrence kernel's operations against the bf16 peak.

The driver's ``facts["h1_steps"]`` holds one entry a ``sched.step()``,
aligned with ``facts["steps"]``: what the engine's calls of that step did
(``benchmark/drivers/falcon_h1_serve.py::_watch``; a decode program runs
``facts["decode_block"]`` iterations, and its record sums riders and
context over them).  As in ``readers.py``, the mean over the steps the loop
took while the trace was on stands for each traced execution, and a reader
that finds nothing to read (a program without the kernel, a run without
these facts) returns None.
"""

from __future__ import annotations

import statistics

from benchmark import flops_falcon_h1 as flops_h1
from benchmark.readers import decode_calls, kernel_seconds, share_of_peak


def _traced(run, key):
    """The record of the traced steps that made a ``key`` call."""
    f = run["facts"]
    log = f.get("h1_steps")
    if not log:
        return []
    return [s for t, s in zip(f["steps"], log) if t[5] and s[key]]


def _share(run, least_bytes, seconds):
    if not run["peaks"] or not seconds or not least_bytes:
        return None
    return 100.0 * least_bytes / run["peaks"]["hbm_bytes_per_s"] / seconds


def _decode_share(run, step_bytes, seconds_of):
    """``step_bytes(cfg, step record)`` meaned over the traced decode steps,
    times the traced executions of the decode program, over
    ``seconds_of(calls)``."""
    calls, steps = decode_calls(run), _traced(run, "decodes")
    if not calls or not steps:
        return None
    cfg = run["facts"]["config"]
    per_step = sum(step_bytes(cfg, s) for s in steps) / len(steps)
    return _share(run, len(calls) * per_step, seconds_of(calls))


def decode_step_ms(run):
    """Median device time of one execution of the decode program, over the
    iterations it runs (``decode_block``): milliseconds a token step."""
    calls = decode_calls(run)
    if not calls or "h1_steps" not in run["facts"]:
        return None
    return 1e3 * statistics.median(d for _, d in calls) / run["facts"].get(
        "decode_block", 1)


def decode_hbm_roofline(run):
    """Bytes a decode program has to move — the weights once an iteration
    (bf16 matrices, f32 small leaves), every rider's recurrent state and
    convolution tail in and out, the live K/V rows once a KV head — against
    the device time of the decode program."""
    return _decode_share(
        run, lambda cfg, s: flops_h1.decode_step_bytes(
            cfg, s["riders"], s["ctx_sum"],
            run["facts"].get("decode_block", 1)),
        lambda calls: sum(d for _, d in calls))


def ssm_step_roofline(run):
    """The state-space decode kernel (`%ssm_step_fwd*`): every rider's f32
    state read and written once a layer."""
    return _decode_share(
        run, lambda cfg, s: flops_h1.ssm_step_bytes(cfg, s["riders"]),
        lambda _: kernel_seconds(run, "%ssm_step_fwd"))


def gqa_decode_roofline(run):
    """The paged decode kernel under grouped-query heads
    (`%paged_decode_fwd*`): the K and V rows of every live context position
    read once a KV head."""
    return _decode_share(
        run, lambda cfg, s: flops_h1.gqa_decode_bytes(cfg, s["ctx_sum"]),
        lambda _: kernel_seconds(run, "%paged_decode_fwd"))


def ssd_chunk_roofline(run):
    """The prompt recurrence kernel (`%ssd_chunk_fwd*`): its operations
    over the REAL prompt tokens prefilled while the trace was on (not the
    bucket's padding) against the bf16 peak — compute bounds it, since its
    operands need not cross HBM (``flops_falcon_h1.ssd_chunk_flops``); the
    kernel multiplies f32 at HIGHEST, six passes, so a sixth is its
    ceiling."""
    f = run["facts"]
    if "h1_steps" not in f or not f.get("prompts_traced"):
        return None
    work = sum(
        flops_h1.ssd_chunk_flops(f["config"], n) for n in f["prompts_traced"])
    return share_of_peak(work, kernel_seconds(run, "%ssd_chunk_fwd"), run)
