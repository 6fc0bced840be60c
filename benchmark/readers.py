"""Arithmetic the per-layer readers share.  Each reader in
``benchmark/metrics/`` is a few lines over these; a reader that finds
nothing to read returns None and the metric is left out of the line."""

from __future__ import annotations

import math
import statistics

from benchmark import flops
from benchmark.trace import reduce

DECODE_PROGRAM = "jit_serve_decode"
PREFILL_PROGRAM = "jit_serve_prefill_"


def quantile(xs, q):
    """The q-quantile (nearest rank) of all of ``xs``; None of nothing."""
    xs = sorted(xs)
    if not xs:
        return None
    return xs[min(len(xs) - 1, max(0, math.ceil(q * len(xs)) - 1))]


def share_of_peak(flops, seconds, run):
    """100 x flops / (seconds x chips x bf16 peak); None without a peak, a
    time or any work."""
    if not run["peaks"] or not seconds or not flops:
        return None
    return 100.0 * flops / (
        seconds * run["chips"] * run["peaks"]["bf16_flops_per_s"]
    )


def serve_mfu(run):
    f = run["facts"]
    return share_of_peak(f["model_flops_in_window"], f["window_s"], run)


def decode_calls(run):
    t = run.get("trace")
    return reduce.module_calls(t, DECODE_PROGRAM) if t else []


def prefill_calls(run):
    t = run.get("trace")
    return reduce.module_calls(t, PREFILL_PROGRAM) if t else []


def traced_steps(run):
    """The loop's own record of the steps taken while the trace was on:
    (t, running, ctx_sum, prefill_flops, decode_flops, traced, tokens)."""
    return [s for s in run["facts"]["steps"] if s[5]]


def decode_step_ms(run):
    calls = decode_calls(run)
    return 1e3 * statistics.median(d for _, d in calls) if calls else None


def decode_hbm_roofline(run):
    """Bytes a decode step has to stream (all weights once + the live KV
    of every running sequence) over the HBM peak, against the device time
    of the decode program: mean bytes per recorded step x traced calls."""
    calls, steps = decode_calls(run), [s for s in traced_steps(run) if s[1]]
    if not calls or not steps or not run["peaks"]:
        return None
    f = run["facts"]
    per_step = f["weight_bytes"] + f["kv_bytes_per_token"] * (
        sum(s[2] for s in steps) / len(steps)
    )
    least = len(calls) * per_step / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / sum(d for _, d in calls)


def prefill_mfu(run):
    """Model FLOPs of the real prompt tokens prefilled while the trace
    was on, over the device time of the prefill programs."""
    calls = prefill_calls(run)
    work = sum(s[3] for s in traced_steps(run))
    if not calls or not work:
        return None
    return share_of_peak(work, sum(d for _, d in calls), run)


def kernel_seconds(run, prefix):
    """Self device seconds of the operations whose name starts with
    ``prefix`` (a Pallas kernel's custom-call), or None."""
    t = run.get("trace")
    if not t:
        return None
    s = sum(v for k, v in reduce.op_seconds(t).items() if k.startswith(prefix))
    return s or None


def paged_decode_roofline(run):
    """The paged decode-attention kernel against the HBM roofline: K and V
    of every live context position read once per layer per step
    (benchmark/flops.py), over the kernel's traced device time."""
    secs = kernel_seconds(run, "%paged_decode_fwd")
    steps = [s for s in traced_steps(run) if s[1]]
    calls = decode_calls(run)
    if not secs or not steps or not calls or not run["peaks"]:
        return None
    f = run["facts"]
    per_step = f["kv_bytes_per_token"] * sum(s[2] for s in steps) / len(steps)
    return 100.0 * len(calls) * per_step / run["peaks"]["hbm_bytes_per_s"] / secs


#: the program sends a prefill to its Pallas flash kernel from this bucket up
FLASH_FROM_BUCKET = 1024


def flash_fwd_roofline(run):
    """The flash-attention forward kernel against the bf16 peak: causal
    QK^T and PV over the real prompt tokens (not the bucket's padding) of
    the prompts prefilled while the trace was on, all layers."""
    secs = kernel_seconds(run, "%flash_fwd")
    f = run["facts"]
    if not secs:
        return None
    below = max([b for b in f["buckets"] if b < FLASH_FROM_BUCKET], default=0)
    work = sum(
        f["n_layer"] * flops.flash_fwd_flops(n, f["n_head"], f["head_dim"])
        for n in f["prompts_traced"] if n > below
    )
    return share_of_peak(work, secs, run)
