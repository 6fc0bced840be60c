"""Arithmetic of the per-layer readers of the Ling cells
(``benchmark/metrics/*.answers.py``): a decode step's and each kernel's
least time at the HBM peak — bytes from ``benchmark/flops_ling.py`` and the
counts the program measured — over its traced device time.

The driver's ``facts["ling_steps"]`` holds one entry a ``sched.step()``,
aligned with ``facts["steps"]``: what the engine's calls of that step did
(``benchmark/drivers/ling_serve.py::_watch``; a decode program runs
``facts["decode_block"]`` iterations, and its record sums riders, context
and counts over them).  As in ``readers.py``, the
mean over the steps the loop took while the trace was on stands for each
traced execution, and a reader that finds nothing to read returns None.
"""

from __future__ import annotations

import statistics

from benchmark import flops_ling
from benchmark.readers import decode_calls, kernel_seconds, prefill_calls


def _traced(run, key):
    """The record of the traced steps that made a ``key`` call."""
    f = run["facts"]
    log = f.get("ling_steps")
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
    if not calls:
        return None
    return 1e3 * statistics.median(d for _, d in calls) / run["facts"].get(
        "decode_block", 1)


def decode_hbm_roofline(run):
    """Bytes a decode step has to move — the unrouted weights once, the
    experts the step touched (the program's counter), every rider's
    recurrent state in and out, the live latent rows, each at the bytes it
    is stored in — against the device time of the decode program."""
    return _decode_share(
        run, lambda cfg, s: flops_ling.decode_step_bytes(
            cfg, s["riders"], s["ctx_sum"], s["touched_decode"],
            run["facts"].get("decode_block", 1)),
        lambda calls: sum(d for _, d in calls))


def kda_step_roofline(run):
    """The KDA decode kernel (`%kda_step_fwd*`): every rider's f32 state
    read and written once a layer."""
    return _decode_share(
        run, lambda cfg, s: flops_ling.kda_step_bytes(cfg, s["riders"]),
        lambda _: kernel_seconds(run, "%kda_step_fwd"))


def mla_decode_roofline(run):
    """The latent decode kernel (`%mla_decode_fwd*`): one cached row a live
    context position."""
    return _decode_share(
        run, lambda cfg, s: flops_ling.mla_decode_bytes(cfg, s["ctx_sum"]),
        lambda _: kernel_seconds(run, "%mla_decode_fwd"))


def moe_grouped_roofline(run):
    """The grouped expert matmul (`%moe_grouped_fwd*`), which both the
    decode and the prefill programs run: the experts each call touched
    streamed once, the routed rows in and out."""
    cfg = run["facts"]["config"]
    least = 0.0
    for calls, key, pairs, touched in (
        (decode_calls(run), "decodes", "pairs_decode", "touched_decode"),
        (prefill_calls(run), "prefills", "pairs_prefill", "touched_prefill"),
    ):
        steps = _traced(run, key)
        if calls and steps:
            n = sum(s[key] for s in steps)
            least += len(calls) * sum(
                flops_ling.moe_grouped_bytes(cfg, s[touched], s[pairs])
                for s in steps) / n
    return _share(run, least, kernel_seconds(run, "%moe_grouped_fwd"))
