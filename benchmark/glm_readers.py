"""Arithmetic of the per-layer readers of the GLM cell
(``benchmark/metrics/*.longdoc.py``): the chunk programs' and the chunk
kernel's operations (``benchmark/flops_glm.py``) over their traced device
time and the bf16 peak, the decode kernel's bytes over its time and the HBM
peak, the decode program's time a step.

The driver's ``facts["glm_steps"]`` holds one entry a ``sched.step()``,
aligned with ``facts["steps"]``: what the engine's calls of that step did
(``benchmark/drivers/glm_serve.py::_watch``).  A trace holds whole calls, and
a step's record is credited whole to the trace when the loop took the step
with the trace on; as in ``readers.py``, a reader that finds nothing to read
returns None.
"""

from __future__ import annotations

import statistics

from benchmark import flops_glm
from benchmark.readers import decode_calls, kernel_seconds, share_of_peak
from benchmark.trace import reduce

CHUNK_PROGRAM = "jit_serve_chunk_prefill_"


def _traced(run):
    """The records of the steps the loop took with the trace on."""
    f = run["facts"]
    log = f.get("glm_steps")
    if not log:
        return []
    return [s for t, s in zip(f["steps"], log) if t[5]]


def chunk_calls(run):
    t = run.get("trace")
    return reduce.module_calls(t, CHUNK_PROGRAM) if t else []


def _chunks(run):
    return [c for s in _traced(run) for c in s["chunks"]]


def chunk_prefill_mfu(run):
    """Model operations of the chunks run with the trace on over the device
    time of the chunk programs: the share of the bf16 peak."""
    calls, chunks = chunk_calls(run), _chunks(run)
    if not calls or not chunks:
        return None
    cfg = run["facts"]["config"]
    # the mean traced chunk stands for each traced execution
    work = len(calls) * sum(
        flops_glm.chunk_flops(cfg, n, o) for n, o in chunks) / len(chunks)
    return share_of_peak(work, sum(d for _, d in calls), run)


def mla_chunk_roofline(run):
    """The chunk attention kernel (`%mla_chunk_fwd*`): its scores and values
    counted in the un-absorbed form, each query against the keys before it
    and itself, over the kernel's device time."""
    secs, calls, chunks = (kernel_seconds(run, "%mla_chunk_fwd"),
                           chunk_calls(run), _chunks(run))
    if not secs or not calls or not chunks:
        return None
    cfg = run["facts"]["config"]
    work = len(calls) * sum(
        flops_glm.chunk_attention_flops(cfg, n, o)
        for n, o in chunks) / len(chunks)
    return share_of_peak(work, secs, run)


def mla_decode_roofline(run):
    """The latent decode kernel (`%mla_decode_fwd*`): one cached row (640
    lanes of bf16) a live context position a layer, over its device time
    and the HBM peak."""
    secs, calls = kernel_seconds(run, "%mla_decode_fwd"), decode_calls(run)
    steps = [s for s in _traced(run) if s["decodes"]]
    if not secs or not calls or not steps or not run["peaks"]:
        return None
    cfg = run["facts"]["config"]
    per_call = sum(flops_glm.mla_decode_bytes(cfg, s["ctx_sum"])
                   for s in steps) / sum(s["decodes"] for s in steps)
    return 100.0 * len(calls) * per_call / run["peaks"][
        "hbm_bytes_per_s"] / secs


def decode_step_ms(run):
    """Median device time of one execution of the decode program."""
    calls = decode_calls(run)
    return 1e3 * statistics.median(d for _, d in calls) if calls else None
