"""The trace reduction against a trace recorded on the chip (TPU v5e, PR
26): two 4-step chunks of `bert-large.phase1-1chip`, 3.07 s of window."""

import gzip
import os
import shutil

import pytest

from benchmark.trace import reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "bert_two_chunks.xplane.pb.gz")


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    out = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(DATA, "rb") as src, open(out, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return reduce.load(str(out))


def test_busy_is_the_union_not_the_sum(trace):
    busy = reduce.busy_seconds(trace)
    # two chunk programs of 1.53 s each ran back to back in a 3.074 s window
    assert 3.05 < busy < 3.07
    nested = sum(d for ev in trace.ops.values() for _, d, _ in ev)
    assert nested > 2 * busy          # the loops nest: durations double count
    self_sum = sum(reduce.op_seconds(trace).values())
    assert abs(self_sum - busy) < 1e-6


def test_programs_and_kernels_are_found(trace):
    calls = reduce.module_calls(trace, "jit_chunk_fn")
    assert len(calls) == 2 and all(1.5 < d < 1.56 for _, d in calls)
    ops = reduce.op_seconds(trace)
    ln = {k: v for k, v in ops.items() if k.startswith("%layer_norm_")}
    assert len(ln) == 10 and all("custom-call" in k for k in ln)
    # ten LayerNorm kernel instances (encoder layers, embeddings, MLM head,
    # forward and backward), 89.5 ms over the 8 traced steps
    assert 0.085 < sum(ln.values()) < 0.095


def test_names_are_short_and_gaps_are_labelled(trace):
    top = reduce.top_ops(trace, 10)
    assert len(top) == 10 and all(len(n) < 80 for n, _ in top)
    assert top[0][0].startswith("%add_add_fusion.4 fusion bf16[128,128,1024]")
    gaps = reduce.idle_gaps(trace, 3)
    # the one real gap: the host between two chunks, inside the feed's hook
    assert gaps[0][0] == "bench/feed_next" and 0.004 < gaps[0][1] < 0.007


def test_self_times_subtract_children():
    ev = [(0.0, 10.0, "outer"), (1.0, 3.0, "a"), (2.0, 1.0, "b"), (5.0, 2.0, "c")]
    got = {n: s for _, s, n in reduce.self_times(ev)}
    assert got == {"outer": 5.0, "a": 2.0, "b": 1.0, "c": 2.0}
    assert reduce.short_name(
        "%fusion.1 = (f32[8]{0:T(8)}, bf16[2,4]{1,0}) fusion(f32[8] %x)"
    ) == "%fusion.1 fusion f32[8]"
