"""The GLM cell's own pieces: the configuration against the catalog,
`flops_glm.py` against hand counts at the published widths, the readers on
a small synthetic trace, and the driver's planted faults through the
harness's own command (each has to read not `correct`)."""

import json
import os

import pytest

from benchmark import flops_glm, glm_readers
from benchmark.tests.test_cells import ROOT, rehearse
from benchmark.trace.reduce import Trace

CELL = "glm-4.7-flash.long-doc-chunked"
with open(os.path.join(ROOT, "benchmark", "configs",
                       "glm-4.7-flash-5l.json")) as f:
    CFG = json.load(f)


def test_config_keeps_every_published_number():
    # the published config.json of the model, as its source lists it
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data", "glm-4.7-flash.published.json")) as f:
        row = json.load(f)
    assert CFG["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k in CFG["reduced"]:
            assert CFG[k + "_published"] == v
        else:
            assert CFG[k] == v, k
    assert CFG["reduced"] == ["num_hidden_layers"]
    assert CFG["num_hidden_layers"] == 5
    assert flops_glm.layer_kinds(CFG) == (
        [("mla", "dense")] + [("mla", "moe")] * 4)


def test_hand_counts():
    h = 2048
    # ISSUE 42's arithmetic: q_a 1.57 M, q_b 3.93 M, kv_a 1.18 M, kv_b
    # 4.59 M, o 10.49 M (and the two latent norms)
    assert flops_glm.mla_params(CFG) == (
        h * 768 + 768 + 768 * 20 * 256 + h * 576 + 512 + 512 * 20 * 448
        + 5120 * h)
    assert flops_glm.expert_params(CFG) == 3 * 2048 * 1536 == 9437184
    assert flops_glm.latent_row_lanes(CFG) == 640
    assert flops_glm.latent_bytes_per_token(CFG) == 5 * 1280
    # 6.52 GB of weights
    assert 6.45e9 < flops_glm.weight_bytes(CFG) < 6.6e9
    # a chunk is its tokens' flat work, the causal keys and one logits row
    n, off = 2048, 12288
    keys = n * off + n * (n + 1) / 2
    assert flops_glm.chunk_attention_flops(CFG, n, off) == \
        5 * 2 * 20 * (192 + 64 + 256) * keys
    assert flops_glm.chunk_flops(CFG, n, off) == pytest.approx(
        n * flops_glm.flat_token_flops(CFG)
        + flops_glm.chunk_attention_flops(CFG, n, off) + 2 * h * 154880)
    # ~0.72 G operations a token outside attention (top-4 of 64 + shared)
    assert 0.7e9 < flops_glm.flat_token_flops(CFG) < 0.75e9
    assert flops_glm.prefill_flops(CFG, 100) == flops_glm.chunk_flops(
        CFG, 100, 0)


def synthetic_run():
    """Two traced chunk programs of 40 ms, three decode programs of 12 ms;
    kernels named as the program names them."""
    ops, mods, t = [], [], 0.0
    for _ in range(2):
        mods.append((t, 0.040, "jit_serve_chunk_prefill_2048(7)"))
        ops.append((t, 0.020,
                    "%mla_chunk_fwd.2 = f32[1]{0} custom-call(...)"))
        t += 0.045
    for _ in range(3):
        mods.append((t, 0.012, "jit_serve_decode(3)"))
        ops.append((t, 0.004,
                    "%mla_decode_fwd = f32[1]{0} custom-call(...)"))
        t += 0.015
    steps = [
        dict(chunks=[(2048, 4096)], decodes=1, riders=30,
             ctx_sum=30 * 16000, emitted=30, flops=0.0),
        dict(chunks=[(2048, 6144)], decodes=2, riders=60,
             ctx_sum=60 * 16000, emitted=60, flops=0.0),
    ]
    loop = [(0.1 * i, 30, 0, 0.0, 0.0, True, 0) for i in range(2)]
    return {
        "trace": Trace(ops={"/device:TPU:0": sorted(ops)},
                       modules={"/device:TPU:0": mods}, host=[]),
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "chips": 1,
        "facts": {"config": CFG, "steps": loop, "glm_steps": steps},
    }


def test_readers_on_a_synthetic_trace():
    run = synthetic_run()
    mean_chunk = (flops_glm.chunk_flops(CFG, 2048, 4096)
                  + flops_glm.chunk_flops(CFG, 2048, 6144)) / 2
    assert glm_readers.chunk_prefill_mfu(run) == pytest.approx(
        100 * 2 * mean_chunk / 0.080 / 197e12)
    attn = (flops_glm.chunk_attention_flops(CFG, 2048, 4096)
            + flops_glm.chunk_attention_flops(CFG, 2048, 6144)) / 2
    assert glm_readers.mla_chunk_roofline(run) == pytest.approx(
        100 * 2 * attn / 0.040 / 197e12)
    per_call = flops_glm.mla_decode_bytes(CFG, 90 * 16000) / 3
    assert glm_readers.mla_decode_roofline(run) == pytest.approx(
        100 * 3 * per_call / 819e9 / 0.012)
    assert glm_readers.decode_step_ms(run) == pytest.approx(12.0)
    for fn in (glm_readers.chunk_prefill_mfu, glm_readers.mla_chunk_roofline,
               glm_readers.mla_decode_roofline):
        assert 0 < fn(run) < 100


def test_readers_find_nothing_without_a_trace_or_a_record():
    run = synthetic_run()
    run["trace"] = None
    bare = dict(synthetic_run(), facts={"config": CFG, "steps": []})
    for fn in (glm_readers.chunk_prefill_mfu, glm_readers.mla_chunk_roofline,
               glm_readers.mla_decode_roofline, glm_readers.decode_step_ms):
        assert fn(run) is None
    for fn in (glm_readers.chunk_prefill_mfu, glm_readers.mla_chunk_roofline,
               glm_readers.mla_decode_roofline):
        assert fn(bare) is None


@pytest.mark.parametrize("fault", ["chunk_bf16_softmax",
                                   "chunk_ignores_history",
                                   "dropped_q_a_norm"])
def test_planted_fault_is_not_correct(fault):
    line, _ = rehearse(CELL, "--plant", fault)
    assert line["correct"] is False
    assert line["checks"]["prompt_end_logit_gap"]["value"] > line["checks"][
        "prompt_end_logit_gap"]["limit"]
