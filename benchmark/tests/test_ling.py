"""The Ling cell's own pieces: `flops_ling.py` against hand counts at the
published widths, the new readers on a small synthetic trace, the driver's
planted faults through the harness's own command."""

import json
import os

import pytest

from benchmark import flops_ling, ling_readers
from benchmark.tests.test_cells import ROOT, rehearse
from benchmark.trace.reduce import Trace

CELL = "ling-3.0-flash-vl.long-answer-batch"
with open(os.path.join(ROOT, "benchmark", "configs",
                       "ling-3.0-flash-vl-ep8.json")) as f:
    CFG = json.load(f)


def test_config_keeps_every_published_number():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash-VL")
    assert CFG["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k in CFG["reduced"]:
            assert CFG[k + "_published"] == v
        else:
            assert CFG[k] == v, k
    assert CFG["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (CFG["num_hidden_layers"], CFG["num_experts"],
            CFG["vocab_size"]) == (8, 64, 19648)
    assert CFG["vocab_size"] * 8 == CFG["vocab_size_published"]
    assert flops_ling.layer_kinds(CFG) == (
        [("kda", "dense")] * 2 + [("kda", "moe")] * 3 + [("mla", "moe")]
        + [("kda", "moe")] * 2)


def test_hand_counts():
    h, nd = 2560, 4096
    # ISSUE 35's arithmetic: q, k, v, gate and out at 2560 x 4096
    assert flops_ling.kda_params(CFG) == (
        5 * h * nd + nd + 4 * 3 * nd + 2 * h * 32 + 128)
    assert flops_ling.mla_params(CFG) == (
        h * 32 * 192 + h * 576 + 512 + 512 * 32 * 256 + h * 32 + nd * h)
    assert flops_ling.expert_params(CFG) == 3 * 2560 * 768 == 5898240
    assert flops_ling.latent_row_lanes(CFG) == 640
    assert flops_ling.latent_bytes_per_token(CFG) == 1280
    # 7 KDA layers x 32 heads x 128 x 128 f32 = 14.7 MB, + 0.5 MB of conv
    assert flops_ling.state_bytes_per_slot(CFG) == 7 * (
        32 * 128 * 128 * 4 + 3 * 12288 * 2) == 15196160
    held = flops_ling.held_weight_bytes(CFG)
    assert 5.7e9 < held < 5.9e9                     # "2.9 B = 5.8 GB"
    assert flops_ling.expected_pairs_per_layer(CFG) == 1.0
    # a full batch that touched 56 of 64 experts in each of 6 layers
    step = flops_ling.decode_step_bytes(CFG, 128, 128 * 1200, 6 * 56)
    assert step == (
        flops_ling.dense_weight_bytes(CFG) + 336 * 2 * 5898240
        + 2 * 128 * 15196160 + 1280 * 128 * 1200)
    assert 9.0e9 < step < 9.6e9
    one = flops_ling.token_flops(CFG, 1000, True, 1.0)
    assert 1.2e9 < one < 1.4e9            # ~0.62 G active parameters
    # a prompt is its tokens' flat work plus the causal triangle
    n = 512
    tri = 2 * 32 * (192 + 128) * n * (n + 1) / 2
    assert flops_ling.prefill_flops(CFG, n, 1.0) == pytest.approx(
        n * flops_ling.token_flops(CFG, 0, False, 1.0) + tri
        + 2 * 2560 * 19648)


def synthetic_run():
    """Three traced decode programs of 10 ms, one prefill; kernels named
    as the program names them."""
    ops, mods, t = [], [], 0.0
    for _ in range(3):
        mods.append((t, 0.014, "jit_serve_decode(123)"))
        ops += [(t, 0.005, "%kda_step_fwd.7 = f32[1]{0} custom-call(...)"),
                (t + 0.0051, 0.006,
                 "%moe_grouped_fwd.3 = bf16[1]{0} custom-call(...)"),
                (t + 0.0112, 0.001,
                 "%mla_decode_fwd = f32[1]{0} custom-call(...)")]
        t += 0.016
    mods.append((t, 0.020, "jit_serve_prefill_512(5)"))
    ops.append((t, 0.007, "%moe_grouped_fwd.9 = bf16[1]{0} custom-call(...)"))
    step = dict(decodes=1, riders=128, ctx_sum=128 * 1000, pairs_decode=800,
                touched_decode=330, prefills=0, pairs_prefill=0,
                touched_prefill=0)
    both = dict(step, prefills=1, pairs_prefill=3000, touched_prefill=384)
    steps = [(0.1 * i, 128, 0, 0.0, 0.0, True, 0) for i in range(3)]
    return {
        "trace": Trace(ops={"/device:TPU:0": sorted(ops)},
                       modules={"/device:TPU:0": mods}, host=[]),
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "chips": 1,
        "facts": {"config": CFG, "steps": steps,
                  "ling_steps": [step, step, both]},
    }


def test_readers_on_a_synthetic_trace():
    run = synthetic_run()
    per_step = flops_ling.decode_step_bytes(CFG, 128, 128000, 330)
    assert ling_readers.decode_hbm_roofline(run) == pytest.approx(
        100 * per_step / 819e9 / 0.014)
    assert ling_readers.decode_step_ms(run) == pytest.approx(14.0)
    # a decode block: the same program ran 4 iterations, its record sums
    # riders, context and counts over them
    block = dict(run, facts=dict(run["facts"], decode_block=4))
    assert ling_readers.decode_step_ms(block) == pytest.approx(3.5)
    assert ling_readers.decode_hbm_roofline(block) == pytest.approx(
        100 * flops_ling.decode_step_bytes(CFG, 128, 128000, 330, 4)
        / 819e9 / 0.014)
    assert flops_ling.decode_step_bytes(CFG, 128, 128000, 330, 4) \
        - per_step == 3 * flops_ling.dense_weight_bytes(CFG)
    assert ling_readers.kda_step_roofline(run) == pytest.approx(
        100 * flops_ling.kda_step_bytes(CFG, 128) / 819e9 / 0.005)
    assert ling_readers.mla_decode_roofline(run) == pytest.approx(
        100 * flops_ling.mla_decode_bytes(CFG, 128000) / 819e9 / 0.001)
    moe = 3 * flops_ling.moe_grouped_bytes(CFG, 330, 800) \
        + flops_ling.moe_grouped_bytes(CFG, 384, 3000)
    assert ling_readers.moe_grouped_roofline(run) == pytest.approx(
        100 * moe / 819e9 / 0.025)
    for fn in (ling_readers.decode_hbm_roofline,
               ling_readers.kda_step_roofline,
               ling_readers.mla_decode_roofline,
               ling_readers.moe_grouped_roofline):
        assert 0 < fn(run) < 100


def test_readers_find_nothing_without_a_trace_or_a_record():
    run = synthetic_run()
    run["trace"] = None
    bare = dict(run, facts={"config": CFG, "steps": []})
    for fn in (ling_readers.decode_hbm_roofline,
               ling_readers.kda_step_roofline,
               ling_readers.mla_decode_roofline,
               ling_readers.moe_grouped_roofline,
               ling_readers.decode_step_ms):
        assert fn(run) is None and fn(bare) is None


@pytest.mark.parametrize("fault", ["dropped_shared_expert", "state_not_reset"])
def test_planted_fault_is_not_correct(fault):
    line, _ = rehearse(CELL, "--plant", fault)
    assert line["correct"] is False
    assert line["checks"]["served_token_gap"]["value"] > line["checks"][
        "served_token_gap"]["limit"]
