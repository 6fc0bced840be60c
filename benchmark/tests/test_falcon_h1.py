"""The Falcon-H1 cell's own pieces: the configuration file against the
catalog row, `flops_falcon_h1.py` against hand counts at the published
widths (bytes at the dtype streamed), the new readers on a small synthetic
trace, the rehearsal and the driver's planted faults through the harness's
own command."""

import json
import os

import pytest

from benchmark import falcon_h1_readers as readers
from benchmark import flops_falcon_h1 as flops_h1
from benchmark.tests.test_cells import ROOT, rehearse
from benchmark.trace.reduce import Trace

CELL = "falcon-h1-34b.short-chat-saturated"
with open(os.path.join(ROOT, "benchmark", "configs",
                       "falcon-h1-34b-instruct-4l.json")) as f:
    CFG = json.load(f)


def test_config_keeps_every_published_number():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Falcon-H1-34B-Instruct")
    assert CFG["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k in CFG["reduced"]:
            assert CFG[k + "_published"] == v
        else:
            assert CFG[k] == v, k
    assert CFG["reduced"] == ["num_hidden_layers"]
    assert CFG["num_hidden_layers"] == 4
    for key in ("cut", "in_proj_order", "ssm_multipliers", "time_step_limit",
                "gated_norm", "state_dtype", "weights", "mamba_d_ssm"):
        assert CFG["assumed"][key]
    assert "18 stages" in CFG["deployment"]
    sv = CFG["serve"]
    assert sv["num_pages"] == sv["max_batch"] * sv["max_pages_per_seq"] + 1


def test_hand_counts():
    # ISSUE 37's arithmetic, a layer: in_proj 5120 x 9248, out_proj 4096 x
    # 5120, q k v o, SwiGLU 3 x 5120 x 21504
    assert flops_h1.matrix_params(CFG) == (
        5120 * 9248 + 4096 * 5120 + 5120 * (2560 + 512 + 512) + 2560 * 5120
        + 3 * 5120 * 21504) == 430_080_000
    # conv 4 x 5120 + bias 5120, dt_bias A_log D 3 x 32, gated norm 4096,
    # two block norms
    assert flops_h1.small_params(CFG) == 4 * 5120 + 5120 + 96 + 4096 + 10240
    table = 261120 * 5120
    assert table * 2 == 2_673_868_800                   # "2.674 GB each"
    assert flops_h1.weight_bytes(CFG) == (
        2 * (4 * 430_080_000 + table) + 4 * (4 * 40_032 + 5120))
    held = flops_h1.held_weight_bytes(CFG)
    assert 8.78e9 < held < 8.80e9                        # "8.79 GB"
    # f32 state 32 x 128 x 256 = 4.19 MB a slot a layer; bf16 tails
    assert flops_h1.ssm_state_bytes_per_slot(CFG) == 4 * 4_194_304
    assert flops_h1.state_bytes_per_slot(CFG) == 4 * (
        4_194_304 + 3 * 5120 * 2)
    # 8 KB a token: 4 layers x K and V x 4 KV heads x 128 x bf16
    assert flops_h1.kv_bytes_per_token(CFG) == 8192
    # a full batch at ~300 live tokens a rider
    assert flops_h1.ssm_step_bytes(CFG, 128) == 2 * 128 * 16_777_216
    assert 4.29e9 < flops_h1.ssm_step_bytes(CFG, 128) < 4.30e9
    assert flops_h1.gqa_decode_bytes(CFG, 128 * 300) == 8192 * 38400
    step = flops_h1.decode_step_bytes(CFG, 128, 128 * 300)
    assert step == (flops_h1.weight_bytes(CFG)
                    + 2 * 128 * flops_h1.state_bytes_per_slot(CFG)
                    + 8192 * 38400)
    assert 10.6e9 < step < 10.9e9                        # "10.7 GB"
    # a decode block: the weights once an iteration more
    assert flops_h1.decode_step_bytes(CFG, 128, 38400, 4) - step == \
        3 * flops_h1.weight_bytes(CFG)
    one = flops_h1.token_flops(CFG, 0, False)
    assert 3.4e9 < one < 3.5e9                           # "3.4 GFLOP a token"
    assert flops_h1.token_flops(CFG, 0, True) - one == 2 * table
    assert flops_h1.token_flops(CFG, 100, False) - one == \
        4 * 4 * 20 * 128 * 100
    n = 512
    assert flops_h1.prefill_flops(CFG, n) == pytest.approx(
        n * one + 4 * 4 * 20 * 128 * n * (n + 1) / 2 + 2 * table)
    # the prompt kernel: 3 chunks of 128 for 300 real tokens, per head the
    # read-out (128 x 256) . (256 x 128) and the state's update
    assert flops_h1.ssd_chunk_flops(CFG, 300) == 4 * 32 * 3 * (
        2 * 128 * 256 * 128 + 2 * 128 * 256)


def synthetic_run():
    """Three traced decode programs of 320 ms (16 iterations), one prefill;
    kernels named as the program names them."""
    ops, mods, t = [], [], 0.0
    for _ in range(3):
        mods.append((t, 0.320, "jit_serve_decode(123)"))
        ops += [(t, 0.110, "%ssm_step_fwd.7 = f32[1]{0} custom-call(...)"),
                (t + 0.111, 0.030,
                 "%paged_decode_fwd.3 = bf16[1]{0} custom-call(...)")]
        t += 0.33
    mods.append((t, 0.012, "jit_serve_prefill_512(5)"))
    ops.append((t, 0.0004, "%ssd_chunk_fwd.9 = f32[1]{0} custom-call(...)"))
    step = dict(decodes=1, riders=16 * 126, ctx_sum=16 * 126 * 310,
                prefills=0)
    steps = [(0.4 * i, 126, 0, 0.0, 0.0, True, 0) for i in range(3)]
    return {
        "trace": Trace(ops={"/device:TPU:0": sorted(ops)},
                       modules={"/device:TPU:0": mods}, host=[]),
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "chips": 1,
        "facts": {"config": CFG, "steps": steps, "decode_block": 16,
                  "h1_steps": [step, step, dict(step, prefills=1)],
                  "prompts_traced": [300]},
    }


def test_readers_on_a_synthetic_trace():
    run = synthetic_run()
    assert readers.decode_step_ms(run) == pytest.approx(20.0)
    per = flops_h1.decode_step_bytes(CFG, 16 * 126, 16 * 126 * 310, 16)
    assert readers.decode_hbm_roofline(run) == pytest.approx(
        100 * per / 819e9 / 0.320)
    assert readers.ssm_step_roofline(run) == pytest.approx(
        100 * flops_h1.ssm_step_bytes(CFG, 16 * 126) / 819e9 / 0.110)
    assert readers.gqa_decode_roofline(run) == pytest.approx(
        100 * flops_h1.gqa_decode_bytes(CFG, 16 * 126 * 310) / 819e9 / 0.030)
    assert readers.ssd_chunk_roofline(run) == pytest.approx(
        100 * flops_h1.ssd_chunk_flops(CFG, 300) / 197e12 / 0.0004)
    for fn in (readers.decode_hbm_roofline, readers.ssm_step_roofline,
               readers.gqa_decode_roofline, readers.ssd_chunk_roofline):
        assert 0 < fn(run) < 100


def test_readers_find_nothing_without_a_trace_or_a_record():
    """A run whose program lacks the kernels (the parent's), or whose
    driver kept no such record (another configuration's), reads None and
    does not raise."""
    run = synthetic_run()
    run["trace"] = None
    bare = dict(synthetic_run(), facts={"config": CFG, "steps": []})
    for fn in (readers.decode_hbm_roofline, readers.ssm_step_roofline,
               readers.gqa_decode_roofline, readers.ssd_chunk_roofline,
               readers.decode_step_ms):
        assert fn(run) is None and fn(bare) is None


def test_rehearsal_is_correct_and_reads_the_programs_counters():
    line, _ = rehearse(CELL)
    assert line["correct"] is True and line["failed"] == 0
    info = line["info"]
    assert info["ssm_slots_written"] >= info["prefills_in_window"] > 0
    assert info["ssm_state_bytes_per_iter"] > 0
    assert 0 < info["riders_per_decode_step"] <= 4
    assert line["checks"]["slots_leaked"]["value"] == 0


@pytest.mark.parametrize(
    "fault", ["altered_token", "state_not_reset", "dropped_attention_branch"])
def test_planted_fault_is_not_correct(fault):
    line, _ = rehearse(CELL, "--plant", fault)
    assert line["correct"] is False
    assert line["checks"]["served_token_gap"]["value"] > line["checks"][
        "served_token_gap"]["limit"]
