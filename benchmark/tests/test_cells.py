"""Every cell rehearsed on the CPU at tiny size through the harness's own
command (``--rehearse 1`` skips only the look for a chip), and `correct`
shown to fail: with the timed path broken underneath (one planted fault
each), and for the control (the reference in the precision below the
configuration's, put in the program's place)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def rehearse(cell, *extra, seconds="3"):
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "3000000019", "--seconds", seconds,
         "--rehearse", "1", *extra],
        capture_output=True, text=True, timeout=900, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_is_correct_and_names_no_device_metric(cell, trace):
    line, err = rehearse(cell, "--trace", trace)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["metrics"] == {}            # nothing under a device name
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checks"       # the compared numbers come last
    for name, c in line["checks"].items():
        assert f"check {name}:" in err
        assert c["value"] <= c["limit"]


def test_no_chip_no_result():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert p.returncode != 0 and p.stdout.strip() == ""


FAULTS = [(w["name"], f) for w in BENCH["workloads"]
          for f in (("frozen_state", "half_batch")
                    if w["config"].startswith("bert") else ("altered_token",))]


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_planted_fault_is_not_correct(cell, fault):
    line, _ = rehearse(cell, "--plant", fault)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def _traffic(name):
    with open(os.path.join(ROOT, "benchmark", "traffic", name + ".json")) as f:
        return json.load(f)


def test_training_control_fp8_is_told_apart():
    """The control: the f32 reference against itself in fp8, over the three
    steps followed, at a size a test can hold.  The limits in the traffic file belong to the
    cell's own size (there fp8 read 3-15x the limits' lower readings and
    failed them on every seed: PERF.md §2); at this size the rounding of
    either precision is smaller, so the test holds the control against the
    configuration's own precision instead: the first loss and the first
    gradient's norms read at least three times what bf16 reads, and the
    half-batch fault ten times.  (The parameters' change is there for the
    frozen-state fault, which `test_planted_fault_is_not_correct` plants.)"""
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, ROOT)
    from benchmark import weights
    from benchmark.drivers import bert_recipe as drv
    from benchmark.tests.test_references import BERT, _bert_batch

    train = dict(_traffic("phase1-1chip"), reference_micro_batch=4)
    rng = np.random.default_rng(3)
    batches = [_bert_batch(rng, b=8) for _ in range(3)]   # the steps followed
    shapes = {k: jax.ShapeDtypeStruct(s, jnp.float32) for k, s in {
        "word": (512, 64), "pos": (32, 64), "type": (2, 64),
        "emb_ln_g": (64,), "emb_ln_b": (64,),
        "qkv_w": (2, 64, 192), "qkv_b": (2, 192), "out_w": (2, 64, 64),
        "out_b": (2, 64), "ln1_g": (2, 64), "ln1_b": (2, 64),
        "fc1_w": (2, 64, 128), "fc1_b": (2, 128), "fc2_w": (2, 128, 64),
        "fc2_b": (2, 64), "ln2_g": (2, 64), "ln2_b": (2, 64),
        "mlm_w": (64, 64), "mlm_b": (64,), "mlm_ln_g": (64,),
        "mlm_ln_b": (64,), "mlm_bias": (512,), "pool_w": (64, 64),
        "pool_b": (64,), "nsp_w": (64, 2), "nsp_b": (2,)}.items()}
    w = weights.seeded_weights(shapes, 4, 0.02)
    w = {k: (jnp.ones_like(v) if k.endswith("_g") else v) for k, v in w.items()}
    ref = drv.reference_steps(BERT, train, w, batches, m_after=1)
    read = {
        name: drv.compare(drv.reference_steps(BERT, train, w, batches,
                                              m_after=1, **kw), ref)[0]
        for name, kw in (("bf16", {"prec": "bf16"}), ("fp8", {"prec": "fp8"}),
                         ("half", {"fault": "half_batch"}),
                         ("no_v", {"fault": "lamb_no_v"}))
    }
    for k in ("loss0_gap", "moment_worst_gap", "moment_median_gap"):
        assert read["fp8"][k] > 3 * read["bf16"][k], (k, read)
    for k in ("loss0_gap", "moment_worst_gap"):
        assert read["half"][k] > 10 * read["bf16"][k], (k, read)
    # the update's direction without LAMB's second moment: each step still
    # moves every leaf by lr * |p|, and the next step's loss tells
    assert read["no_v"]["loss1_gap"] > 10 * read["bf16"]["loss1_gap"], read
    assert read["no_v"]["loss0_gap"] == 0.0
    assert set(train["limits"]) <= set(read["fp8"])
    same, _ = drv.compare(ref, ref)
    assert all(v == 0.0 for v in same.values())


def test_serving_control_fp8_is_told_apart():
    """At each position of the same prompts and tokens, the token that the
    fp8 forward puts first lies below the reference's best; greedy tokens
    of the reference itself read 0, and bf16's no more than fp8's."""
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, ROOT)
    from benchmark import weights
    from benchmark.drivers import gpt_serve as drv
    from benchmark.tests.test_references import GPT
    from apex_tpu.models.gpt import GptModel

    cfg = dict(GPT, n_positions=64)
    shapes = jax.eval_shape(GptModel(drv.program_config(cfg)).init,
                            jax.random.PRNGKey(0), jnp.zeros((8, 1), jnp.int32))
    w = drv.to_reference(weights.seeded_weights(shapes, 6, 0.2))
    rng = np.random.default_rng(8)
    seqs = []
    for _ in range(6):
        ids = jnp.asarray(rng.integers(0, 512, (64,)), jnp.int32)
        greedy = np.asarray(jnp.argmax(drv.ref_gpt2.logits(w, ids, cfg), -1))
        seqs.append((list(np.asarray(ids[:24])), [int(greedy[23])]))
    sound, _, n = drv.served_token_gaps(cfg, w, seqs)
    assert sound == 0.0 and n == 6
    long = [(p, [0] * 30) for p, _ in seqs]
    fp8, _, _ = drv.served_token_gaps(cfg, w, long, control="fp8")
    bf16, _, _ = drv.served_token_gaps(cfg, w, long, control="bf16")
    assert fp8 > 0.01 and fp8 >= bf16, (fp8, bf16)
