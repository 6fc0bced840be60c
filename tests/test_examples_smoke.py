"""Example smoke tests (VERDICT r1 weak item 7 / next-round item 9).

The reference runs its ImageNet example as the L1 test harness
(SURVEY §4.2); the analog here: every ``examples/`` script must complete a
couple of synthetic-data steps on the CPU mesh.  Each runs in a
subprocess (own backend, own argv) so example-level breakage — imports,
argparse, train-loop wiring — fails THIS suite instead of rotting.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_example(relpath, argv, n_devices=2, timeout=420):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_devices}"
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, relpath)] + argv,
        env=env,
        cwd=REPO,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        timeout=timeout,
    )
    assert proc.returncode == 0, (
        f"{relpath} {argv} failed:\n{proc.stdout[-3000:]}"
    )
    return proc.stdout


@pytest.mark.parametrize("opt_level", ["O1", "O2"])
def test_imagenet_amp_smoke(opt_level):
    out = _run_example(
        "examples/imagenet/main_amp.py",
        [
            "--opt-level", opt_level, "--steps", "2", "--batch-size", "8",
            "--image-size", "32", "--num-classes", "10",
        ],
    )
    assert "loss" in out.lower() or "img/s" in out.lower(), out[-500:]


def test_imagenet_amp_syncbn_smoke():
    _run_example(
        "examples/imagenet/main_amp.py",
        [
            "--opt-level", "O0", "--steps", "2", "--batch-size", "8",
            "--image-size", "32", "--num-classes", "10", "--sync-bn",
        ],
    )


def test_dcgan_amp_smoke():
    _run_example(
        "examples/dcgan/main_amp.py",
        ["--steps", "2", "--batch", "4", "--zdim", "8"],
    )


def test_simple_ddp_smoke():
    out = _run_example(
        "examples/simple/distributed/distributed_data_parallel.py", []
    )
    assert "devices: 2" in out, out[-500:]


def test_simple_resilient_accum_smoke(tmp_path):
    """Resilient loop + DDP gradient accumulation (no_sync boundary
    sync, int8 wire) over a 2-device dp mesh."""
    out = _run_example(
        "examples/simple/resilient/train_resilient.py",
        ["--steps", "8", "--accum", "2", "--wire", "int8",
         "--save-every", "4", "--dir", str(tmp_path / "demo")],
        n_devices=2,
    )
    assert "dp=2, accum=2, wire=int8" in out, out[-500:]
    assert "final loss" in out, out[-500:]


def test_bert_pretrain_tiny_smoke():
    # default path: packed masked-position MLM head (the recipe input)
    _run_example("examples/bert/pretrain_bert.py", ["--tiny"])


def test_bert_pretrain_dense_head_smoke():
    # --max-predictions-per-seq 0 keeps the dense-label MLM head
    _run_example(
        "examples/bert/pretrain_bert.py",
        ["--tiny", "--max-predictions-per-seq", "0"],
    )


def test_gpt_train_tiny_smoke():
    out = _run_example(
        "examples/gpt/train_gpt.py",
        ["--tiny", "--steps", "4", "--batch", "4", "--seq-len", "64"],
    )
    assert "chunk 0: loss" in out, out[-500:]


def test_gpt_train_pp_smoke():
    """Pipeline-parallel LM example: 1F1B, loss finite and printed."""
    out = _run_example(
        "examples/gpt/train_gpt_pp.py",
        ["--pp", "2", "--steps", "3", "--layers", "2", "--seq", "16",
         "--hidden", "32", "--vocab", "64"],
        n_devices=2,
    )
    assert "pipeline LM: pp=2 (1F1B)" in out, out[-500:]
    assert "step   2" in out, out[-500:]


def test_gpt_train_pp_interleaved_smoke():
    """Interleaved virtual-stage LM example (vpp=2)."""
    out = _run_example(
        "examples/gpt/train_gpt_pp.py",
        ["--pp", "2", "--vpp", "2", "--steps", "3", "--layers", "4",
         "--seq", "16", "--hidden", "32", "--vocab", "64"],
        n_devices=2,
    )
    assert "interleaved vpp=2" in out, out[-500:]
    assert "step   2" in out, out[-500:]


def test_gpt_train_pp_hand_1f1b_smoke():
    """Hand-scheduled 1F1B (stash ring) LM example end-to-end."""
    out = _run_example(
        "examples/gpt/train_gpt_pp.py",
        ["--pp", "2", "--hand-1f1b", "--steps", "3", "--layers", "2",
         "--seq", "16", "--hidden", "32", "--vocab", "64"],
        n_devices=2,
    )
    assert "hand-1F1B stash=residuals" in out, out[-500:]
    assert "step   2" in out, out[-500:]


def test_gpt_train_pp_hand_interleaved_smoke():
    """Hand-scheduled INTERLEAVED 1F1B (chunk stash ring, --vpp composed
    with --hand-1f1b) LM example end-to-end."""
    out = _run_example(
        "examples/gpt/train_gpt_pp.py",
        ["--pp", "2", "--vpp", "2", "--hand-1f1b", "--steps", "3",
         "--layers", "4", "--seq", "16", "--hidden", "32",
         "--vocab", "64", "--nm", "4"],
        n_devices=2,
    )
    assert "hand-interleaved-1F1B vpp=2 stash=residuals" in out, out[-500:]
    assert "step   2" in out, out[-500:]


def test_gpt_train_cp_ring_smoke():
    """Context-parallel ring attention end-to-end in the example."""
    out = _run_example(
        "examples/gpt/train_gpt.py",
        [
            "--tiny", "--steps", "4", "--batch", "2", "--seq-len", "64",
            "--context-parallel", "ring", "--cp", "2",
        ],
        n_devices=4,
    )
    assert "cp=2(ring)" in out, out[-500:]


def test_gpt_train_cp_zigzag_smoke():
    """The causal-load-balanced zigzag layout end-to-end in the example
    (layout-aware input sharding + zigzag RoPE + zigzag loss shift)."""
    out = _run_example(
        "examples/gpt/train_gpt.py",
        [
            "--tiny", "--steps", "4", "--batch", "2", "--seq-len", "64",
            "--context-parallel", "ring_zigzag", "--cp", "2",
        ],
        n_devices=4,
    )
    assert "cp=2(ring_zigzag)" in out, out[-500:]


def test_gpt_train_tp_sp_moe_smoke():
    out = _run_example(
        "examples/gpt/train_gpt.py",
        [
            "--tiny", "--steps", "4", "--batch", "2", "--seq-len", "64",
            "--tp", "2", "--sequence-parallel", "--num-experts", "4",
        ],
        n_devices=4,
    )
    assert "sp=True experts=4" in out, out[-500:]


def test_bert_pretrain_checkpoint_resume(tmp_path):
    """Train 8 steps with checkpointing, resume to 16, and compare with
    an uninterrupted 16-step run: the resumed run must pick up at step 8
    AND produce the same remaining loss trajectory (bit-exact params from
    the checkpoint + fast-forwarded deterministic data stream)."""

    def losses(out):
        return [
            line.split("loss ", 1)[1]
            for line in out.splitlines()
            if line.startswith("chunk ")
        ]

    d = str(tmp_path / "ck")
    args = ["--tiny", "--ckpt-dir", d, "--save-every", "4", "--chunk", "4"]
    _run_example(
        "examples/bert/pretrain_bert.py", args + ["--steps", "8"]
    )
    out_resumed = _run_example(
        "examples/bert/pretrain_bert.py",
        args + ["--steps", "16", "--resume"],
    )
    assert "resumed from step 8" in out_resumed, out_resumed[-800:]
    out_full = _run_example(
        "examples/bert/pretrain_bert.py",
        ["--tiny", "--chunk", "4", "--steps", "16"],
    )
    # resumed chunks 0..1 == uninterrupted chunks 2..3 (steps 8..16)
    assert losses(out_resumed) == losses(out_full)[2:], (
        out_resumed[-600:],
        out_full[-600:],
    )


def test_serve_gpt_smoke(tmp_path):
    """Train -> checkpoint -> restore (bit-exact assert inside the
    example) -> serve through the AOT engine + paged cache + scheduler;
    the JSONL must carry the serving TTFT/throughput gauges."""
    import json

    d = str(tmp_path / "serve_demo")
    out = _run_example(
        "examples/simple/serve/serve_gpt.py",
        ["--dir", d, "--train-steps", "6", "--requests", "3",
         "--metrics-out", os.path.join(d, "serve.jsonl")],
        n_devices=1,
    )
    assert "round-trips: restored == trained" in out, out[-800:]
    assert "served 3 requests (0 shed)" in out, out[-800:]
    recs = [
        json.loads(l)
        for l in open(os.path.join(d, "serve.jsonl"))
        if l.strip()
    ]
    metrics = {r["metric"] for r in recs}
    assert {"serve/ttft_ms", "serve/tokens_per_s"} <= metrics, metrics
