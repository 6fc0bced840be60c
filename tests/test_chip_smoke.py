"""``chip_smoke.py`` on the CPU: its phases run at tiny widths with the
Pallas kernels in interpret mode, its checks can fail, it refuses to run
without a TPU, and the compile cache lands where it is meant to."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402
from apex_tpu.models.gpt import GptConfig  # noqa: E402
from apex_tpu.ops import _dispatch  # noqa: E402
from apex_tpu.serve import ServeConfig  # noqa: E402
from apex_tpu.utils import compile_cache  # noqa: E402

TINY_TRAIN = (
    "--tiny", "--steps", "12", "--chunk", "4", "--batch", "32",
    "--seq-len", "32", "--max-predictions-per-seq", "8", "--lr", "0.01",
)


def tiny_serve(**kw):
    cfg = GptConfig(
        vocab_size=128, hidden_size=128, num_layers=2, num_heads=2,
        intermediate_size=256, max_seq_len=64, dtype=jnp.float32,
    )
    serve = ServeConfig(
        page_size=8, num_pages=40, max_batch=2, max_pages_per_seq=8
    )
    args = dict(
        prompt_lens=(5, 40, 6, 7), new_tokens=(4, 6),
        decode_probe_steps=2, tol=2e-4,
    )
    args.update(kw)
    return cs.phase_serve(cfg, serve, **args)


@pytest.fixture
def force_pallas():
    _dispatch.set_use_pallas(True)  # interpret mode on the CPU backend
    yield
    _dispatch.set_use_pallas(None)


# ---------------------------------------------------------------------------
# (a) the phases complete at tiny widths, and their checks can fail
# ---------------------------------------------------------------------------


class TestPhases:
    def test_train_phase_runs_the_recipe(self, eight_devices, monkeypatch):
        # the CPU backend reports no memory_stats; the chip must
        monkeypatch.setattr(
            cs, "_check_state_is_spread", lambda what, devices: []
        )
        # --tiny is 64 wide, under the LayerNorm kernel's lane width (the
        # kernel path inside a phase: test_serve_phase_end_to_end)
        out = cs.phase_train(TINY_TRAIN, steps=12, ln_path="jnp")
        assert out["steps"] == 12 and out["dp"] == 8
        assert out["per_device_batch"] == 4
        assert out["loss_last"] < out["loss_first"]
        assert out["params_on_devices"] == list(range(8))

    def test_train_phase_trips_on_a_jnp_layer_norm(self, eight_devices):
        # what the chip run asks for: the kernel, not its reference
        with pytest.raises(cs.SmokeFailure, match="layer_norm took the"):
            cs.phase_train(TINY_TRAIN, steps=12)

    def test_state_spread_needs_every_device_to_report(self):
        class Dev:
            def __init__(self, stats):
                self.memory_stats = lambda: stats

        full, half = {"bytes_in_use": 100}, {"bytes_in_use": 60}
        one = {"bytes_in_use": 1}
        assert cs._check_state_is_spread("x", [Dev(full), Dev(half)]) == [
            100, 60,
        ]
        with pytest.raises(cs.SmokeFailure, match="lopsided"):
            cs._check_state_is_spread("x", [Dev(full), Dev(one)])
        for silent in ({}, None):
            with pytest.raises(cs.SmokeFailure, match="no bytes_in_use"):
                cs._check_state_is_spread("x", [Dev(full), Dev(silent)])

    def test_serve_phase_end_to_end(self, force_pallas):
        out = tiny_serve()
        assert out["requests"] == 4 and out["tokens_out"] == 4 + 6 + 4 + 6
        assert out["programs"] == ["decode", "prefill_64", "prefill_8"]
        assert [p["bucket"] for p in out["logit_probes"]] == [8, 64]

    def test_serve_phase_trips_on_a_jnp_kernel(self):
        with pytest.raises(
            cs.SmokeFailure, match="paged_decode_attention took the 'jnp'"
        ):
            tiny_serve()

    def test_serve_phase_trips_on_a_shed_request(self, force_pallas):
        # the shape of a Mosaic runtime error: prefill keeps faulting,
        # the scheduler retries, sheds(retries_exhausted) and drains —
        # rc 0 unless the smoke reads the ledger
        from apex_tpu.resilience import chaos

        with chaos.inject(chaos.Fault(
            chaos.SERVE_PREFILL, steps=(1, 2, 3), mode="raise",
        )):
            with pytest.raises(cs.SmokeFailure, match="status='shed'"):
                tiny_serve()

    def test_serve_phase_trips_on_an_absorbed_fault(self, force_pallas):
        # one transient decode fault: every request still finishes with
        # its full token count; only the counters remember
        from apex_tpu.resilience import chaos

        with chaos.inject(chaos.Fault(
            chaos.SERVE_DECODE, steps=(2,), mode="raise", max_hits=1,
        )):
            with pytest.raises(cs.SmokeFailure, match="retries=1"):
                tiny_serve()

    def test_serve_phase_trips_on_the_logit_bound(self, force_pallas):
        with pytest.raises(cs.SmokeFailure, match="deviate"):
            tiny_serve(tol=-1.0)

    def test_trainer_phase_builds_verified(self, eight_devices):
        assert cs.phase_trainer()["mode"] == "ddp"
        out = cs.phase_trainer(2, 2)
        assert out["mode"] == "zero" and out["loss_last"] < out["loss_first"]

    def test_loss_checks(self):
        cs._check_losses([3.0, 2.5, 2.0], 3, "x")
        with pytest.raises(cs.SmokeFailure, match="did not fall"):
            cs._check_losses([2.0, 2.5], 2, "x")
        with pytest.raises(cs.SmokeFailure, match="non-finite"):
            cs._check_losses([2.0, float("nan")], 2, "x")
        with pytest.raises(cs.SmokeFailure, match="1 steps, want 8"):
            cs._check_losses([2.0], 8, "x")

    def test_empty_memory_stats_is_a_failure(self):
        # the CPU backend reports none — on the chip that is a failure
        with pytest.raises(cs.SmokeFailure, match="no memory_stats"):
            cs.peak_bytes()


# ---------------------------------------------------------------------------
# (b) no TPU, no result
# ---------------------------------------------------------------------------


def _run_smoke(cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    return subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=cwd, capture_output=True,
        text=True, env=dict(env, JAX_PLATFORMS="cpu"), timeout=120,
    )


def test_refuses_the_cpu_and_names_it():
    proc = _run_smoke(REPO)
    assert proc.returncode not in (0, None)
    assert "'platform': 'cpu'" in proc.stderr
    assert "JAX_PLATFORMS='cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_alone_in_a_directory_no_phase_can_run(tmp_path):
    """With nothing else of the repo beside it the script has no program
    to drive: on the chip it gets past the TPU check and dies on the first
    import of the package, before any result line."""
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        (tmp_path / "chip_smoke.py").write_text(f.read())
    proc = _run_smoke(tmp_path)
    assert proc.returncode != 0 and '"ok"' not in proc.stdout
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "-c", "import chip_smoke as c; c.phase_trainer()"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env=dict(env, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode != 0
    assert "No module named 'apex_tpu'" in proc.stderr


# ---------------------------------------------------------------------------
# (c) where the compile cache goes
# ---------------------------------------------------------------------------


class TestCompileCache:
    @pytest.fixture
    def config_updates(self, monkeypatch):
        seen = []
        monkeypatch.setattr(
            jax.config, "update", lambda k, v: seen.append((k, v))
        )
        return seen

    def test_env_dir_is_left_to_jax(self, monkeypatch, config_updates):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert compile_cache.enable_compile_cache() == "/somewhere/else"
        assert "jax_compilation_cache_dir" not in dict(config_updates)

    def test_unset_on_a_chip_is_the_checkout(
        self, monkeypatch, config_updates
    ):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        want = os.path.join(REPO, ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert dict(config_updates)["jax_compilation_cache_dir"] == want

    def test_cpu_runs_stay_out(self, monkeypatch, config_updates):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache.enable_compile_cache() is None
        assert config_updates == []
        assert not os.path.exists(os.path.join(REPO, ".jax_cache"))

    def test_same_directory_from_any_cwd_and_process(self, tmp_path):
        code = (
            "import sys; sys.path.insert(0, sys.argv[1]);"
            "from apex_tpu.utils.compile_cache import compile_cache_dir;"
            "print(compile_cache_dir())"
        )
        env = {
            k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"
        }
        env["JAX_PLATFORMS"] = "cpu"
        dirs = {
            subprocess.run(
                [sys.executable, "-c", code, REPO], cwd=cwd, env=env,
                capture_output=True, text=True, timeout=120, check=True,
            ).stdout.strip()
            for cwd in (REPO, str(tmp_path))
        }
        assert dirs == {os.path.join(REPO, ".jax_cache")}

    def test_one_helper_and_its_callers(self):
        """Only the helper sets JAX's cache options; every chip entry
        point goes through it."""
        naming, calling = [], []
        for base, dirs, files in os.walk(REPO):
            dirs[:] = [
                d for d in dirs
                if not d.startswith(".") and d not in ("_unpacked", "chiprun_out")
            ]
            for name in files:
                if not name.endswith((".py", ".sh")):
                    continue
                path = os.path.join(base, name)
                if os.path.abspath(path) == os.path.abspath(__file__):
                    continue
                with open(path, encoding="utf-8") as f:
                    text = f.read()
                rel = os.path.relpath(path, REPO)
                if (
                    "jax_compilation_cache_dir" in text
                    or "jax_persistent_cache" in text
                ):
                    naming.append(rel)
                if "enable_compile_cache()" in text:
                    calling.append(rel)
        assert naming == [os.path.join("apex_tpu", "utils", "compile_cache.py")]
        assert sorted(calling) == sorted([
            os.path.join("apex_tpu", "utils", "compile_cache.py"),
            "bench.py",
            "chip_smoke.py",
            os.path.join("examples", "bert", "pretrain_bert.py"),
            os.path.join("examples", "gpt", "train_gpt.py"),
            os.path.join("examples", "imagenet", "main_amp.py"),
            os.path.join("tools", "serve_bench.py"),
        ])
