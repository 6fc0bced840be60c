"""chip_smoke.py — the standing proof that the system starts on the chip.

One process, one command::

    python chip_smoke.py

It drives the repo's two documented entry points once each, at the full
width of the models they ship with, on whatever TPU JAX hands it:

1. **training** — BERT-Large (336 M) + FusedLAMB, seq 128, batch 128, the
   recipe's 20-prediction MLM head, through ``examples/bert/
   pretrain_bert.py::main`` (memmap corpus -> loader -> native MLM
   corruption -> DevicePrefetcher -> shard_map over dp -> FusedLAMB);
2. **serving** — ``InferenceEngine(GptConfig(), params, ServeConfig(...))``
   with its default ``verify=True``, ``.build()``, then
   ``ContinuousBatchingScheduler`` answering more requests than it has
   slots, with prompts in the 128 and 2048 prefill buckets;
3. **the trainer's verified build** — ``Trainer.build`` with its default
   ``verify="error"`` on the demo MLP, one step;
4. **four chips**, when the machine has them: the same recipe at dp=4, the
   GPT recipe at dp=2 x tp=2 with sequence parallelism, the trainer's
   verified build at dp=2 x tp=2 (its ZeRO update), and
   ``dryrun_multichip(4)``.

Weights are random from a seed, the corpus and prompts are generated, no
file outside the checkout's tracked tree is read, and nothing here stands
in for a missing chip: without a TPU the script exits non-zero before any
phase, a kernel that gave way to its jnp reference fails its phase, and a
serving fault the scheduler absorbed (retry, shed, rebuild) fails the run.
Every check raises; the last stdout line is printed only when all passed::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Wall-clock figures it prints are set-up facts, not benchmark metrics.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# the device, and what the process compiled
# ---------------------------------------------------------------------------


def require_tpu() -> dict:
    """Exit non-zero, naming what JAX found, unless device 0 is a TPU
    whose kind is in the peak table (exact lookup — no default peak)."""
    devices = jax.devices()
    d0 = devices[0]
    found = {
        "platform": d0.platform,
        "kind": d0.device_kind,
        "count": len(devices),
    }
    if d0.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, JAX found {found} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})",
            file=sys.stderr,
        )
        sys.exit(2)
    from apex_tpu.observability import meter

    meter.peak_flops_for(d0.device_kind)  # UnknownDeviceError if not listed
    meter.peak_hbm_bandwidth_for(d0.device_kind)
    return found


class CompileLog:
    """Backend-compile seconds and persistent-cache hits/misses, read
    from JAX's own monitoring events, split per phase."""

    def __init__(self):
        self.secs = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self) -> dict:
        out = {
            "compile_s": round(self.secs, 1),
            "cache_hits": self.hits,
            "cache_misses": self.misses,
        }
        self.secs, self.hits, self.misses = 0.0, 0, 0
        return out


def peak_bytes(devices=None) -> list:
    """``peak_bytes_in_use`` per device (the process's high-water mark).
    An empty ``memory_stats()`` is a failure on the chip — only the CPU
    backend has none."""
    peaks = []
    for d in devices if devices is not None else jax.devices():
        stats = d.memory_stats() or {}
        check(
            "peak_bytes_in_use" in stats,
            f"{d} reports no memory_stats (got {stats!r})",
        )
        peaks.append(int(stats["peak_bytes_in_use"]))
    return peaks


def _check_state_is_spread(what: str, devices) -> list:
    """``bytes_in_use`` per device, read while a phase's result is still
    alive: code that has only met one chip may put everything on device
    0."""
    held = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    check(None not in held, f"{what}: a device has no bytes_in_use: {held}")
    check(
        min(held) > 0.5 * max(held),
        f"{what}: device memory is lopsided: {held}",
    )
    return held


def _load_script(*relpath):
    """Import a script of the checkout that is not part of a package."""
    name = os.path.splitext(relpath[-1])[0]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, *relpath)
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _check_losses(losses, steps: int, what: str) -> None:
    check(len(losses) >= steps, f"{what}: {len(losses)} steps, want {steps}")
    check(
        all(np.isfinite(l) for l in losses),
        f"{what}: non-finite loss in {losses}",
    )
    check(
        losses[-1] < losses[0],
        f"{what}: loss did not fall ({losses[0]:.4f} -> {losses[-1]:.4f})",
    )


def _check_path(op: str, want: str = "pallas") -> None:
    from apex_tpu.ops import _dispatch

    got = _dispatch.last_paths().get(op)
    check(
        got == want,
        f"{op} took the {got!r} path, want {want!r} "
        f"(all paths: {_dispatch.last_paths()})",
    )


# ---------------------------------------------------------------------------
# phase 1: training, through examples/bert/pretrain_bert.py::main
# ---------------------------------------------------------------------------

#: BERT-Large phase-1 shape with chip history: seq 128, batch 128, K=20
TRAIN_ARGV = (
    "--steps", "8", "--chunk", "4", "--batch", "128", "--seq-len", "128",
    "--max-predictions-per-seq", "20",
)


def phase_train(
    argv=TRAIN_ARGV, *, steps: int = 8, ln_path: str = "pallas"
) -> dict:
    """``ln_path``: the recipe's ``--tiny`` width (64) is below the
    LayerNorm kernel's lane width, so the CPU test of this phase expects
    ``"jnp"`` there; on the chip the default stands."""
    from apex_tpu import _native
    from apex_tpu import parallel_state as ps
    from apex_tpu.ops import _dispatch

    ps.destroy_model_parallel()
    _dispatch.clear_paths()
    recipe = _load_script("examples", "bert", "pretrain_bert.py")
    try:
        out = recipe.main(list(argv))
    finally:
        ps.destroy_model_parallel()
    losses = out["losses"]
    _check_losses(losses, steps, "bert")
    check(
        np.isfinite(out["param_norm_end"])
        and out["param_norm_end"] != out["param_norm_start"],
        f"bert: parameters did not change (norm "
        f"{out['param_norm_start']} -> {out['param_norm_end']})",
    )
    _check_path("layer_norm", ln_path)
    mesh = out["mesh"]
    placed = sorted(
        d.id for d in jax.tree_util.tree_leaves(out["params"])[0].devices()
    )
    check(
        placed == sorted(d.id for d in mesh.devices.flat),
        f"bert: params live on devices {placed}, the mesh is "
        f"{mesh.devices.flat}",
    )
    # the recipe shards the batch over the mesh's dp axis and nothing else
    dp = mesh.shape["dp"]
    check(dp == mesh.size, f"bert: mesh {dict(mesh.shape)} is not all dp")
    batch = int(argv[argv.index("--batch") + 1])
    return {
        "steps": len(losses),
        "dp": dp,
        "bytes_in_use": _check_state_is_spread("bert", mesh.devices.flat),
        "per_device_batch": batch // dp,
        "loss_first": round(losses[0], 4),
        "loss_last": round(losses[-1], 4),
        "param_norm": [out["param_norm_start"], out["param_norm_end"]],
        "paths": _dispatch.last_paths(),
        "params_on_devices": placed,
        "native_input_pipeline": _native.available(),
    }


# ---------------------------------------------------------------------------
# phase 2: serving, through InferenceEngine + ContinuousBatchingScheduler
# ---------------------------------------------------------------------------

#: counters that must read zero after a clean run: each is a fault the
#: scheduler would absorb (retry, shed, rebuild) while the run exits 0
SERVE_ZERO_COUNTERS = (
    "serve/engine_faults", "serve/engine_rebuilds", "serve/retries",
    "serve/readmitted", "serve/shed", "serve/decode_timeouts",
    "serve/admission_faults", "serve/kv_alloc_faults", "serve/clamped",
)

#: max |engine logit - reference logit| allowed, engine in bf16 against
#: the unpaged f32 (true-f32 matmuls) ``GptModel.apply``.  tests/
#: test_serve.py pins 2e-4 for an f32 engine; widened for bf16 compute
#: through 12 layers: measured on v5e 0.023-0.028 over prefill and four
#: decode steps at both the 128 and 2048 buckets, on logits whose
#: largest magnitude is 3.0-3.3 (PR 21) — the bound is ~3.5x that.
SERVE_LOGIT_TOL = 0.1


def _reference_logits(cfg, params, token_ids):
    """Unpaged f32 reference: the full forward, every position's logits
    (the ``tests/test_serve.py`` reference, at true-f32 matmuls)."""
    from apex_tpu.models.gpt import GptModel, _tied_vocab_logits

    model = GptModel(dataclasses.replace(cfg, dtype=jnp.float32))

    @jax.jit
    def fwd(params, ids):
        h = model.apply(params, ids)
        return _tied_vocab_logits(params, model, h, sp_gathered=False)[:, 0]

    ids = jnp.asarray(np.asarray(token_ids, np.int32)[:, None])
    with jax.default_matmul_precision("highest"):
        return np.asarray(fwd(params, ids), np.float32)


def _logit_deviation(engine, cfg, params, prompt, decode_steps: int) -> dict:
    """Prefill ``prompt`` and teacher-force ``decode_steps`` tokens
    through the engine directly; compare every step's last-position
    logits with ONE reference forward over the final sequence (causal:
    position i's logits depend on tokens <= i only)."""
    serve = engine.serve
    pool = engine.pool
    pages = pool.alloc(pool.pages_for(len(prompt) + decode_steps))
    check(pages is not None, "page pool cannot hold the probe request")
    try:
        logits, tok = engine.prefill(
            prompt, pages[: pool.pages_for(len(prompt))]
        )
        got = [np.asarray(logits, np.float32)]
        seq = list(prompt)
        table = np.zeros(
            (serve.max_batch, serve.max_pages_per_seq), np.int32
        )
        table[0, : len(pages)] = pages
        tokens = np.zeros((serve.max_batch,), np.int32)
        lengths = np.zeros((serve.max_batch,), np.int32)
        for _ in range(decode_steps):
            seq.append(int(tok))
            tokens[0], lengths[0] = tok, len(seq)
            logits, nxt = engine.decode(tokens, lengths, table)
            got.append(np.asarray(logits[0], np.float32))
            tok = int(nxt[0])
    finally:
        pool.free(pages)
    ref = _reference_logits(cfg, params, seq)[len(prompt) - 1:]
    devs = [float(np.abs(g - r).max()) for g, r in zip(got, ref)]
    check(
        all(np.isfinite(g).all() for g in got),
        "engine produced non-finite logits",
    )
    return {
        "prompt_tokens": len(prompt),
        "bucket": engine.bucket_for(len(prompt)),
        "max_abs_dev": [round(d, 5) for d in devs],
        "ref_max_abs": round(float(np.abs(ref).max()), 4),
    }


def _full_serve_shape():
    from apex_tpu.models.gpt import GptConfig
    from apex_tpu.serve import ServeConfig

    # the pool sized as this engine lets a 16 GB chip be filled: 4096
    # usable pages x 0.75 MiB (12 layers x K,V x 16 heads x 16 x 64
    # bf16) = 3 GiB of KV beside 0.8 GB of f32 weights.  The compiled
    # decode step holds about 2.8x the pool (XLA's memory analysis for
    # v5e: 9.3 GiB at this size, 17.3 GiB at 8193 pages), so this is
    # near the most that leaves room for the reference forward.
    return GptConfig(), ServeConfig(
        page_size=16, num_pages=4097, max_batch=32, max_pages_per_seq=128
    )


def phase_serve(
    cfg=None,
    serve=None,
    *,
    # 36 prompts for the 128 bucket and 4 for the 2048 bucket (1500 is
    # not a tile multiple: the flash kernel's padding path), 40 requests
    # on 32 slots, uneven lengths so slots free while others still decode
    prompt_lens=(100,) * 18 + (1500, 1500) + (72,) * 18 + (1500, 1500),
    new_tokens=(32, 48, 64),
    decode_probe_steps: int = 4,
    tol: float = SERVE_LOGIT_TOL,
    seed: int = 0,
) -> dict:
    from apex_tpu.models.gpt import GptModel
    from apex_tpu.observability import MetricRegistry
    from apex_tpu.observability.metrics import board
    from apex_tpu.ops import _dispatch
    from apex_tpu.serve import (
        ContinuousBatchingScheduler,
        InferenceEngine,
        Request,
    )
    from apex_tpu.serve.scheduler import SHED_REASONS

    if cfg is None:
        cfg, serve = _full_serve_shape()
    rs = np.random.RandomState(seed)
    params = GptModel(cfg).init(
        jax.random.PRNGKey(seed), jnp.zeros((8, 1), jnp.int32)
    )
    registry = MetricRegistry(fetch_every=1)
    engine = InferenceEngine(cfg, params, serve, registry=registry)
    check(engine.serve.verify, "ServeConfig.verify must default to True")

    # build, reading the dispatch log after each group of programs: the
    # short buckets + decode first, the longest bucket on its own
    buckets = sorted({engine.bucket_for(n) for n in prompt_lens})
    check(len(buckets) >= 2, f"want two prefill buckets, got {buckets}")
    _dispatch.clear_paths()
    engine.build(buckets=tuple(buckets[:-1]))
    _check_path("paged_decode_attention")
    _check_path("layer_norm")
    _dispatch.clear_paths()
    engine.build(buckets=(buckets[-1],))
    _check_path("flash_attention")
    compiled = dict(engine.compile_counts)

    sched = ContinuousBatchingScheduler(engine, registry=registry)
    check(
        len(prompt_lens) > serve.max_batch,
        "want more requests than slots, so that some are admitted "
        "mid-stream",
    )
    requests = [
        sched.submit(Request(
            prompt=[int(t) for t in rs.randint(0, cfg.vocab_size, size=n)],
            max_new_tokens=new_tokens[i % len(new_tokens)],
        ))
        for i, n in enumerate(prompt_lens)
    ]
    sched.run()

    for r in requests:
        check(
            r.status == "done" and len(r.tokens) == r.max_new_tokens
            and r.retries == 0,
            f"request {r.rid}: status={r.status!r} "
            f"tokens={len(r.tokens)}/{r.max_new_tokens} "
            f"retries={r.retries} shed_reason={r.shed_reason!r}",
        )
    check(not sched.shed, f"{len(sched.shed)} requests shed")
    registry.fetch()
    counters = registry.values()
    zero = SERVE_ZERO_COUNTERS + tuple(
        f"serve/shed_{reason}" for reason in SHED_REASONS
    )
    nonzero = {k: counters[k] for k in zero if counters.get(k)}
    check(not nonzero, f"fault counters are not zero: {nonzero}")
    check(
        counters.get("serve/completed") == len(requests),
        f"serve/completed={counters.get('serve/completed')}, "
        f"want {len(requests)}",
    )
    check(engine.rebuilds == 0, f"engine.rebuilds={engine.rebuilds}")
    check(engine.retraces == 0, f"engine.retraces={engine.retraces}")
    check(
        engine.compile_counts == compiled,
        f"serving compiled past the build: {engine.compile_counts} "
        f"vs {compiled}",
    )
    check(
        any(r.first_decode_iter for r in requests),
        "no request was admitted mid-stream",
    )
    check(engine.pool.in_use == 0, f"{engine.pool.in_use} pages leaked")

    # numerics: one short and one long prompt against the reference
    probes = [
        _logit_deviation(
            engine, cfg, params,
            [int(t) for t in rs.randint(0, cfg.vocab_size, size=n)],
            decode_probe_steps,
        )
        for n in (min(prompt_lens), max(prompt_lens))
    ]
    worst = max(max(p["max_abs_dev"]) for p in probes)
    check(
        worst <= tol,
        f"logits deviate {worst} from the unpaged reference, bound {tol} "
        f"({probes})",
    )
    check(engine.pool.in_use == 0, "probe leaked pages")
    return {
        "requests": len(requests),
        "tokens_out": sum(len(r.tokens) for r in requests),
        "decode_iters": engine.decode_iters,
        "prefill_calls": engine.prefill_calls,
        "programs": sorted(compiled),
        "logit_probes": probes,
        "logit_tol": tol,
        # analysis.memory.estimate_peak over the compiled programs, as
        # the verified build published it, beside XLA's own figure for
        # the same executables (compare peak_bytes_in_use)
        "static_peak_hbm_estimate": board.get("serve/peak_hbm_bytes"),
        "xla_memory_analysis": {
            name: _xla_total_bytes(exe)
            for name, exe in [("decode", engine._programs["decode", None])] + [
                (f"prefill_{b}", exe)
                for (kind, b), exe in engine._programs.items()
                if kind == "prefill"
            ]
        },
    }


def _xla_total_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return int(
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    )


# ---------------------------------------------------------------------------
# phase 3: Trainer.build with its default verify="error"
# ---------------------------------------------------------------------------


def phase_trainer(dp: int = 1, tp: int = 1) -> dict:
    """One chip builds the replicated update; dp > 1 the ZeRO one
    (reduce-scatter / sharded update / all-gather over dp), which the
    build verifies against the collectives XLA actually compiled."""
    from apex_tpu import parallel_state as ps
    from apex_tpu.train import build_demo

    ps.destroy_model_parallel()
    step = build_demo(dp, tp, devices=jax.devices()[: dp * tp])
    check(step.config.verify == "error", "verify must default to error")
    mode = "zero" if dp > 1 else "ddp"
    check(step.mode == mode, f"demo trainer is {step.mode!r}, want {mode!r}")
    state, aux = step(step.state, step.example_batch)
    first = float(aux["loss"])
    for _ in range(3):
        state, aux = step(state, step.example_batch)
    loss = float(aux["loss"])
    check(
        np.isfinite(loss) and loss < first,
        f"demo trainer loss {first} -> {loss}",
    )
    ps.destroy_model_parallel()
    return {
        "mesh": {"dp": dp, "tp": tp}, "mode": step.mode,
        "loss_first": first, "loss_last": loss,
    }


# ---------------------------------------------------------------------------
# phase 4: four chips
# ---------------------------------------------------------------------------

GPT_TP_ARGV = (
    "--tp", "2", "--sequence-parallel", "--steps", "8", "--chunk", "4",
    "--batch", "8", "--seq-len", "512",
)


def phase_gpt_tp(argv=GPT_TP_ARGV, *, steps: int = 8) -> dict:
    from apex_tpu import parallel_state as ps

    ps.destroy_model_parallel()
    recipe = _load_script("examples", "gpt", "train_gpt.py")
    try:
        out = recipe.main(list(argv))
    finally:
        ps.destroy_model_parallel()
    _check_losses(out["losses"], steps, "gpt dp x tp")
    shape = dict(out["mesh"].shape)
    check(shape.get("tp") == 2, f"gpt leg ran on mesh {shape}, want tp=2")
    return {
        "steps": len(out["losses"]),
        "mesh": shape,
        "bytes_in_use": _check_state_is_spread(
            "gpt dp x tp", out["mesh"].devices.flat
        ),
        "loss_first": round(out["losses"][0], 4),
        "loss_last": round(out["losses"][-1], 4),
    }


def phase_dryrun(n: int) -> dict:
    _load_script("__graft_entry__.py").dryrun_multichip(n)
    return {"devices": n}


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def run_phase(name: str, fn, compile_log: CompileLog, report: dict, **kw):
    print(f"\n=== {name} ===", flush=True)
    t0 = time.monotonic()
    out = fn(**kw)
    out.update(compile_log.take())
    out["wall_s"] = round(time.monotonic() - t0, 1)
    out["peak_bytes_in_use"] = peak_bytes()
    report[name] = out
    print(f"--- {name} OK: {json.dumps(out, default=str)}", flush=True)
    gc.collect()
    return out


def main() -> int:
    device = require_tpu()
    # a mesh laid out in naive device order is a failure here, not a
    # warning (parallel_state._ici_device_mesh)
    warnings.filterwarnings(
        "error", message="mesh_utils.create_device_mesh failed"
    )
    import jaxlib

    from apex_tpu import _native
    from apex_tpu.ops import _dispatch
    from apex_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    check(cache_dir is not None, "no compile cache directory in use")

    def cache_entries() -> int:
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    cache_before = cache_entries()
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:  # shipped without metadata
        libtpu = "unknown"
    print(
        f"device: {device} | jax {jax.__version__} jaxlib "
        f"{jaxlib.__version__} libtpu {libtpu}\n"
        f"compile cache: {cache_dir} ({cache_before} entries; "
        f"JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')!r})\n"
        f"native input pipeline: {_native.available()}",
        flush=True,
    )
    check(
        not _dispatch.pallas_interpret() and _dispatch.use_pallas(),
        "Pallas kernels would not compile for the chip "
        f"(interpret={_dispatch.pallas_interpret()}, "
        f"use_pallas={_dispatch.use_pallas()}, APEX_TPU_DISABLE_PALLAS="
        f"{os.environ.get('APEX_TPU_DISABLE_PALLAS')!r})",
    )

    report: dict = {}
    log = CompileLog()
    n = device["count"]
    train = run_phase("train", phase_train, log, report)
    check(train["dp"] == n, f"recipe ran at dp={train['dp']} on {n} devices")
    run_phase("serve", phase_serve, log, report)
    run_phase("trainer", phase_trainer, log, report)
    if n >= 4:
        run_phase("gpt_dp2_tp2_sp", phase_gpt_tp, log, report)
        run_phase("trainer_dp2_tp2", phase_trainer, log, report, dp=2, tp=2)
        run_phase("dryrun_multichip", phase_dryrun, log, report, n=4)
    else:
        print(f"\nmultichip: skipped ({n} device)", flush=True)

    print(
        f"\ncompile cache: {cache_before} -> {cache_entries()} entries; "
        f"misses this run: "
        f"{sum(p['cache_misses'] for p in report.values())}, hits: "
        f"{sum(p['cache_hits'] for p in report.values())}",
        flush=True,
    )
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
        json.dump({"device": device, "phases": report}, f, indent=2,
                  default=str)
        f.write("\n")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
