"""Benchmark harness — the five BASELINE parity configs.

Default (no args) runs BASELINE config #3, the north star: BERT-Large
phase-1 pretraining step (seq 128) with FusedLAMB + fused LayerNorm + flash
attention, and prints ONE JSON line {"metric", "value", "unit",
"vs_baseline"} — the driver contract.  ``--config all`` (or a config name)
additionally runs the other BASELINE.md table rows:

  #1 resnet50     ResNet-50 synthetic-ImageNet train step, single device
                  (≙ examples/imagenet/main_amp.py)                [img/s]
  #3 bert_lamb    BERT-Large + FusedLAMB (north star)          [MFU, step]
  #4 mha          fused self-attention vs unfused composition
                  (≙ apex/contrib/multihead_attn plots)          [speedup]
     train3d      the composable trainer (apex_tpu.train) at dp=2, tp=2,
                  and dp=2 x tp=2 — REPLACES the old degenerate
                  ddp_syncbn (dp=1) / tp_gpt (tp=1) proxies in the
                  multi-device slot: its rows are honest only when the
                  mesh is real (dp/tp >= 2), and bench_diff
                  --check-schema refuses degenerate train3d rows
                  outright                                     [step time]

The old ddp_syncbn (#2) and tp_gpt (#5) configs remain invocable by name
for single-config comparisons against historical BENCH_all rounds:

  #2 ddp_syncbn   ResNet-50 + DDP + SyncBatchNorm over a dp mesh of all
                  available devices (≙ apex/parallel/*)            [img/s]
  #5 tp_gpt       GPT block train step over a tp mesh of all available
                  devices (≙ tensor_parallel/layers.py)       [step time]

vs_baseline: #3 = MFU / 0.50 (the BASELINE.json ≥50%-MFU target); #4 =
speedup over the unfused composition (its own reference baseline, as in the
reference's README plots); #1/#2/#5 = null — the reference publishes no
absolute numbers for these (BASELINE.md "published: {}"), so the honest
record is the measurement itself with its basis in the unit string.

MFU accounting per BASELINE.md: FLOPs/step = 6·N·T, peak = per-chip bf16
peak × chips the program is placed on.  Timing discipline: K steps inside
one jitted ``lax.scan`` (donated carry — the idiomatic TPU train loop), a
device→host transfer of the final loss as the sync point, median over
repeated chunks.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


# single source of truth for metric names, shared by every bench's _emit
_METRIC_NAMES = {
    "resnet50": "resnet50_imgs_per_sec",
    "ddp_syncbn": "ddp_syncbn_resnet50_imgs_per_sec",
    "bert_lamb": "bert_large_lamb_mfu",
    "mha": "mha_fused_speedup",
    "tp_gpt": "tp_gpt_block_step_ms",
    "train3d": "train3d_dp2tp2_step_ms",
    "long_attn": "long_context_flash_attn_tflops",
    "zero": "zero_lamb_int8_wire_speedup",
    "serve": "serve_decode_tokens_per_s",
    "fleet": "fleet_chaos_goodput_pct",
    "all": "bert_large_lamb_mfu",  # the headline stands in for the batch
}


# Headline remat policy (dots | sums | full) — one read shared by the
# main() fail-fast guard and bench_bert_lamb's default config.
_BENCH_POLICY = os.environ.get("APEX_TPU_BENCH_POLICY", "dots")
# --lint: run the apex_tpu.analysis passes (docs/analysis.md) over the
# headline step's jaxpr + compiled HLO and emit the finding counts as a
# metric line.  Env var so `--config all` subprocess wrappers inherit it.
_BENCH_LINT = os.environ.get("APEX_TPU_BENCH_LINT", "") == "1"

# Per-chip dense bf16 peak FLOP/s — ONE model shared with live
# telemetry (apex_tpu.observability.meter), so bench artifacts and a
# run's --metrics-out JSONL can never disagree on the MFU denominator.
from apex_tpu.observability.meter import (  # noqa: E402
    chip_peak_flops as _chip_peak,
    transformer_train_flops as _train_flops,
)

# Optional JSONL sink mirroring every _emit line (--metrics-out): the
# stdout contract for the driver stays byte-identical, the file gets
# the same records for trajectory diffing.
_METRICS_SINK = None

# Optional flight recorder (--flight / APEX_TPU_FLIGHT): every emitted
# metric line lands in its event log, and an unhandled exception dumps
# the black box — the crash forensics for a bench that dies
# mid-config (docs/observability.md).
_FLIGHT = None

# Every emitted record, in-memory — what --gate hands tools/bench_diff.py
# after the configs finish (degenerate rows ride along; the gate excludes
# them itself, so the exclusion rule lives in ONE place).
_GATE_RECORDS = []


def _emit(metric, value, unit, vs_baseline, degenerate=False):
    """``degenerate=True`` marks a multi-device config that ran with only
    one device visible (dp=1/tp=1): the number is a valid single-chip
    measurement but does NOT exercise the config's collective path."""
    rec = {
        "metric": metric,
        "value": value,
        "unit": unit,
        "vs_baseline": vs_baseline,
    }
    if degenerate:
        rec["degenerate"] = True
    print(json.dumps(rec), flush=True)
    _GATE_RECORDS.append(rec)
    if _METRICS_SINK is not None:
        _METRICS_SINK.write(rec)
    if _FLIGHT is not None:
        _FLIGHT.note("bench_metric", **rec)


def _time_chunks(fn, carry, chunk, trials, profile=None, reduce="median"):
    """Per-step time of ``fn`` (a jitted scan chunk on ``carry``).

    Warmup (compile + one chunk) runs BEFORE the optional ``profile``
    context is entered, so a collected trace covers only steady state.
    Returns ``(step_time, carry, last_sync)`` — last_sync is the final
    synced scalar (the loss for the train benches: the cheap end-to-end
    sanity signal recorded in the unit string).
    """
    carry, sync = fn(*carry)  # warmup/compile — outside the profile window
    last = float(jnp.sum(sync))
    times = []
    with profile if profile is not None else contextlib.nullcontext():
        for _ in range(trials):
            t0 = time.perf_counter()
            carry, sync = fn(*carry)
            last = float(jnp.sum(sync))  # device->host: the sync point
            times.append((time.perf_counter() - t0) / chunk)
    times.sort()
    t = times[0] if reduce == "min" else times[len(times) // 2]
    return t, carry, last


# ---------------------------------------------------------------------------
# #3 BERT-Large + FusedLAMB (north star, the default headline)
# ---------------------------------------------------------------------------


def bench_bert_lamb(trace_dir=None, batch=128, chunk=6, trials=3,
                    cfg_kwargs=None, mlm_loss_chunks="auto",
                    max_predictions_per_seq=20, emit=True):
    """Returns (mfu, step_time, loss, mfu_exec) — mfu is the 6·N·T
    recipe-parity headline, mfu_exec the executed-FLOPs utilization
    (equal for the dense head).  ``cfg_kwargs`` overrides the tuned
    model config (tools/mfu_sweep.py reuses this function for its variants,
    so sweep numbers and the headline stay comparable).

    ``max_predictions_per_seq``: fixed-K masked-position MLM head (the
    reference recipe's masked_lm_positions input; 20 is its phase-1 value
    at seq 128).  The r2 headline scored the MLM head on all 128 positions
    — ~3.1 TFLOP/step of vocab matmul where the recipe does ~0.5;
    None restores that dense-label variant.  ``mlm_loss_chunks="auto"``
    resolves to unchunked for the packed head and the measured-best 16
    for dense; an explicit None always means unchunked."""
    import apex_tpu.utils
    from apex_tpu.models import (
        BertForPreTraining,
        bert_large_config,
        bert_pretrain_loss,
    )
    from apex_tpu.optimizers import fused_lamb

    seq_len = 128
    # Measured on the v5e chip (tools/mfu_sweep.py): scan-over-layers spends
    # ~1/3 of the step copying remat saves into (L, ...) stacked buffers
    # (0.41 MFU); unrolling removes it (0.45); recomputing the attention
    # core (drops the f32 (B,H,S,S) saves) + chunking the MLM loss (the
    # 2 GB f32 logits never exist) reaches 0.53.
    if cfg_kwargs is None:
        # remat_prevent_cse=False on the unrolled path is deliberate: XLA
        # keeps whichever forward activations fit HBM instead of honoring
        # the full recompute (same values; 316 ms vs 371 ms measured) —
        # the right trade on one chip at batch 128.
        # _BENCH_POLICY lets the on-chip queue flip the headline remat
        # policy (dots vs the staged "sums" epilogue-fusion bet,
        # docs/mfu.md lever #1) without editing code mid-window.
        cfg_kwargs = dict(
            remat=True, remat_policy=_BENCH_POLICY, scan_layers=False,
            remat_attention=True, remat_prevent_cse=False,
        )
    cfg = bert_large_config(**cfg_kwargs)
    model = BertForPreTraining(cfg)
    tx = fused_lamb(learning_rate=1e-3, weight_decay=0.01)

    key = jax.random.PRNGKey(0)
    ids = jax.random.randint(key, (seq_len, batch), 0, cfg.vocab_size)
    labels = jnp.where(ids % 7 == 0, ids, -1)
    batch_data = {
        "input_ids": ids,
        "token_type_ids": jnp.zeros_like(ids),
        "attention_mask": jnp.ones((batch, seq_len), jnp.int32),
        "mlm_labels": labels,
        "nsp_labels": jnp.zeros((batch,), jnp.int32),
    }
    if max_predictions_per_seq:
        from apex_tpu.data import pack_mlm_predictions

        pos, pids, w = pack_mlm_predictions(
            labels, max_predictions_per_seq
        )
        batch_data.update(
            mlm_positions=jnp.asarray(pos),
            mlm_label_ids=jnp.asarray(pids),
            mlm_weights=jnp.asarray(w),
        )
    if mlm_loss_chunks == "auto":
        # packed head: the (K·B, V) logits are small — unchunked.  Dense
        # fallback: never materialize the full (S·B, V) f32 logits (~2 GB
        # at batch 128); 16 is the measured-best chunking.  An explicit
        # None always means unchunked.
        mlm_loss_chunks = None if max_predictions_per_seq else 16

    params = model.init(jax.random.PRNGKey(1), ids)
    opt_state = tx.init(params)
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))
    # the MFU denominator: the devices the step runs on, read before the
    # donated params are consumed — not every device the host can see
    placed_on = jax.tree_util.tree_leaves(params)[0].devices()

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_chunk(params, opt_state):
        def body(carry, _):
            params, opt_state = carry
            loss, grads = jax.value_and_grad(
                lambda p: bert_pretrain_loss(
                    p, model, batch_data, mlm_loss_chunks=mlm_loss_chunks
                )
            )(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = jax.tree_util.tree_map(jnp.add, params, updates)
            return (params, opt_state), loss

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), None, length=chunk
        )
        return (params, opt_state), losses[-1]

    timed_fn = train_chunk
    hlo_out = os.environ.get("APEX_TPU_BENCH_HLO_OUT")
    if hlo_out or _BENCH_LINT:
        # Compiled-HLO text of the headline step, for the trace↔source
        # join (tools/trace_summary.py TRACE --hlo FILE — the docs/mfu.md
        # lever-#2 copies attribution).  AOT lower().compile() does NOT
        # land in the jit dispatch cache, so dispatching train_chunk
        # afterwards would pay a SECOND full compile — time the compiled
        # executable itself instead (same program, donation semantics
        # preserved).  --lint
        # rides the same single compile: the analysis passes read the
        # executable's text rather than paying their own.
        compiled = train_chunk.lower(params, opt_state).compile()
        module_text = compiled.as_text()  # one render serves both uses
        if hlo_out:
            with open(hlo_out, "w") as f:
                f.write(module_text)
        timed_fn = compiled
    if _BENCH_LINT:
        from apex_tpu import analysis

        donated = sum(
            len(jax.tree_util.tree_leaves(a)) for a in (params, opt_state)
        )
        lint_hlo_text = module_text
        # APEX_TPU_BENCH_HBM_BUDGET (bytes) arms the static peak-HBM
        # gate on the headline step; unset leaves the memory pass
        # reporting-only (the peak still rides the unit string below)
        hbm_budget = os.environ.get("APEX_TPU_BENCH_HBM_BUDGET")
        report = analysis.lint_hlo(
            lint_hlo_text, donated=donated,
            hbm_budget=int(hbm_budget) if hbm_budget else None,
            name="bert_lamb/train_chunk",
        )
        report.extend(analysis.lint_jaxpr(
            jax.make_jaxpr(train_chunk)(params, opt_state),
            name="bert_lamb/train_chunk",
        ).findings)
        analysis.publish_report(report)
        print(report.render(), file=sys.stderr)
        _emit(
            "graph_lint_errors",
            float(len(report.errors())),
            "ERROR findings (bert_lamb step; warnings=%d, rules=%s; "
            "docs/analysis.md)" % (
                len(report.warnings()), ",".join(report.rule_ids()) or "-"
            ),
            None,
        )
        # the sharding/memory half of the linter (ISSUE 9): ERROR count
        # scoped to the sharding-conformance/reshard/budget rules, plus
        # the static peak-HBM estimate of the same compiled module —
        # the record rides the standard bench-line schema that
        # tools/bench_diff.py --check-schema enforces
        _SHARD_RULES = (
            "sharding-replicated", "sharding-mismatch",
            "reshard-unplanned", "reshard-plan", "memory-budget",
        )
        shard_errors = sum(
            1 for f in report.errors() if f.rule in _SHARD_RULES
        )
        est = analysis.memory.estimate_peak(lint_hlo_text)
        analysis.memory.publish_peak(est)
        _emit(
            "graph_lint_shard_errors",
            float(shard_errors),
            "sharding/reshard/memory ERROR findings (bert_lamb step; "
            "peak_hbm=%.1fMiB; budget %s; docs/analysis.md)" % (
                est["peak_bytes"] / (1 << 20),
                ("%s bytes" % hbm_budget) if hbm_budget
                else "unarmed (APEX_TPU_BENCH_HBM_BUDGET)",
            ),
            None,
        )
        # the kernel half of the linter (ISSUE 10): the three shipped
        # Pallas kernels at their default configs, judged compile-free
        # (VMEM/tiling/coverage/dead-tiles — docs/analysis.md "Kernel
        # passes"); ERROR count rides the bench_diff schema so a
        # kernel-config regression gates like shard errors do
        krep = analysis.kernels.analyze_default_kernels()
        analysis.kernels.publish_kernel_report(krep)
        kernel_waste = max(
            [
                (e.get("dead_tiles") or {}).get("waste_fraction", 0.0)
                for e in krep.sections["kernels"]
            ] or [0.0]
        )
        _emit(
            "graph_lint_kernel_errors",
            float(len(krep.errors())),
            "kernel-pass ERROR findings (flash/layer_norm/decode "
            "defaults; warnings=%d; causal dead-tile waste=%.3f; "
            "docs/analysis.md)" % (len(krep.warnings()), kernel_waste),
            None,
        )
        # the host-side half of the linter (PR 19): lock discipline
        # over every threaded class + replay purity over the
        # replay-critical modules (docs/analysis.md "Concurrency &
        # replay-purity passes") — golden-pinned at zero so a new race
        # or impurity gates like a graph regression does
        conc_report = analysis.lint_package()
        _emit(
            "concurrency_lint_errors",
            float(len(conc_report.errors())),
            "concurrency/replay-purity ERROR findings (apex_tpu "
            "package; warnings=%d, files=%d; docs/analysis.md)" % (
                len(conc_report.warnings()),
                conc_report.sections.get("files_scanned", 0),
            ),
            None,
        )

    profile = apex_tpu.utils.trace(trace_dir) if trace_dir else None
    step_time, carry, loss = _time_chunks(
        timed_fn, (params, opt_state), chunk, trials, profile=profile
    )
    del carry

    tokens = seq_len * batch
    # Headline numerator: the BASELINE.md contract formula 6·N·T — the
    # same accounting the reference recipe's A100 numbers use, and that
    # recipe also gathers masked positions (max_predictions_per_seq), so
    # packed-head step times are the apples-to-apples comparison.
    flops = _train_flops(n_params, tokens)
    peak = sum(_chip_peak(d) for d in placed_on)
    mfu = flops / (step_time * peak)
    # Honesty sidecar: the packed head EXECUTES fewer decoder FLOPs than
    # 6·N·T credits (K·B rows instead of T through the tied V×H decoder).
    # mfu_exec charges only executed work — the utilization number, vs
    # the recipe-parity headline above.  Dense head: identical.
    mfu_exec = mfu
    if max_predictions_per_seq:
        dec = cfg.vocab_size * cfg.hidden_size
        kb = max_predictions_per_seq * batch
        flops_exec = flops - 6.0 * (tokens - kb) * dec
        mfu_exec = flops_exec / (step_time * peak)
    if emit:
        extra = ""
        if max_predictions_per_seq:
            extra = ", mfu_exec=%.4f, mpps=%d" % (
                mfu_exec, max_predictions_per_seq
            )
        # record the remat policy that actually ran so artifacts from
        # different APEX_TPU_BENCH_POLICY settings stay distinguishable
        extra += ", policy=%s" % cfg.remat_policy
        _emit(
            _METRIC_NAMES["bert_lamb"],
            round(mfu, 4),
            "MFU (step_time_ms=%.1f, batch=%d, params=%dM, loss=%.3f%s)"
            % (step_time * 1e3, batch, n_params // 1_000_000, loss, extra),
            round(mfu / 0.50, 4),
        )
    return mfu, step_time, loss, mfu_exec


# ---------------------------------------------------------------------------
# #1 / #2 ResNet-50 (single device / DDP + SyncBN over dp)
# ---------------------------------------------------------------------------


def _resnet_step_fns(use_syncbn, batch, tx):
    from apex_tpu.models.resnet import resnet50

    model = resnet50(use_syncbn=use_syncbn)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (batch, 224, 224, 3), jnp.bfloat16)
    y = jax.random.randint(key, (batch,), 0, 1000)
    variables = model.init(jax.random.PRNGKey(1), x, train=False)
    params, batch_stats = variables["params"], variables["batch_stats"]
    opt_state = tx.init(params)

    def loss_fn(p, bs):
        logits, updates = model.apply(
            {"params": p, "batch_stats": bs}, x, train=True,
            mutable=["batch_stats"],
        )
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        loss = -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))
        return loss, updates["batch_stats"]

    return loss_fn, params, batch_stats, opt_state, model


def bench_resnet50(trace_dir=None, batch=256, chunk=4, trials=3):
    """BASELINE #1: single-device synthetic-ImageNet train step."""
    import apex_tpu.utils
    from apex_tpu.optimizers import fused_sgd

    tx = fused_sgd(learning_rate=0.1, momentum=0.9)
    loss_fn, params, batch_stats, opt_state, _ = _resnet_step_fns(
        False, batch, tx
    )

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_chunk(params, batch_stats, opt_state):
        def body(carry, _):
            params, batch_stats, opt_state = carry
            (loss, batch_stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params, batch_stats)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = jax.tree_util.tree_map(jnp.add, params, updates)
            return (params, batch_stats, opt_state), loss

        carry, losses = jax.lax.scan(
            body, (params, batch_stats, opt_state), None, length=chunk
        )
        return carry, losses[-1]

    step_time, _, loss = _time_chunks(
        train_chunk, (params, batch_stats, opt_state), chunk, trials,
        profile=apex_tpu.utils.trace(trace_dir) if trace_dir else None,
    )
    _emit(
        _METRIC_NAMES["resnet50"],
        round(batch / step_time, 1),
        "img/s (step_time_ms=%.1f, batch=%d, loss=%.3f, single device; "
        "reference publishes no absolute number)"
        % (step_time * 1e3, batch, loss),
        None,
    )


def bench_ddp_syncbn(trace_dir=None, batch_per_replica=128, chunk=4, trials=3):
    """BASELINE #2: DDP ResNet-50 + SyncBatchNorm over every device."""
    from jax.sharding import Mesh, PartitionSpec as P

    import apex_tpu.utils
    from apex_tpu import parallel_state as ps
    from apex_tpu.optimizers import fused_sgd
    from apex_tpu.parallel.distributed import all_reduce_gradients

    devices = jax.devices()
    dp = len(devices)
    ps.destroy_model_parallel()
    ps.initialize_model_parallel(devices=devices)
    global_batch = batch_per_replica * dp

    tx = fused_sgd(learning_rate=0.1, momentum=0.9)
    loss_fn, params, batch_stats, opt_state, _ = _resnet_step_fns(
        True, batch_per_replica, tx
    )

    mesh = Mesh(devices, ("dp",))

    def one_step(params, batch_stats, opt_state):
        (loss, batch_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params, batch_stats)
        grads = all_reduce_gradients(grads)
        loss = jax.lax.pmean(loss, "dp")
        updates, opt_state = tx.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(jnp.add, params, updates)
        return params, batch_stats, opt_state, loss

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_chunk(params, batch_stats, opt_state):
        def body(carry, _):
            p, bs, os_ = carry
            p, bs, os_, loss = one_step(p, bs, os_)
            return (p, bs, os_), loss

        def sharded(p, bs, os_):
            carry, losses = jax.lax.scan(
                body, (p, bs, os_), None, length=chunk
            )
            return carry, losses[-1]

        return jax.shard_map(
            sharded, mesh=mesh, in_specs=(P(), P(), P()),
            out_specs=(P(), P()), check_vma=False,
        )(params, batch_stats, opt_state)

    step_time, _, loss = _time_chunks(
        train_chunk, (params, batch_stats, opt_state), chunk, trials,
        profile=apex_tpu.utils.trace(trace_dir) if trace_dir else None,
    )
    ps.destroy_model_parallel()
    _emit(
        _METRIC_NAMES["ddp_syncbn"],
        round(global_batch / step_time, 1),
        "img/s (step_time_ms=%.1f, dp=%d, global_batch=%d, loss=%.3f, "
        "SyncBN; reference publishes no absolute number)"
        % (step_time * 1e3, dp, global_batch, loss),
        None,
        degenerate=dp == 1,
    )


# ---------------------------------------------------------------------------
# #4 fused multihead attention vs unfused composition
# ---------------------------------------------------------------------------


def bench_mha(trace_dir=None, batch=8, seq=2048, heads=16, head_dim=64,
              chunk=8, trials=3):
    """BASELINE #4: fused attention core vs the unfused composition, fwd+bwd
    (≙ the reference's multihead_attn speedup-vs-torch.nn plots)."""
    import apex_tpu.utils
    from apex_tpu.ops.attention import flash_attention, mha_reference

    key = jax.random.PRNGKey(0)
    shape = (batch, heads, seq, head_dim)
    q, k, v = (
        jax.random.normal(kk, shape, jnp.bfloat16)
        for kk in jax.random.split(key, 3)
    )

    def timed(fn):
        @jax.jit
        def chunk_fn(q, k, v):
            def body(carry, _):
                qq, kk, vv = carry
                def loss(qq, kk, vv):
                    return jnp.sum(
                        fn(qq, kk, vv, causal=True).astype(jnp.float32) ** 2
                    )
                dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(qq, kk, vv)
                # feed grads back so scan iterations are not DCE'd
                return (dq, dk, dv), jnp.float32(0)

            carry, _ = jax.lax.scan(body, (q, k, v), None, length=chunk)
            return carry, carry[0][0, 0, 0]

        t, _, _ = _time_chunks(
            lambda *c: chunk_fn(*c), (q, k, v), chunk, trials,
            profile=apex_tpu.utils.trace(trace_dir) if trace_dir else None,
        )
        return t

    t_fused = timed(flash_attention)
    trace_dir = None  # one trace (the fused pass) is enough
    t_unfused = timed(mha_reference)
    speedup = t_unfused / t_fused
    _emit(
        _METRIC_NAMES["mha"],
        round(speedup, 3),
        "x vs unfused (fused_ms=%.2f, unfused_ms=%.2f, b=%d h=%d s=%d d=%d, "
        "fwd+bwd)" % (t_fused * 1e3, t_unfused * 1e3, *((batch, heads, seq,
                                                         head_dim))),
        round(speedup, 3),
    )


# ---------------------------------------------------------------------------
# #5 tensor-parallel GPT block
# ---------------------------------------------------------------------------


def bench_tp_gpt(trace_dir=None, batch=8, seq=1024, chunk=4, trials=3):
    """BASELINE #5: GPT block train step over a tp mesh of all devices."""
    from jax.sharding import Mesh, PartitionSpec as P

    import apex_tpu.utils
    from apex_tpu import parallel_state as ps
    from apex_tpu.models.gpt import GptBlock, GptConfig
    from apex_tpu.optimizers import fused_adam

    devices = jax.devices()
    tp = len(devices)
    ps.destroy_model_parallel()
    ps.initialize_model_parallel(
        tensor_model_parallel_size=tp, devices=devices
    )
    mesh = Mesh(devices, (ps.TENSOR_PARALLEL_AXIS,))

    cfg = GptConfig(
        hidden_size=1024, num_heads=16, intermediate_size=4096,
        sequence_parallel=tp > 1, dtype=jnp.bfloat16,
    )
    block = GptBlock(cfg)
    tx = fused_adam(learning_rate=1e-4)
    x = jax.random.normal(
        jax.random.PRNGKey(0), (seq, batch, cfg.hidden_size), jnp.bfloat16
    )

    def build(x):
        xl = x
        if tp > 1:
            rank = jax.lax.axis_index(ps.TENSOR_PARALLEL_AXIS)
            sp = seq // tp
            xl = jax.lax.dynamic_slice_in_dim(x, rank * sp, sp, 0)
        params = block.init(jax.random.PRNGKey(1), xl)
        return params, tx.init(params), xl

    def sharded_chunk(length, x):
        # params live only inside shard_map (per-rank tp shards have no
        # convenient global representation), so init runs inside the jit;
        # the two-length timing below subtracts it out of the step time.
        params, opt_state, xl = build(x)

        def body(carry, _):
            params, opt_state = carry

            def loss_fn(p):
                y = block.apply(p, xl)
                return jnp.sum(y.astype(jnp.float32) ** 2)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = jax.tree_util.tree_map(jnp.add, params, updates)
            return (params, opt_state), loss

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), None, length=length,
        )
        return losses[-1]

    def timed(length, profile=None):
        fn = jax.jit(
            jax.shard_map(
                functools.partial(sharded_chunk, length),
                mesh=mesh, in_specs=(P(),), out_specs=P(),
                check_vma=False,
            )
        )

        def wrapped(x):
            return (x,), fn(x)

        # total (init + length steps) time; per-step division happens in
        # the subtraction below, so pass chunk=1 here.  min (not median)
        # over trials: the subtraction needs the noise floor of each.
        total, _, _ = _time_chunks(
            wrapped, (x,), 1, trials, profile=profile, reduce="min"
        )
        return total

    t_long = timed(2 * chunk)
    t_short = timed(chunk)
    if trace_dir:
        # dedicated traced run — its time is NOT used, so profiler
        # overhead cannot bias the init-cancelling subtraction below
        timed(2 * chunk, profile=apex_tpu.utils.trace(trace_dir))
    ps.destroy_model_parallel()
    if t_long <= t_short:
        # timing noise swamped the subtraction: report the conservative
        # upper bound (init amortized over 2*chunk steps) and say so
        step_time = t_long / (2 * chunk)
        basis = "upper bound incl. per-call init: noisy subtraction"
    else:
        step_time = (t_long - t_short) / chunk
        basis = "init-cancelled two-length measurement"
    _emit(
        _METRIC_NAMES["tp_gpt"],
        round(step_time * 1e3, 2),
        "ms/step (tp=%d, seq=%d, batch=%d, h=%d, SP=%s, %s; reference "
        "publishes no absolute number)"
        % (tp, seq, batch, cfg.hidden_size, tp > 1, basis),
        None,
        degenerate=tp == 1,
    )


# ---------------------------------------------------------------------------
# ZeRO gradient sync: BERT-Large + DistributedFusedLAMB, wire f32 vs int8
# ---------------------------------------------------------------------------


def bench_zero(trace_dir=None, batch_per_replica=32, chunk=3, trials=3,
               cfg_kwargs=None):
    """BERT-Large + DistributedFusedLAMB (cross-replica weight-update
    sharding) over a dp mesh of all devices, A/B'd over the comm layer's
    wire format: f32 vs int8 grads with bf16 param gather (the
    recommended aggressive setting, docs/comm.md).  Value = f32/int8
    step-time speedup — the wall-clock effect of cutting DP sync bytes
    ~4x; both step times ride in the unit string.  dp=1 runs are marked
    degenerate (no wire to cut: the engine skips collectives entirely,
    so the honest expectation there is ~1.0x).  ``cfg_kwargs`` overrides
    the BERT-Large shape (CPU smoke drives use a tiny model).
    """
    from jax.sharding import Mesh, PartitionSpec as P

    import apex_tpu.utils
    from apex_tpu import parallel_state as ps
    from apex_tpu.models import (
        BertForPreTraining,
        bert_large_config,
        bert_pretrain_loss,
    )
    from apex_tpu.parallel import DistributedFusedLAMB

    devices = jax.devices()
    dp = len(devices)
    seq_len = 128
    global_batch = batch_per_replica * dp
    if cfg_kwargs is None:
        cfg_kwargs = dict(
            remat=True, remat_policy=_BENCH_POLICY, scan_layers=False,
            remat_attention=True, remat_prevent_cse=False,
        )
    cfg = bert_large_config(**cfg_kwargs)
    model = BertForPreTraining(cfg)

    key = jax.random.PRNGKey(0)
    ids = jax.random.randint(key, (seq_len, global_batch), 0, cfg.vocab_size)
    labels = jnp.where(ids % 7 == 0, ids, -1)
    batch_data = {
        "input_ids": ids,
        "token_type_ids": jnp.zeros_like(ids),
        "attention_mask": jnp.ones((global_batch, seq_len), jnp.int32),
        "mlm_labels": labels,
        "nsp_labels": jnp.zeros((global_batch,), jnp.int32),
    }
    # dense-label MLM head: every leaf's batch axis is explicit below, so
    # per-rank slicing inside shard_map stays a one-liner
    _BATCH_AXIS = {
        "input_ids": 1, "token_type_ids": 1, "attention_mask": 0,
        "mlm_labels": 1, "nsp_labels": 0,
    }
    params = model.init(jax.random.PRNGKey(1), ids[:, :batch_per_replica])
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))

    mesh = Mesh(devices, (ps.DATA_PARALLEL_AXIS,))
    ps.destroy_model_parallel()
    ps.initialize_model_parallel(devices=devices)

    def run(wire, param_wire, profile=None):
        # fresh param copy per A/B arm: the step donates its carry, so
        # sharing one tree would hand arm 2 deleted buffers
        arm_params = jax.tree_util.tree_map(jnp.copy, params)
        dist = DistributedFusedLAMB(
            lr=1e-3, weight_decay=0.01, wire=wire, param_wire=param_wire,
        )
        state = dist.init(arm_params, world=dp)
        state_spec = jax.tree_util.tree_map(
            lambda x: P("dp") if getattr(x, "ndim", 0) == 1 else P(),
            state,
        )

        def sharded_chunk(params, state, batch):
            rank = jax.lax.axis_index(ps.DATA_PARALLEL_AXIS)
            local = {
                k: jax.lax.dynamic_slice_in_dim(
                    v, rank * batch_per_replica, batch_per_replica,
                    _BATCH_AXIS[k],
                )
                for k, v in batch.items()
            }

            def body(carry, _):
                params, state = carry
                loss, grads = jax.value_and_grad(
                    lambda p: bert_pretrain_loss(
                        p, model, local, mlm_loss_chunks=16
                    )
                )(params)
                loss = jax.lax.pmean(loss, ps.DATA_PARALLEL_AXIS)
                params, state = dist.update_inside_shard_map(
                    grads, state, params
                )
                return (params, state), loss

            (params, state), losses = jax.lax.scan(
                body, (params, state), None, length=chunk
            )
            return params, state, losses[-1]

        fn = jax.jit(
            jax.shard_map(
                sharded_chunk, mesh=mesh,
                in_specs=(P(), state_spec, P()),
                out_specs=(P(), state_spec, P()),
                check_vma=False,
            ),
            donate_argnums=(0, 1),
        )

        def wrapped(p, s):
            p, s, loss = fn(p, s, batch_data)
            return (p, s), loss

        t, carry, loss = _time_chunks(
            wrapped, (arm_params, state), chunk, trials, profile=profile
        )
        del carry
        return t, loss

    t_f32, loss = run("f32", None)
    t_int8, _ = run(
        "int8", "bf16",
        profile=apex_tpu.utils.trace(trace_dir) if trace_dir else None,
    )
    ps.destroy_model_parallel()
    speedup = t_f32 / t_int8
    _emit(
        _METRIC_NAMES["zero"],
        round(speedup, 3),
        "x vs f32 wire (f32_ms=%.1f, int8_ms=%.1f, dp=%d, "
        "global_batch=%d, params=%dM, loss=%.3f, ZeRO LAMB, "
        "param_wire=bf16; reference publishes no absolute number)"
        % (t_f32 * 1e3, t_int8 * 1e3, dp, global_batch,
           n_params // 1_000_000, loss),
        None,
        degenerate=dp == 1,
    )


# ---------------------------------------------------------------------------
# long-context attention (beyond-reference capability demo)
# ---------------------------------------------------------------------------


def bench_long_attn(trace_dir=None, batch=1, heads=8, seq=16384,
                    head_dim=128, chunk=4, trials=3):
    """Causal flash attention fwd+bwd at long sequence — the regime the
    reference cannot reach (its fmha kernels cap at seq 512, its fused
    softmax at ~2k; an unfused composition would materialize a
    (S, S) = 17 GB f32 score tensor here).  Reports achieved TFLOP/s and
    fraction of chip peak; vs_baseline is null (no reference number
    exists at this length by construction)."""
    import apex_tpu.utils
    from apex_tpu.ops.attention import flash_attention

    key = jax.random.PRNGKey(0)
    shape = (batch, heads, seq, head_dim)
    q, k, v = (
        jax.random.normal(kk, shape, jnp.bfloat16)
        for kk in jax.random.split(key, 3)
    )

    @jax.jit
    def chunk_fn(q, k, v):
        def body(carry, _):
            qq, kk, vv = carry

            def loss(qq, kk, vv):
                o = flash_attention(qq, kk, vv, causal=True)
                return jnp.sum(o.astype(jnp.float32) ** 2)

            dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(qq, kk, vv)
            return (dq, dk, dv), jnp.float32(0)

        carry, _ = jax.lax.scan(body, (q, k, v), None, length=chunk)
        return carry, carry[0][0, 0, 0]

    t, _, _ = _time_chunks(
        lambda *c: chunk_fn(*c), (q, k, v), chunk, trials,
        profile=apex_tpu.utils.trace(trace_dir) if trace_dir else None,
    )
    # causal fwd ≈ 2·B·H·S²·D MACs = 4·B·H·S²·D/2 FLOPs; bwd ≈ 2.5× fwd
    flops = 3.5 * 4 * batch * heads * seq * seq * head_dim / 2
    peak = _chip_peak(jax.devices()[0])
    tf = flops / t / 1e12
    _emit(
        _METRIC_NAMES["long_attn"],
        round(tf, 1),
        "TFLOP/s (%.0f%% of peak, step_ms=%.1f, b=%d h=%d s=%d d=%d, "
        "causal fwd+bwd, O(S) memory; reference caps at seq 512)"
        % (100 * flops / t / peak, t * 1e3, batch, heads, seq, head_dim),
        None,
    )


# ---------------------------------------------------------------------------
# Serving smoke config (seconds on CPU — the verify_tier1.sh PERF pass;
# docs/serving.md)
# ---------------------------------------------------------------------------


def bench_serve(trace_dir=None, prompt_len=48, decode_steps=24, trials=3):
    """Paged-inference smoke rows: prefill tokens/s, continuous-batch
    decode tokens/s, and TTFT through the real scheduler path — a tiny
    GPT so the rows land in seconds on CPU.  Like ``bench_smoke``
    these are SCHEMA/PRESENCE rows, not performance claims: they pin
    the serving metric names into the golden/gate stream
    (``tools/bench_golden_cpu.jsonl``) so serving perf can never go
    flat silently; real serving load curves come from
    ``tools/serve_bench.py``."""
    import numpy as np

    from apex_tpu.models.gpt import GptConfig, GptModel
    from apex_tpu.serve import (
        ContinuousBatchingScheduler,
        InferenceEngine,
        Request,
        ServeConfig,
    )

    cfg = GptConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        intermediate_size=128, max_seq_len=256, dtype=jnp.float32,
    )
    serve_cfg = ServeConfig(
        page_size=16, num_pages=64, max_batch=4, max_pages_per_seq=8,
        verify=False,
    )
    model = GptModel(cfg)
    ids = jax.random.randint(
        jax.random.PRNGKey(0), (prompt_len, 1), 0, cfg.vocab_size
    )
    params = model.init(jax.random.PRNGKey(1), ids)
    engine = InferenceEngine(cfg, params, serve_cfg)
    rs = np.random.RandomState(0)

    def prompt(n):
        return list(rs.randint(0, cfg.vocab_size, size=n))

    # -- prefill tokens/s (direct engine path, batch-of-1 buckets) ------
    pages = engine.pool.alloc(engine.pool.pages_for(prompt_len))
    engine.prefill(prompt(prompt_len), pages)  # warmup/compile
    times = []
    for _ in range(trials):
        t0 = time.perf_counter()
        engine.prefill(prompt(prompt_len), pages)
        times.append(time.perf_counter() - t0)
    times.sort()
    t_prefill = times[len(times) // 2]
    _emit(
        "serve_prefill_tokens_per_s",
        round(prompt_len / t_prefill, 1),
        "tokens/s (prompt=%d, bucket=%d, page=%d, h=%d L=%d; CI "
        "serving smoke on CPU, not a perf claim)"
        % (prompt_len, engine.bucket_for(prompt_len),
           serve_cfg.page_size, cfg.hidden_size, cfg.num_layers),
        None,
    )
    engine.pool.free(pages)

    # -- decode tokens/s at a full continuous batch ---------------------
    b = serve_cfg.max_batch
    reqs = []
    tables = np.zeros((b, serve_cfg.max_pages_per_seq), np.int32)
    for i in range(b):
        p = engine.pool.alloc(engine.pool.pages_for(prompt_len))
        _, tok = engine.prefill(prompt(prompt_len), p)
        reqs.append({"pages": p, "tok": tok, "ctx": prompt_len})
    lengths = np.zeros((b,), np.int32)
    tokens = np.zeros((b,), np.int32)

    def decode_once():
        for i, r in enumerate(reqs):
            if r["ctx"] // serve_cfg.page_size >= len(r["pages"]):
                got = engine.pool.alloc(1)
                if got is None:
                    raise RuntimeError(
                        "bench serve: page pool exhausted — raise "
                        "num_pages or lower decode_steps/prompt_len"
                    )
                r["pages"] += got
            tables[i, : len(r["pages"])] = r["pages"]
            tokens[i] = r["tok"]
            lengths[i] = r["ctx"] + 1
        _, nxt = engine.decode(tokens, lengths, tables)
        for i, r in enumerate(reqs):
            r["ctx"] += 1
            r["tok"] = int(nxt[i])

    decode_once()  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(decode_steps):
        decode_once()
    t_decode = (time.perf_counter() - t0) / decode_steps
    _emit(
        "serve_decode_tokens_per_s",
        round(b / t_decode, 1),
        "tokens/s (batch=%d, ctx~%d, page=%d, paged KV; CI serving "
        "smoke on CPU, not a perf claim)"
        % (b, prompt_len + decode_steps, serve_cfg.page_size),
        None,
    )
    for r in reqs:
        engine.pool.free(r["pages"])

    # -- TTFT through the scheduler (queue -> admit -> prefill) ---------
    # spans ON: this row doubles as the span-recording overhead gate —
    # the golden tolerance on serve_ttft_ms binds the scheduler path
    # WITH per-request span chains being recorded
    from apex_tpu.observability.spans import SpanRecorder

    ttfts = []
    for _ in range(trials):
        # each scheduler takes the engine over with its own recorder
        sched = ContinuousBatchingScheduler(
            engine, spans=SpanRecorder(capacity=1024)
        )
        sched.submit(Request(prompt=prompt(prompt_len), max_new_tokens=2))
        sched.run()
        ttfts.append(sched.completed[-1].ttft_ms)
    ttfts.sort()
    engine.spans = None
    _emit(
        "serve_ttft_ms",
        round(ttfts[len(ttfts) // 2], 3),
        "ms (prompt=%d via ContinuousBatchingScheduler, queue->first "
        "token, span recording ON; CI serving smoke on CPU, not a perf "
        "claim)" % prompt_len,
        None,
    )

    # -- live ops plane rows (docs/observability.md "Live ops plane") ---
    # ops_scrape_ms: a REAL HTTP GET against the OpenMetrics endpoint
    # serving the last scheduler's TTFT histogram + the board — the
    # exporter's cost rides the bench_diff golden stream so scrape
    # overhead can never regress silently
    import urllib.request

    from apex_tpu.observability import ometrics, slo as slo_lib

    srv = ometrics.OpsServer(
        histograms=[sched.ttft_hist], port=0
    ).start()
    scrape_ms = []
    body = b""
    for _ in range(3):
        t0 = time.perf_counter()
        with urllib.request.urlopen(srv.url, timeout=10) as resp:
            body = resp.read()
        scrape_ms.append(1e3 * (time.perf_counter() - t0))
    srv.stop()
    scrape_ms.sort()
    _emit(
        "ops_scrape_ms",
        round(scrape_ms[len(scrape_ms) // 2], 3),
        "ms (HTTP GET /metrics, median of 3, %d bytes exposition; CI "
        "ops smoke on CPU, not a perf claim)" % len(body),
        None,
    )
    # slo_alerts_fired: the deterministic burn-rate drill (a 5x burn
    # against a 90% objective judged by one (60s, 240s, 2x) window
    # fires exactly once) — pins the multi-window alert math into the
    # golden stream
    _emit(
        "slo_alerts_fired",
        float(slo_lib.burn_rate_drill()),
        "alerts (canonical burn-rate drill: 50% errors vs a 90% "
        "objective, one 60s/240s window at factor 2 — must fire "
        "exactly once)",
        None,
    )

    # -- prefix-cache rows (docs/serving.md "Prefix caching") ----------
    # serve_prefix_hit_ttft_ms: TTFT of a fully-cached prompt through
    # the real scheduler path — the hit borrows every committed page
    # and chunked prefill re-runs only the final grain-aligned chunk.
    # serve_prefill_flops_saved_pct: analytic prefill FLOPs the hit
    # skipped vs a cold run of the same prompt (deterministic — a
    # function of the grain-floored resume point, not the clock).
    # Together they pin the prefix-cache fast path into the golden
    # stream (_ms lower-better / _pct higher-better per bench_diff's
    # suffix rules); the workload-level proof lives in verify_tier1.sh's
    # prefix gate over tools/serve_bench.py.
    psched = ContinuousBatchingScheduler(
        engine,
        spans=SpanRecorder(capacity=1024),
        prefix_cache=True,
        prefill_chunk_tokens=serve_cfg.page_size,
    )
    shared = prompt(prompt_len)
    # cold run: compiles the chunk/fork programs and commits the prefix
    psched.submit(Request(prompt=list(shared), max_new_tokens=2))
    psched.run()
    hit_ttfts = []
    for _ in range(trials):
        psched.submit(Request(prompt=list(shared), max_new_tokens=2))
        psched.run()
        hit_ttfts.append(psched.completed[-1].ttft_ms)
    hit_req = psched.completed[-1]
    assert hit_req.cache_hit_tokens > 0, "prefix cache never hit"
    hit_ttfts.sort()
    engine.spans = None
    _emit(
        "serve_prefix_hit_ttft_ms",
        round(hit_ttfts[len(hit_ttfts) // 2], 3),
        "ms (fully-cached prompt=%d, page=%d, chunk=%d; queue->first "
        "token on a warm prefix cache; CI serving smoke on CPU, not a "
        "perf claim)"
        % (prompt_len, serve_cfg.page_size, serve_cfg.page_size),
        None,
    )
    grain = serve_cfg.page_size
    start = (min(hit_req.cache_hit_tokens, prompt_len - 1) // grain) * grain
    h, ff, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers

    def _pf_flops(n, skip=0):
        linear = (4 * h * h + 2 * h * ff) * (n - skip)
        attn = 2 * h * (n * (n + 1) - skip * (skip + 1)) // 2
        return L * (linear + attn)

    _emit(
        "serve_prefill_flops_saved_pct",
        round(
            100.0 * (1.0 - _pf_flops(prompt_len, start)
                     / _pf_flops(prompt_len)), 3),
        "%% prefill FLOPs skipped by a full prefix hit (prompt=%d, "
        "resume at token %d of %d; analytic model, deterministic)"
        % (prompt_len, start, prompt_len),
        None,
    )
    # hand every cached page back and prove the pool drained clean —
    # the smoke row must not leak pages into the chaos section below
    psched.prefix.flush()
    psched.leak_check()
    assert engine.pool.in_use == 0, engine.pool.in_use

    # -- speculative-decode rows (docs/serving.md "Speculative decoding")
    # serve_spec_accept_rate / serve_spec_tokens_per_step: a friendly
    # (self-draft) k=4 speculative run through the real scheduler path.
    # Greedy self-draft acceptance is exact by construction, so the
    # accept-rate row pins 1.0 and the tokens/step row pins the
    # k+1-wide emission — deterministic SCHEMA rows like the rest of
    # this config (the workload-level proof, including the chaos storm
    # and the plain-decode replay, lives in verify_tier1.sh's spec gate
    # over tools/serve_bench.py).
    from apex_tpu.observability import MetricRegistry
    from apex_tpu.serve import SpecConfig

    sreg = MetricRegistry(fetch_every=1)
    sengine = InferenceEngine(
        cfg, params, serve_cfg, registry=sreg,
        spec=SpecConfig(draft_params=None, k=4),
    ).build()
    ssched = ContinuousBatchingScheduler(sengine, registry=sreg)
    for _ in range(2):
        ssched.submit(Request(prompt=prompt(16), max_new_tokens=12))
    ssched.run()
    ssched.leak_check()
    assert sengine.pool.in_use == 0, sengine.pool.in_use
    sreg.fetch()
    svals = sreg.values()
    assert svals.get("serve/spec_rounds", 0.0) > 0, svals
    _emit(
        "serve_spec_accept_rate",
        round(svals["serve/spec_accept_rate"], 3),
        "draft tokens accepted / drafted (self-draft k=4, greedy: "
        "exact by construction, MUST be 1.0; CI serving smoke on CPU)",
        None,
    )
    _emit(
        "serve_spec_tokens_per_step",
        round(svals["serve/spec_tokens_per_step"], 3),
        "tokens emitted per decode step (self-draft k=4 over %d "
        "requests; plain decode is 1.0 by definition; CI serving "
        "smoke on CPU, not a perf claim)" % len(ssched.completed),
        None,
    )

    # -- serving resilience rows (docs/serving.md "Failure semantics") --
    # reuses tools/serve_chaos_drill.py (the SERVE-CHAOS gate's exact
    # machinery: fault-free Poisson reference + an APEX_TPU_CHAOS storm
    # at all four serve sites + overload-ladder probe + drain) and
    # emits the two headline rows: request goodput under the storm and
    # the p99 TTFT inflation vs the fault-free reference.  The gate's
    # evidence artifact is reused via APEX_TPU_SERVE_CHAOS_ARTIFACT
    # (verify_tier1.sh runs SERVE-CHAOS before PERF and hands it over)
    # so CI pays for ONE storm, not two.
    import importlib.util as _ilu

    root = os.path.dirname(os.path.abspath(__file__))
    spec = _ilu.spec_from_file_location(
        "serve_chaos_drill",
        os.path.join(root, "tools", "serve_chaos_drill.py"),
    )
    scd = _ilu.module_from_spec(spec)
    spec.loader.exec_module(scd)
    defaults = scd.build_parser().parse_args([])
    art = None
    reuse = os.environ.get("APEX_TPU_SERVE_CHAOS_ARTIFACT")
    if reuse and os.path.exists(reuse):
        try:
            with open(reuse) as f:
                cand = json.load(f)
            # accept only an artifact of the SAME storm: a stale file
            # from a different spec/geometry must not publish rows
            # describing a drill the current code never ran.  Every
            # key the artifact's config section records must equal the
            # drill's defaults, plus the chaos spec itself.
            cfg_sec = cand.get("config", {})
            if (cand.get("chaos_spec") == defaults.chaos
                    and cfg_sec
                    and all(getattr(defaults, k, None) == v
                            for k, v in cfg_sec.items())):
                art = cand
        except (OSError, ValueError):
            art = None
    if art is None:
        art = scd.run_drill(defaults)
    storm_req = art["storm"]
    chaos_desc = (
        "storm %s; rebuilds=%d retries=%d; sheds %s"
        % (art["chaos_spec"], art["engine"]["rebuilds"],
           art["registry"].get("serve/retries", 0),
           dict(sorted(storm_req["shed_reasons"].items())))
    )
    _emit(
        "serve_chaos_goodput_pct",
        round(100.0 * storm_req["completed"] / storm_req["offered"], 3)
        if storm_req["offered"] else 0.0,
        "%% requests completed under the serve chaos storm (%s)"
        % chaos_desc,
        None,
    )
    _emit(
        "serve_chaos_p99_inflation",
        round(art["p99_ttft_inflation"], 3),
        "x storm p99 TTFT over the fault-free reference (bound 2.0 — "
        "graceful degradation, not collapse; %s)" % chaos_desc,
        None,
    )


# ---------------------------------------------------------------------------
# train3d: the composable trainer at dp=2 / tp=2 / dp=2 x tp=2
# ---------------------------------------------------------------------------


def bench_train3d(trace_dir=None, steps=8, trials=3):
    """The ``apex_tpu.train`` trainer's honest multi-device rows — the
    replacement for the degenerate ddp_syncbn (dp=1) / tp_gpt (tp=1)
    proxies (ISSUE 12).  Three arms — dp=2, tp=2, dp=2 x tp=2 — each a
    REAL mesh when enough devices are visible (CI mocks 8 CPU devices
    via ``--xla_force_host_platform_device_count=8``; an on-chip window
    uses real chips).  Every arm's trainer build SELF-VERIFIES
    (``TrainConfig(verify="error")``): the compiled step's sharding,
    collective schedule, and memory must equal the config-derived plan
    or the bench dies loudly — so a row here is a verified shape, not
    just a number.  With too few devices the arm falls back to a
    single-device build marked ``degenerate`` — and ``bench_diff
    --check-schema`` REFUSES degenerate train3d rows, so the fallback
    can never pass a gate.

    With ``--lint`` a ``train3d_lint_errors`` line carries the total
    ERROR findings across the three builds (0 by construction: a build
    with errors raises).
    """
    from apex_tpu.train import build_demo

    arms = (("dp2", 2, 1), ("tp2", 1, 2), ("dp2tp2", 2, 2))
    navail = len(jax.devices())
    lint_errors = 0
    modes = []
    for name, dp, tp in arms:
        degenerate = navail < dp * tp
        bdp, btp = (1, 1) if degenerate else (dp, tp)
        step = build_demo(bdp, btp, verify="error")
        if step.report is not None:
            lint_errors += len(step.report.errors())
        state, batch = step.state, step.example_batch
        st, aux = step(state, batch)  # warmup/compile
        float(aux["loss"])
        times = []
        loss = 0.0
        for _ in range(trials):
            t0 = time.perf_counter()
            for _ in range(steps):
                st, aux = step(st, batch)
            loss = float(aux["loss"])  # device->host: the sync point
            times.append((time.perf_counter() - t0) / steps)
        times.sort()
        step_ms = times[len(times) // 2] * 1e3
        modes.append(f"{name}:{step.mode}")
        _emit(
            f"train3d_{name}_step_ms",
            round(step_ms, 3),
            "ms/step (dp=%d, tp=%d, rows=%d, dim=%d, mode=%s, wire=%s, "
            "loss=%.4f, %d devices, build self-verified; "
            "apex_tpu.train demo config)"
            % (bdp, btp, step.tokens_per_step(),
               step.example_batch[0].shape[1], step.mode,
               step.config.wire, loss, navail),
            None,
            degenerate=degenerate,
        )
    if _BENCH_LINT:
        _emit(
            "train3d_lint_errors",
            float(lint_errors),
            "ERROR findings across the three self-verified trainer "
            "builds (%s; a failing build raises, so nonzero here means "
            "a verify='warn' escape; docs/training.md)"
            % ", ".join(modes),
            None,
        )
        # host-side concurrency + replay-purity lint (PR 19), riding
        # the same --lint invocation so the golden stream pins the
        # package race/impurity ERROR count at zero
        from apex_tpu import analysis

        conc_report = analysis.lint_package()
        _emit(
            "concurrency_lint_errors",
            float(len(conc_report.errors())),
            "concurrency/replay-purity ERROR findings (apex_tpu "
            "package; warnings=%d, files=%d; docs/analysis.md)" % (
                len(conc_report.warnings()),
                conc_report.sections.get("files_scanned", 0),
            ),
            None,
        )


# ---------------------------------------------------------------------------
# CI smoke config (seconds on CPU — the verify_tier1.sh PERF pass)
# ---------------------------------------------------------------------------


def bench_smoke(trace_dir=None, dim=128, batch=64, chunk=4, trials=2):
    """Tiny MLP train step, single-device AND under a dp shard_map over
    every visible device — NOT a performance claim, a schema driver:
    it exercises the real ``_time_chunks``/``_emit`` path (including
    the degenerate-marking contract on the dp row) in seconds on CPU,
    so ``tools/bench_diff.py --check-schema`` can gate contract drift
    in CI without a TPU (``tools/bench_golden_cpu.jsonl`` is the
    committed golden line)."""
    from jax.sharding import Mesh, PartitionSpec as P

    key = jax.random.PRNGKey(0)
    w1 = jax.random.normal(key, (dim, dim), jnp.float32) * 0.1
    w2 = jax.random.normal(key, (dim, dim), jnp.float32) * 0.1
    x = jax.random.normal(key, (batch, dim), jnp.float32)
    y = jnp.ones((batch, dim), jnp.float32)

    def loss_fn(params, x, y):
        h = jnp.tanh(x @ params["w1"])
        return jnp.mean((h @ params["w2"] - y) ** 2)

    def body(carry, _):
        params = carry
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
        params = jax.tree_util.tree_map(
            lambda p, g: p - 1e-2 * g, params, grads
        )
        return params, loss

    @functools.partial(jax.jit, donate_argnums=(0,))
    def train_chunk(params):
        params, losses = jax.lax.scan(body, params, None, length=chunk)
        return (params,), losses[-1]

    # each arm gets its own copy: the chunks donate their carry, and
    # the dp arm below needs live source buffers
    params = {"w1": jnp.copy(w1), "w2": jnp.copy(w2)}
    t, _, loss = _time_chunks(
        lambda p: train_chunk(p), (params,), chunk, trials
    )
    _emit(
        "smoke_mlp_step_ms",
        round(t * 1e3, 3),
        "ms/step (dim=%d, batch=%d, loss=%.4f, single device; CI "
        "schema smoke, not a perf claim)" % (dim, batch, loss),
        None,
    )

    devices = jax.devices()
    dp = len(devices)
    mesh = Mesh(devices, ("dp",))

    def dp_body(carry, _):
        params = carry
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.pmean(g, "dp"), grads
        )
        params = jax.tree_util.tree_map(
            lambda p, g: p - 1e-2 * g, params, grads
        )
        return params, loss

    @functools.partial(jax.jit, donate_argnums=(0,))
    def dp_chunk(params):
        def sharded(params):
            params, losses = jax.lax.scan(
                dp_body, params, None, length=chunk
            )
            return params, losses[-1]

        params, loss = jax.shard_map(
            sharded, mesh=mesh, in_specs=(P(),), out_specs=(P(), P()),
            check_vma=False,
        )(params)
        return (params,), loss

    params = {"w1": jnp.copy(w1), "w2": jnp.copy(w2)}
    t_dp, _, loss = _time_chunks(
        lambda p: dp_chunk(p), (params,), chunk, trials
    )
    _emit(
        "smoke_dp_mlp_step_ms",
        round(t_dp * 1e3, 3),
        "ms/step (dp=%d, dim=%d, batch=%d, loss=%.4f, psum grad sync; "
        "CI schema smoke, not a perf claim)" % (dp, dim, batch, loss),
        None,
        degenerate=dp == 1,
    )


def bench_goodput(trace_dir=None, steps=60, preempt_every=12):
    """The preemptible-fleet I/O plane (docs/goodput.md), measured:
    reuses ``tools/goodput_drill.py``'s storm (the GOODPUT gate's
    exact machinery — uninterrupted reference + APEX_TPU_CHAOS
    preemption storm over the resilient example's real programs, fed
    by the resumable stream, saved by the async engine) and emits the
    headline rows: storm goodput %, the step path's zero-stall
    percentage, checkpoint enqueue/finalize stall ms, input-stall
    fraction, and the resumed-loss drift (which must be 0.0 — a
    nonzero value here means determinism broke, not that a knob needs
    tuning).  CI-grade numbers on CPU; not TPU perf claims."""
    import importlib.util
    import tempfile

    # APEX_TPU_GOODPUT_ARTIFACT: reuse an evidence artifact a previous
    # drill wrote (verify_tier1.sh runs the GOODPUT gate first and
    # hands its --json here) instead of paying a second full
    # reference+storm+resume drill for the same numbers.  Ignored
    # unless the artifact matches the requested storm geometry.
    art = None
    reuse = os.environ.get("APEX_TPU_GOODPUT_ARTIFACT")
    if reuse and os.path.exists(reuse):
        try:
            with open(reuse) as f:
                cand = json.load(f)
            if (cand.get("steps") == steps
                    and cand.get("preempt_every") == preempt_every):
                art = cand
        except (OSError, ValueError):
            art = None
    if art is None:
        root = os.path.dirname(os.path.abspath(__file__))
        spec = importlib.util.spec_from_file_location(
            "goodput_drill",
            os.path.join(root, "tools", "goodput_drill.py"),
        )
        gd = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(gd)
        workdir = tempfile.mkdtemp(prefix="apex_tpu_bench_goodput_")
        try:
            art = gd.run_drill(
                steps=steps, preempt_every=preempt_every,
                workdir=workdir,
            )
        finally:
            # CI runs this config every PERF pass: don't leave a
            # corpus + three checkpoint trees in /tmp per invocation
            shutil.rmtree(workdir, ignore_errors=True)

    def med(xs):
        # 0.0 on empty, never NaN: on fast storage every write can
        # settle before a drain point, leaving no finalize events —
        # and a NaN row would sail through every bench_diff
        # comparison (all NaN compares are False) instead of gating
        return sorted(xs)[len(xs) // 2] if xs else 0.0

    a = art["accountant"]
    storm = (
        "preempt every %d of %d steps + 1 healed save fault; accepted=%d "
        "skipped=%d discarded=%d resumes=%d; async ckpt engine + "
        "resumable stream; docs/goodput.md"
        % (preempt_every, steps, a["accepted"], a["skipped"],
           a["discarded"], a["resumes"])
    )
    _emit(
        "goodput_storm_pct", round(art["goodput"] * 100, 3),
        "%% productive/executed steps under the chaos storm (%s)" % storm,
        None,
    )
    _emit(
        "goodput_zero_stall_pct",
        round((1.0 - art["ckpt"]["stall_frac"]) * 100, 3),
        "%% of run wall time NOT stalled on checkpointing (snapshot+"
        "enqueue over wall on the full-length reference run, "
        "background writes excluded — the <1%% overhead bound "
        "inverted; %d saves)" % int(art["ckpt"]["saves"]),
        None,
    )
    _emit(
        "goodput_ckpt_enqueue_ms",
        round(med(art["ckpt"]["snapshot_ms"]), 3),
        "ms median host-snapshot+enqueue per save — the ONLY "
        "checkpoint cost on the step path (write runs behind)",
        None,
    )
    _emit(
        "goodput_ckpt_finalize_ms",
        round(med(art["ckpt"]["finalize_ms"]), 3),
        "ms median finalize barrier (rollback anchor / preemption / "
        "shutdown drains — off the step path by design)",
        None,
    )
    _emit(
        "goodput_input_stall_frac",
        round(art["input_stall_fraction"], 5),
        "fraction of wall time the consumer blocked on the prefetch "
        "queue (DevicePrefetcher depth=2 over the token loader)",
        None,
    )
    _emit(
        "goodput_resume_loss_drift",
        art["loss_trajectory"]["max_abs_drift"],
        "max |stormed - uninterrupted| per-step loss over %d steps "
        "(MUST be 0.0: resume is bit-exact by contract)"
        % art["loss_trajectory"]["ref_steps"],
        None,
    )


def bench_fleet(trace_dir=None):
    """The fleet control plane (docs/serving.md "Fleet operations"),
    measured: reuses ``tools/fleet_drill.py``'s seeded storm (the FLEET
    gate's exact machinery — crash + preemption + arrival spike +
    mid-load rolling deploy over an autoscaled multi-replica fleet on a
    virtual clock, vs a fault-free fixed-size reference) and emits the
    three headline rows: request goodput under the combined storm, the
    number of accepted requests LOST by the rolling deploy (0 by
    contract — a nonzero value means the zero-downtime guarantee broke,
    not that a knob needs tuning), and the storm's p99 TTFT inflation
    over the fault-free reference (bound 2.0 in the drill itself).
    Plus the canary-gate rows from ``tools/canary_drill.py``: the
    detection latency of a planted bad deploy
    (``fleet_canary_detect_ticks``) and the clean-deploy false-verdict
    count (``fleet_canary_false_positive``, pinned 0.0).
    CI-grade numbers on CPU virtual time; not TPU perf claims.

    The FLEET gate's evidence artifact is reused via
    APEX_TPU_FLEET_ARTIFACT (verify_tier1.sh runs FLEET before PERF and
    hands its --json here) so CI pays for ONE storm, not two — accepted
    only when the artifact's recorded config and chaos spec equal the
    drill's defaults, exactly like the serve-chaos reuse above."""
    import importlib.util as _ilu

    root = os.path.dirname(os.path.abspath(__file__))
    spec = _ilu.spec_from_file_location(
        "fleet_drill", os.path.join(root, "tools", "fleet_drill.py"),
    )
    fd = _ilu.module_from_spec(spec)
    spec.loader.exec_module(fd)
    defaults = fd.build_parser().parse_args([])
    art = None
    reuse = os.environ.get("APEX_TPU_FLEET_ARTIFACT")
    if reuse and os.path.exists(reuse):
        try:
            with open(reuse) as f:
                cand = json.load(f)
            cfg_sec = cand.get("config", {})
            if (cand.get("chaos_spec") == defaults.chaos
                    and cfg_sec
                    and all(getattr(defaults, k, None) == v
                            for k, v in cfg_sec.items())):
                art = cand
        except (OSError, ValueError):
            art = None
    if art is None:
        art = fd.run_drill(defaults)
    storm = art["storm"]
    fr = art["fleet_registry"]
    lost = sum(d["lost_requests"] for d in art["deploys"])
    desc = (
        "storm %s; crashes=%d preempts=%d router_faults=%d rerouted=%d "
        "scale_out=%d scale_in=%d deploys=%d replicas=%d"
        % (art["chaos_spec"],
           fr.get("fleet/replica_crashes", 0),
           fr.get("fleet/preempts", 0),
           fr.get("fleet/router_faults", 0),
           fr.get("fleet/rerouted", 0),
           fr.get("fleet/scale_out", 0),
           fr.get("fleet/scale_in", 0),
           fr.get("fleet/deploys", 0),
           len(art["replicas"]))
    )
    _emit(
        "fleet_chaos_goodput_pct",
        round(100.0 * storm["completed"] / storm["offered"], 3)
        if storm["offered"] else 0.0,
        "%% requests completed under the fleet storm (%s)" % desc,
        None,
    )
    _emit(
        "fleet_deploy_lost_requests",
        float(lost),
        "accepted requests lost across %d rolling deploy(s) under the "
        "storm (MUST be 0: drain+handoff re-routes, never sheds; %s)"
        % (len(art["deploys"]), desc),
        None,
    )
    inflation = art["p99_ttft_inflation"]
    _emit(
        "fleet_p99_inflation",
        round(inflation, 3) if inflation == inflation else 0.0,
        "x storm p99 TTFT over the fault-free fixed-size reference "
        "(drill bound 2.0x; <1.0 means the autoscaled storm fleet "
        "beat the reference; %s)" % desc,
        None,
    )

    # -- canary-gate rows (tools/canary_drill.py) --------------------------
    # same reuse contract as the storm above: the CANARY gate runs the
    # drill before PERF and hands its --json via APEX_TPU_CANARY_ARTIFACT,
    # accepted only when the artifact's recorded config equals the
    # drill's defaults; otherwise the drill runs here.
    cspec = _ilu.spec_from_file_location(
        "canary_drill", os.path.join(root, "tools", "canary_drill.py"),
    )
    cd = _ilu.module_from_spec(cspec)
    cspec.loader.exec_module(cd)
    cdefaults = cd.build_parser().parse_args([])
    cart = None
    creuse = os.environ.get("APEX_TPU_CANARY_ARTIFACT")
    if creuse and os.path.exists(creuse):
        try:
            with open(creuse) as f:
                cand = json.load(f)
            cfg_sec = cand.get("config", {})
            if cfg_sec and all(
                getattr(cdefaults, k, None) == v
                for k, v in cfg_sec.items()
            ):
                cart = cand
        except (OSError, ValueError):
            cart = None
    if cart is None:
        cart = cd.run_drill(cdefaults)
    cdesc = (
        "planted NaN-poisoned weights + %dx-throttled decode behind a "
        "frac=%.2f canary hold, %d replicas, soak=%d window=%d ticks"
        % (cdefaults.slow_factor, cdefaults.canary_frac,
           cdefaults.replicas, cdefaults.soak_ticks,
           cdefaults.max_window_ticks)
    )
    detect = cart.get("detect_ticks")
    _emit(
        "fleet_canary_detect_ticks",
        float(detect) if detect is not None else float("nan"),
        "virtual ticks from canary window open to the FAIL verdict + "
        "auto-rollback on the planted regression (%s; lower is faster "
        "detection, bounded by the drill's soak floor)" % cdesc,
        None,
    )
    _emit(
        "fleet_canary_false_positive",
        float(cart.get("false_positives", -1)),
        "canary FAIL verdicts across %d clean deploys of re-seeded "
        "same-architecture weights (MUST stay 0.0: the one-sided "
        "tests + min-sample honesty floor admit no verdict from the "
        "hold's own load skew)" % len(cart.get("clean_runs", [])),
        None,
    )


_CONFIGS = {
    "resnet50": bench_resnet50,
    "ddp_syncbn": bench_ddp_syncbn,
    "bert_lamb": bench_bert_lamb,
    "mha": bench_mha,
    "tp_gpt": bench_tp_gpt,
    "train3d": bench_train3d,
    "zero": bench_zero,
    "long_attn": bench_long_attn,
    "smoke": bench_smoke,
    "serve": bench_serve,
    "goodput": bench_goodput,
    "fleet": bench_fleet,
}

#: configs `--config all` skips: smoke/serve/goodput/fleet are CI
#: schema/acceptance drivers, and ddp_syncbn/tp_gpt are the
#: degenerate-prone proxies train3d REPLACES in the batch (still
#: invocable by name for historical comparisons)
_ALL_EXCLUDED = (
    "smoke", "serve", "goodput", "fleet", "ddp_syncbn", "tp_gpt"
)


def main(config="bert_lamb", trace_dir=None):
    # Fail a typo'd APEX_TPU_BENCH_POLICY BEFORE any backend touch:
    # under --config all the bert config would otherwise raise only
    # after the earlier benches had run.  The guard and
    # the consumer share ONE module-level read (_BENCH_POLICY) and the
    # validation delegates to the models' own resolution, so a policy
    # added there is automatically accepted here.
    from apex_tpu.transformer.pipeline_parallel.schedules import (
        resolve_remat_policy,
    )

    try:
        resolve_remat_policy(_BENCH_POLICY)
    except ValueError as e:
        raise SystemExit(f"APEX_TPU_BENCH_POLICY: {e}")
    from apex_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if config == "all":
        for name, fn in _CONFIGS.items():
            if name in _ALL_EXCLUDED:
                continue
            # one trace (the headline config) per invocation
            fn(trace_dir if name == "bert_lamb" else None)
        return
    _CONFIGS[config](trace_dir)


def _run_gate(baseline_path=None):
    """bench.py --gate: judge THIS invocation's emitted lines against
    the last committed round with tools/bench_diff.py (regression gate
    on every measured metric + the flatline gate on the flash-attention
    line when it was measured).  Returns the number of failures; emits
    a ``bench_gate_failures`` metric line so the gate verdict rides the
    same artifact stream it judges."""
    import importlib.util

    root = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location(
        "bench_diff", os.path.join(root, "tools", "bench_diff.py")
    )
    bd = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bd)

    baseline_path = baseline_path or bd.default_baseline(root)
    if baseline_path is None:
        print("bench gate: no baseline round found — nothing to gate",
              file=sys.stderr)
        return 0
    current = bd.collapse(list(_GATE_RECORDS))
    baseline = bd.collapse(bd.load_records(baseline_path))
    # judge only what this invocation measured: --config bert_lamb must
    # not "fail" for not re-running the other rows
    baseline = {m: s for m, s in baseline.items() if m in current}
    rows = bd.compare(current, baseline)
    print(f"bench gate vs {os.path.basename(baseline_path)}:",
          file=sys.stderr)
    print(bd.render(rows), file=sys.stderr)
    failures = [
        f"regression: {r['metric']} {r['baseline']} -> {r['current']}"
        for r in rows if r["status"] == "regressed"
    ]
    flash = next(
        (r for r in rows if r["metric"] == bd.FLAT_DEFAULT), None
    )
    if flash is not None and flash["status"] == "flat":
        failures.append(
            f"flatline: {bd.FLAT_DEFAULT} stuck at {flash['current']}"
        )
    for f_ in failures:
        print(f"bench gate FAIL {f_}", file=sys.stderr)
    _emit(
        "bench_gate_failures",
        float(len(failures)),
        "regressions+flatlines vs %s (tools/bench_diff.py; "
        "docs/observability.md)" % os.path.basename(baseline_path),
        None,
    )
    return len(failures)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--config",
        default="bert_lamb",
        choices=sorted(_CONFIGS) + ["all"],
        help="BASELINE parity config to run (default: the #3 north star)",
    )
    ap.add_argument(
        "--trace",
        metavar="DIR",
        default=None,
        help="collect a jax.profiler trace of the timed window into DIR",
    )
    ap.add_argument(
        "--hlo-out",
        metavar="FILE",
        default=None,
        help="write the compiled headline step's optimized-HLO text to "
        "FILE (bert_lamb config; feeds tools/trace_summary.py --hlo). "
        "Equivalent to APEX_TPU_BENCH_HLO_OUT, the programmatic channel.",
    )
    ap.add_argument(
        "--metrics-out",
        metavar="FILE",
        default=None,
        help="also append every emitted metric line to FILE as JSONL "
        "(the observability sink schema, docs/observability.md) — "
        "stdout output is unchanged",
    )
    ap.add_argument(
        "--flight",
        metavar="N[:DIR]",
        default=None,
        help="arm a flight recorder: keep the last N emitted metric "
        "lines and dump flight_<ts>.json on an unhandled exception "
        "(crash forensics, docs/observability.md).  Equivalent to "
        "APEX_TPU_FLIGHT=N[:DIR].",
    )
    ap.add_argument(
        "--lint",
        action="store_true",
        help="run the apex_tpu.analysis graph-lint passes over the "
        "headline step (transfer/donation via compiled HLO, callback "
        "scan via jaxpr) and emit a graph_lint_errors metric line "
        "(docs/analysis.md).  Equivalent to APEX_TPU_BENCH_LINT=1.",
    )
    ap.add_argument(
        "--gate",
        action="store_true",
        help="after the configs run, judge this invocation's metric "
        "lines against the last committed BENCH round with "
        "tools/bench_diff.py (regression + flash-attention flatline "
        "gates); exit 4 on failure so the trajectory cannot go flat "
        "silently again (ROADMAP item 2)",
    )
    ap.add_argument(
        "--gate-baseline",
        metavar="FILE",
        default=None,
        help="baseline round for --gate (default: the newest "
        "BENCH_all_r*.json at the repo root)",
    )
    args = ap.parse_args()
    if args.hlo_out:
        os.environ["APEX_TPU_BENCH_HLO_OUT"] = args.hlo_out
    if args.lint:
        os.environ["APEX_TPU_BENCH_LINT"] = "1"
        _BENCH_LINT = True
    if args.metrics_out:
        from apex_tpu.observability.export import JSONLSink

        _METRICS_SINK = JSONLSink(args.metrics_out)
    from apex_tpu.observability.flight import FlightRecorder

    _FLIGHT = FlightRecorder.from_env(
        args.flight, run={"bench": args.config}
    ) if args.flight else FlightRecorder.from_env(
        run={"bench": args.config}
    )
    try:
        main(config=args.config, trace_dir=args.trace)
        if args.gate and _run_gate(args.gate_baseline):
            sys.exit(4)
    except BaseException as e:
        if _FLIGHT is not None and not isinstance(e, SystemExit):
            from apex_tpu.resilience.runner import _safe_dump

            # guarded: a failing dump (full disk, bad dir) must not
            # demote the crash being debugged to "During handling..."
            _safe_dump(_FLIGHT, f"{type(e).__name__}: {e}")
        raise
    finally:
        if _METRICS_SINK is not None:
            _METRICS_SINK.close()
