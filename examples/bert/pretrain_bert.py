"""BERT-Large phase-1 pretraining — the north-star recipe (BASELINE #3).

End-to-end over the full framework stack: packed-corpus input pipeline
(:mod:`apex_tpu.data`: memmap dataset → sharded loader → native-C++ MLM
corruption → background device prefetch), BERT-Large from
:mod:`apex_tpu.models`, FusedLAMB, bf16 compute with f32 params, data
parallelism over the mesh with K steps per jitted scan chunk, and
orbax-backed checkpoint/resume (:mod:`apex_tpu.checkpoint`).

    python examples/bert/pretrain_bert.py --steps 24 --batch 32
    # resume from the newest checkpoint:
    python examples/bert/pretrain_bert.py --ckpt-dir /tmp/ckpt --resume
    # tiny smoke on CPU:
    JAX_PLATFORMS=cpu python examples/bert/pretrain_bert.py --tiny
"""

import os
import sys

sys.path.insert(
    0, os.path.abspath(os.path.join(os.path.dirname(__file__), "../.."))
)

import argparse
import dataclasses
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from apex_tpu import checkpoint as ckpt
from apex_tpu import parallel_state as ps
from apex_tpu import _native
from apex_tpu.data import (
    DataLoader,
    DevicePrefetcher,
    TokenFileDataset,
    bert_mlm_batches,
    synthetic_token_corpus,
)
from apex_tpu.models import BertConfig, BertForPreTraining, bert_pretrain_loss
from apex_tpu.optimizers import fused_lamb
from apex_tpu.parallel import all_reduce_gradients
from apex_tpu.utils.compile_cache import enable_compile_cache


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=24)
    p.add_argument("--batch", type=int, default=32, help="global batch")
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--chunk", type=int, default=4, help="steps per jit call")
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument(
        "--data", default=None,
        help="packed token file (uint16); default: synthesize a corpus",
    )
    p.add_argument("--ckpt-dir", default=None, help="checkpoint directory")
    p.add_argument(
        "--save-every", type=int, default=8, help="checkpoint every N steps"
    )
    p.add_argument(
        "--resume", action="store_true",
        help="resume from the newest checkpoint in --ckpt-dir",
    )
    p.add_argument("--tiny", action="store_true", help="toy config smoke run")
    p.add_argument(
        "--max-predictions-per-seq", type=int, default=20,
        help="fixed-K masked-position MLM head (the reference recipe's "
        "masked_lm_* input; 0 = dense labels over all positions)",
    )
    return p.parse_args(argv)


def corpus_path(args, cfg) -> str:
    """--data, or a synthetic zipf corpus written once to a temp file —
    either way the batches flow through the real memmap pipeline."""
    if args.data:
        return args.data
    return synthetic_token_corpus(
        os.path.join(
            tempfile.gettempdir(),
            f"apex_tpu_synth_corpus_v{cfg.vocab_size}.bin",
        ),
        vocab_size=cfg.vocab_size,
        num_tokens=2_000_000,
        floor=1000,
    )


def batch_stream(args, cfg, start_step=0):
    """chunk-stacked batch dicts: each leaf (chunk, ...) for lax.scan.

    ``start_step`` seeks the deterministic stream (O(1), index-level) so
    a resumed run continues on the batches an uninterrupted run would
    have seen — restoring params without advancing the data would
    silently retrain on already-consumed batches.
    """
    ds = TokenFileDataset(corpus_path(args, cfg), seq_len=args.seq_len)
    loader = DataLoader(ds, batch_size=args.batch, seed=1234)
    stream = bert_mlm_batches(
        loader, seed=42, mask_prob=0.15, mask_id=103,
        vocab_size=cfg.vocab_size, special_floor=1000,
        start_step=start_step,
        max_predictions_per_seq=args.max_predictions_per_seq or None,
    )
    while True:
        chunk = [next(stream) for _ in range(args.chunk)]
        if args.max_predictions_per_seq:
            # the loss reads only the packed triple — don't ship the
            # dense (S, B) labels to device alongside it
            for b in chunk:
                b.pop("mlm_labels", None)
        yield jax.tree_util.tree_map(lambda *xs: np.stack(xs), *chunk)


def model_config(args) -> BertConfig:
    """The model ``main`` trains: BERT-Large, or the ``--tiny`` toy."""
    if args.tiny:
        return BertConfig(
            vocab_size=2048, hidden_size=64, num_layers=2, num_heads=4,
            intermediate_size=128, max_position_embeddings=args.seq_len,
            dtype=jnp.float32,
        )
    # each layer's checkpoint keeps what its four dense matmuls made (qkv,
    # fc1 and the two post-residual sums: 302 MB a layer at 16,384 tokens a
    # chip) and the backward recomputes only attention's core, GELU and
    # LayerNorm; "full" ran every layer's matmuls a second time.  The layers
    # run as a loop over the stacked leaves, which holds 3 GB less than the
    # scan for the same saves and is the fastest form measured (a v5e, 128 x
    # 128 tokens: 324 ms a step against 384; PERF.md section 6, PR 38).
    return BertConfig(remat=True, remat_policy="sums", scan_layers=False)


def build_step(model, tx, mesh, max_predictions_per_seq):
    """The jitted chunk ``main`` runs: ``(params, opt_state, batches) ->
    (params, opt_state, losses)`` over ``mesh``, each leaf of ``batches``
    stacked ``(chunk, ...)``, params and optimizer state donated."""

    def one_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: bert_pretrain_loss(p, model, batch)
        )(params)
        grads = all_reduce_gradients(grads)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(jnp.add, params, updates)
        return params, opt_state, jax.lax.pmean(loss, ps.DATA_PARALLEL_AXIS)

    def chunk_fn(params, opt_state, batches):
        def body(carry, batch):
            params, opt_state = carry
            params, opt_state, loss = one_step(params, opt_state, batch)
            return (params, opt_state), loss

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), batches
        )
        return params, opt_state, losses

    batch_specs = {
        "input_ids": P(None, None, "dp"),
        "token_type_ids": P(None, None, "dp"),
        "attention_mask": P(None, "dp"),
        "mlm_labels": P(None, None, "dp"),
        "nsp_labels": P(None, "dp"),
    }
    if max_predictions_per_seq:
        # the packed triple is (chunk, K, B) — dp shards B like the labels
        # (which the stream drops in this mode; see batch_stream)
        del batch_specs["mlm_labels"]
        batch_specs.update(
            mlm_positions=P(None, None, "dp"),
            mlm_label_ids=P(None, None, "dp"),
            mlm_weights=P(None, None, "dp"),
        )
    return jax.jit(
        jax.shard_map(
            chunk_fn,
            mesh=mesh,
            in_specs=(P(), P(), batch_specs),
            out_specs=(P(), P(), P()),
            check_vma=False,
        ),
        donate_argnums=(0, 1),
    )


def main(argv=None):
    """Run the recipe; returns the per-step losses, the params, the mesh
    and the global parameter norm before and after (the initial params
    are donated to the first step) for callers that check the run —
    ``chip_smoke.py`` — rather than read its prints."""
    args = parse_args(argv)
    enable_compile_cache()
    cfg = model_config(args)
    mesh = ps.initialize_model_parallel()
    dp = ps.get_data_parallel_world_size()
    if args.batch % dp:
        raise SystemExit(f"--batch must divide dp={dp}")
    if dp > 1:
        # the MLM loss is the mean over the global batch's masked positions,
        # not the mean of the ranks' means (each rank's rows hold their own
        # number of them); the step averages the gradients over dp
        cfg = dataclasses.replace(cfg, mlm_mean_axis=ps.DATA_PARALLEL_AXIS)
    if args.max_predictions_per_seq < 0:
        raise SystemExit("--max-predictions-per-seq must be >= 0")

    model = BertForPreTraining(cfg)
    tx = fused_lamb(learning_rate=args.lr, weight_decay=0.01)
    ids0 = jnp.zeros((args.seq_len, args.batch), jnp.int32)
    # the state enters the first step placed as every step returns it:
    # fresh from init it made the second step a second program (traced,
    # lowered and compiled again)
    rep = jax.sharding.NamedSharding(mesh, P())
    params = jax.device_put(model.init(jax.random.PRNGKey(0), ids0), rep)
    opt_state = jax.device_put(tx.init(params), rep)
    start_step = 0
    if (
        args.resume
        and args.ckpt_dir
        and ckpt.latest_step(args.ckpt_dir) is not None
    ):
        # restore replicated over the mesh (a concrete-array template
        # would re-commit every leaf to device 0 and clash with shard_map)
        tmpl = jax.tree_util.tree_map(
            # .dtype/np.shape read metadata only — no device->host copy
            # of the (large) params/optimizer leaves (jnp.result_type
            # would also downcast the int64 step under disabled x64)
            lambda x: jax.ShapeDtypeStruct(
                np.shape(x), x.dtype, sharding=rep
            ),
            ckpt.snapshot_training_state(params, opt_state, step=0),
        )
        with ckpt.CheckpointManager(args.ckpt_dir) as mgr:
            restored = mgr.restore(template=tmpl)
        params, opt_state, start_step, _, _ = ckpt.restore_training_state(
            restored
        )
        print(f"resumed from step {start_step} ({args.ckpt_dir})")
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))
    print(
        f"BERT {n_params/1e6:.0f}M params | dp={dp} | "
        f"native input pipeline: {_native.available()}"
    )
    param_norm = jax.jit(
        lambda tree: jnp.sqrt(
            sum(jnp.vdot(x, x) for x in jax.tree_util.tree_leaves(tree))
        )
    )
    norm_start = float(param_norm(params))

    step = build_step(model, tx, mesh, args.max_predictions_per_seq)

    n_chunks = max(0, (args.steps - start_step) // args.chunk)
    if n_chunks == 0:
        print(
            f"nothing to do: resumed step {start_step} >= --steps "
            f"{args.steps} (or < one --chunk remaining)"
        )
    mgr = (
        ckpt.CheckpointManager(
            args.ckpt_dir, max_to_keep=2, save_interval_steps=args.save_every
        )
        if args.ckpt_dir
        else None
    )
    t0 = time.perf_counter()
    losses = jnp.zeros((1,))
    all_losses = []
    with DevicePrefetcher(
        batch_stream(args, cfg, start_step), depth=2
    ) as prefetch:
        for c in range(n_chunks):
            batches = next(prefetch)
            params, opt_state, losses = step(params, opt_state, batches)
            all_losses += [float(l) for l in losses]
            print(
                f"chunk {c}: loss "
                f"{' '.join(f'{l:.3f}' for l in all_losses[-args.chunk:])}"
            )
            if mgr is not None:
                done = start_step + (c + 1) * args.chunk
                mgr.save(
                    done,
                    ckpt.snapshot_training_state(
                        params, opt_state, step=done
                    ),
                )
    jax.block_until_ready(losses)
    if mgr is not None:
        mgr.wait_until_finished()
        print(f"checkpoints at steps {mgr.all_steps()} in {args.ckpt_dir}")
        mgr.close()
    dt = time.perf_counter() - t0
    steps_done = n_chunks * args.chunk
    if steps_done:
        print(
            f"{steps_done} steps in {dt:.1f}s = "
            f"{dt / steps_done * 1e3:.0f} ms/step"
        )
    norm_end = float(param_norm(params))
    print(f"param norm {norm_start:.4f} -> {norm_end:.4f}")
    return {
        "losses": all_losses,
        "params": params,
        "mesh": mesh,
        "param_norm_start": norm_start,
        "param_norm_end": norm_end,
    }


if __name__ == "__main__":
    main()
