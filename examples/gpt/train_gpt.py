"""GPT causal-LM training — decoder stack over the full parallelism menu.

Demonstrates the pieces BASELINE #5 benches plus the beyond-reference
axes: tensor parallelism (+ Megatron SP), context parallelism (ring or
Ulysses attention for long sequences), and Switch-MoE expert
parallelism, over the packed-corpus input pipeline.

    python examples/gpt/train_gpt.py --steps 16 --batch 8 --seq-len 512
    python examples/gpt/train_gpt.py --context-parallel ring --seq-len 2048
    python examples/gpt/train_gpt.py --tp 4 --sequence-parallel
    python examples/gpt/train_gpt.py --num-experts 8
    # tiny CPU smoke:
    JAX_PLATFORMS=cpu python examples/gpt/train_gpt.py --tiny
"""

import os
import sys

sys.path.insert(
    0, os.path.abspath(os.path.join(os.path.dirname(__file__), "../.."))
)

import argparse
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from apex_tpu import parallel_state as ps
from apex_tpu.data import (
    DataLoader,
    TokenFileDataset,
    synthetic_token_corpus,
)
from apex_tpu.models.gpt import (
    GptConfig,
    GptModel,
    gpt_lm_loss,
    gpt_lm_loss_cp,
)
from apex_tpu.optimizers import fused_adam
from apex_tpu.transformer.moe import sync_moe_gradients
from apex_tpu.transformer.tensor_parallel import (
    allreduce_sequence_parallel_gradients,
)
from apex_tpu.utils.compile_cache import enable_compile_cache


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=16)
    p.add_argument("--batch", type=int, default=8, help="global batch")
    p.add_argument("--seq-len", type=int, default=512, help="global seq len")
    p.add_argument("--chunk", type=int, default=4, help="steps per jit call")
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--sequence-parallel", action="store_true")
    p.add_argument(
        "--context-parallel",
        choices=["ring", "ring_zigzag", "ulysses"],
        default=None,
    )
    p.add_argument("--cp", type=int, default=2, help="cp degree when used")
    p.add_argument("--num-experts", type=int, default=0)
    p.add_argument("--data", default=None, help="packed uint16 token file")
    p.add_argument("--tiny", action="store_true")
    return p.parse_args(argv)


def corpus(args, vocab) -> str:
    if args.data:
        return args.data
    return synthetic_token_corpus(
        os.path.join(
            tempfile.gettempdir(), f"apex_tpu_gpt_corpus_v{vocab}.bin"
        ),
        vocab_size=vocab,
        zipf_a=1.2,
        seed=1,
    )


def main(argv=None):
    """Run the recipe; returns the per-step losses, the params and the
    mesh for callers (``chip_smoke.py``) that check the run."""
    args = parse_args(argv)
    enable_compile_cache()
    cp = args.cp if args.context_parallel else 1
    cfg = GptConfig(
        **(
            dict(
                vocab_size=1024, hidden_size=64, num_layers=2, num_heads=4,
                intermediate_size=128, dtype=jnp.float32,
            )
            if args.tiny
            else dict(vocab_size=50304, remat=True)
        ),
        max_seq_len=args.seq_len,
        sequence_parallel=args.sequence_parallel,
        context_parallel=args.context_parallel,
        num_experts=args.num_experts,
    )
    mesh = ps.initialize_model_parallel(
        tensor_model_parallel_size=args.tp, context_parallel_size=cp
    )
    dp = ps.get_data_parallel_world_size()
    if args.steps % args.chunk:
        raise SystemExit(
            f"--steps ({args.steps}) must be a multiple of --chunk "
            f"({args.chunk}); a remainder would be silently dropped"
        )
    if args.batch % dp:
        raise SystemExit(
            f"--batch ({args.batch}) must be divisible by dp={dp}"
        )
    if args.seq_len % max(cp, 1):
        raise SystemExit(
            f"--seq-len ({args.seq_len}) must be divisible by cp={cp}"
        )
    if args.context_parallel == "ring_zigzag" and args.seq_len % (2 * cp):
        # 2 chunks per rank: a bare cp-divisible length would silently
        # truncate without this (zigzag_shard also raises at trace time)
        raise SystemExit(
            f"--seq-len ({args.seq_len}) must be divisible by 2*cp="
            f"{2 * cp} for ring_zigzag"
        )

    model = GptModel(cfg)
    tx = fused_adam(learning_rate=args.lr)
    ds = TokenFileDataset(corpus(args, cfg.vocab_size), seq_len=args.seq_len)
    loader = iter(DataLoader(ds, batch_size=args.batch, seed=7))

    def next_chunk():
        # (chunk, S, B) seq-first token batches
        return np.stack(
            [next(loader).T for _ in range(args.chunk)]
        ).astype(np.int32)

    ids0 = jnp.zeros((args.seq_len // max(cp, 1), args.batch), jnp.int32)

    def loss_fn(params, ids_local):
        if cp > 1:
            return gpt_lm_loss_cp(params, model, ids_local)
        return gpt_lm_loss(params, model, ids_local)

    def init_params(key):
        """params live inside shard_map (per-rank tp/ep shards), so init
        is its own jit call and the carry crosses chunks via donation."""
        params = model.init(key, ids0)
        params = {k: v for k, v in params.items() if k != "losses"}
        opt_state = tx.init(params)
        return params, opt_state

    def train_chunk(params, opt_state, chunk_ids):
        def body(carry, ids):
            params, opt_state = carry
            if cp > 1:
                rank = jax.lax.axis_index(ps.CONTEXT_PARALLEL_AXIS)
                s_local = ids.shape[0] // cp
                if args.context_parallel == "ring_zigzag":
                    from apex_tpu.transformer.context_parallel import (
                        zigzag_shard,
                    )

                    ids = zigzag_shard(ids, rank, cp, axis=0)
                else:
                    ids = jax.lax.dynamic_slice_in_dim(
                        ids, rank * s_local, s_local, 0
                    )
            loss, grads = jax.value_and_grad(loss_fn)(params, ids)
            if args.num_experts:
                grads = sync_moe_gradients(
                    grads,
                    sequence_parallel_axis=(
                        ps.TENSOR_PARALLEL_AXIS
                        if args.sequence_parallel and args.tp > 1
                        else None
                    ),
                )
            else:
                grads = jax.tree_util.tree_map(
                    lambda g: jax.lax.pmean(g, ps.DATA_PARALLEL_AXIS), grads
                )
                if args.sequence_parallel and args.tp > 1:
                    grads = allreduce_sequence_parallel_gradients(grads)
            if cp > 1:
                grads = jax.tree_util.tree_map(
                    lambda g: jax.lax.pmean(g, ps.CONTEXT_PARALLEL_AXIS),
                    grads,
                )
            updates, opt_state = tx.update(grads, opt_state, params)
            params = jax.tree_util.tree_map(jnp.add, params, updates)
            return (params, opt_state), jax.lax.pmean(
                loss, ps.DATA_PARALLEL_AXIS
            )

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), chunk_ids
        )
        return params, opt_state, losses

    batch_spec = P(None, None, ps.DATA_PARALLEL_AXIS)  # (chunk, S, B/dp)
    init_fn = jax.jit(
        jax.shard_map(
            init_params, mesh=mesh, in_specs=(P(),),
            out_specs=(P(), P()), check_vma=False,
        )
    )
    step_fn = jax.jit(
        jax.shard_map(
            train_chunk, mesh=mesh,
            in_specs=(P(), P(), batch_spec),
            out_specs=(P(), P(), P()),
            check_vma=False,
        ),
        donate_argnums=(0, 1),
    )

    params, opt_state = init_fn(jax.random.PRNGKey(0))
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))
    print(
        f"GPT {n_params/1e6:.0f}M params/rank | dp={dp} tp={args.tp} "
        f"cp={cp}({args.context_parallel or '-'}) "
        f"sp={args.sequence_parallel} experts={args.num_experts}"
    )
    t0 = time.perf_counter()
    losses = jnp.zeros((1,))
    all_losses = []
    for c in range(args.steps // args.chunk):
        params, opt_state, losses = step_fn(
            params, opt_state, next_chunk()
        )
        all_losses += [float(l) for l in losses]
        print(
            f"chunk {c}: loss "
            f"{' '.join(f'{l:.3f}' for l in all_losses[-args.chunk:])}"
        )
    jax.block_until_ready(losses)
    dt = time.perf_counter() - t0
    done = (args.steps // args.chunk) * args.chunk
    if done:
        print(f"{done} steps in {dt:.1f}s = {dt/done*1e3:.0f} ms/step")
    return {"losses": all_losses, "params": params, "mesh": mesh}


if __name__ == "__main__":
    main()
