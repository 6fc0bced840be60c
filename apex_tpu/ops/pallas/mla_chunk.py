"""Pallas TPU latent-attention prefill chunk — absorbed-form MLA over the
latent pages, many query rows at once.

A prefill chunk of ``C`` tokens at position ``offset`` has written its own
latent rows ``[c | k_r | 0]`` into its pages; each of its queries attends
every row before its own position, the chunk's earlier rows and its own
included, all read back from the pool through the sequence's page table.
In absorbed form (:mod:`apex_tpu.ops.pallas.mla_decode`) every head's query
is carried into latent space (``[q_n W_b^K | q_r | 0]``), so the chunk is
multi-query attention of ``C * heads`` query rows over ``W``-lane rows that
hold keys and values at once: the decode kernel's grid with a block of
query rows in place of one sequence's heads, and a causal bound a row.

The grid is ``(C / BLOCK_Q, pages / PAGES_PER_STEP)``.  A query block is
``BLOCK_Q`` tokens times every head, token-major (row ``t * heads + h``);
a step takes ``PAGES_PER_STEP`` pages through as many scalar-prefetched
index maps and runs one ``(rows, W) x (W, kv rows)`` score matmul and one
``(rows, kv rows) x (kv rows, W)`` context matmul on the MXU, the
online-softmax state in VMEM.  A step wholly past the block's last query
position is skipped, and its index maps hold the block's last live page,
so nothing more is copied in.  No score tensor larger than one step's tile
exists.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._dispatch import pallas_interpret
from apex_tpu.ops.pallas.flash_attention import _LANES, MASK_VALUE

__all__ = ["mla_chunk_fwd", "BLOCK_Q", "PAGES_PER_STEP"]

#: tokens of a query block (times the heads: its rows)
BLOCK_Q = 64
#: latent pages a grid step reads
PAGES_PER_STEP = 32
#: a 64-token block of 20 heads holds ~20 MB of tiles and scratch
_VMEM_LIMIT = 64 * 1024 * 1024


def _kernel(pt_ref, off_ref, q_ref, qpos_ref, *refs, pp, page, scale, bq,
            steps):
    del pt_ref
    pages, (o_ref, acc_ref, m_ref, l_ref) = refs[:pp], refs[pp:]
    i, j = pl.program_id(0), pl.program_id(1)
    rows = pp * page

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the block's last query position: later steps hold no live row
    @pl.when(j * rows <= off_ref[0] + i * bq + bq - 1)
    def _step():
        q = q_ref[...]                                     # (bq*heads, W)
        kv = jnp.concatenate([r[0, 0, 0] for r in pages], axis=0)
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                          # (rows_q, rows)
        pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * rows
        s = jnp.where(pos <= qpos_ref[...], s, MASK_VALUE)
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(kv.dtype), kv, preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == steps - 1)
    def _finalize():
        # step 0 holds position 0, which every query row sees: l > 0
        o_ref[...] = acc_ref[...] / l_ref[:, :1]


@functools.partial(jax.jit, static_argnames=("layer", "scale"))
def mla_chunk_fwd(q, pool, page_table, offset, *, layer: int, scale):
    """``q`` ``(C, heads, W)`` the chunk's absorbed queries ``[q_n W_b^K |
    q_r | 0]``, query ``t`` at position ``offset + t``; ``pool`` ``(L, P,
    1, page, W)`` the latent pages, the chunk's own rows already written;
    ``page_table`` ``(NP,)`` the sequence's pages; ``offset`` ``()`` int32.
    Returns the context in latent space ``(C, heads, W)`` f32 (lanes past
    ``kv_lora_rank`` hold the weighted ``k_r`` and are the caller's to
    drop): query ``t`` over rows ``0 .. offset + t``."""
    c, heads, w = q.shape
    page = pool.shape[3]
    np_ = page_table.shape[0]
    bq = math.gcd(c, BLOCK_Q)
    pp = math.gcd(np_, PAGES_PER_STEP)
    steps = np_ // pp
    rq = bq * heads
    offset = jnp.asarray(offset, jnp.int32).reshape(1)

    def page_block(k):
        def index(i, j, pt, off):
            # past the block's last live page: hold it (no new copy)
            last = (off[0] + i * bq + bq - 1) // page
            return (layer, pt[jnp.minimum(j * pp + k, last)], 0, 0, 0)

        return pl.BlockSpec((1, 1, 1, page, w), index)

    # each query row's position, token-major like the rows
    qpos = offset[0] + jnp.repeat(
        jnp.arange(c, dtype=jnp.int32), heads)[:, None]
    rows_q = pl.BlockSpec((rq, w), lambda i, j, pt, off: (i, 0))
    out = pl.pallas_call(
        functools.partial(
            _kernel, pp=pp, page=page, scale=scale, bq=bq, steps=steps
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(c // bq, steps),
            in_specs=[rows_q,
                      pl.BlockSpec((rq, 1), lambda i, j, pt, off: (i, 0))]
            + [page_block(k) for k in range(pp)],
            out_specs=rows_q,
            scratch_shapes=[
                pltpu.VMEM((rq, w), jnp.float32),
                pltpu.VMEM((rq, _LANES), jnp.float32),
                pltpu.VMEM((rq, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((c * heads, w), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=pallas_interpret(),
        name="mla_chunk_fwd",
    )(
        jnp.asarray(page_table, jnp.int32), offset,
        q.astype(pool.dtype).reshape(c * heads, w), qpos, *([pool] * pp),
    )
    return out.reshape(c, heads, w)
