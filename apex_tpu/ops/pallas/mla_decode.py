"""Pallas TPU latent-attention decode — absorbed-form MLA over latent pages.

Multi-head latent attention caches ONE row a token, shared by every query
head: the normalised latent ``c`` (``kv_lora_rank`` wide) and the rotated
shared key ``k_r``.  In absorbed form a head's query is carried into latent
space once a step (``q_n W_b^K``), so decode attention is multi-query
attention whose keys are the cached rows themselves and whose values are
their first ``kv_lora_rank`` lanes: the scores are ``[q_abs | q_r] . [c |
k_r]`` and the context comes back in latent space, for ``W_b^V`` to carry
out of it.  Nothing per head is ever read from the cache.

The pool is ``(L, P, 1, page, W)``: a row is ``[c | k_r | 0]``, ``W`` the
row padded to whole 128-lane tiles (576 -> 640) so the pool keeps the plain
row-major layout every program agrees on (docs/serving.md "Layer kinds and
the cache set").  The grid is ``(B, pages / PAGES_PER_STEP)``: a step takes
``PAGES_PER_STEP`` pages of one sequence through as many scalar-prefetched
index maps over the same operand (a 16-row page alone is 20 KB: too small a
step), runs one ``(heads, W) x (W, rows)`` score matmul and one ``(heads,
rows) x (rows, W)`` context matmul on the MXU, and keeps the online-softmax
state in VMEM.  Page-table entries past a sequence's live pages point at
the null page, so consecutive dead entries re-use one resident block; steps
wholly past ``length`` are skipped.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._dispatch import pallas_interpret
from apex_tpu.ops.pallas.flash_attention import _LANES, MASK_VALUE

__all__ = ["mla_decode_fwd", "PAGES_PER_STEP"]

PAGES_PER_STEP = 8


def _kernel(pt_ref, len_ref, q_ref, *refs, pp, page, scale, steps):
    del pt_ref
    pages, (o_ref, acc_ref, m_ref, l_ref) = refs[:pp], refs[pp:]
    b, j = pl.program_id(0), pl.program_id(1)
    rows = pp * page

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]

    @pl.when(j * rows < length)
    def _step():
        q = q_ref[0]                                        # (heads, W)
        kv = jnp.concatenate([r[0, 0, 0] for r in pages], axis=0)
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                           # (heads, rows)
        pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * rows
        s = jnp.where(pos < length, s, MASK_VALUE)
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
        l = l_ref[:, :1]
        # an idle slot (length 0) accumulated nothing: zeros, not 0/0
        o_ref[0] = jnp.where(
            l > 0, acc_ref[...] / jnp.maximum(l, 1e-30), 0.0
        )


@functools.partial(jax.jit, static_argnames=("layer", "scale"))
def mla_decode_fwd(q, pool, page_table, lengths, *, layer: int, scale):
    """``q`` ``(B, heads, W)`` the absorbed queries ``[q_n W_b^K | q_r |
    0]``; ``pool`` ``(L, P, 1, page, W)`` the latent pages; ``page_table``
    ``(B, NP)``; ``lengths`` ``(B,)`` live rows, the current token's
    included.  Returns the context in latent space ``(B, heads, W)`` f32
    (lanes past ``kv_lora_rank`` hold the weighted ``k_r`` and are the
    caller's to drop); idle rows are zeros."""
    b, heads, w = q.shape
    page = pool.shape[3]
    np_ = page_table.shape[1]
    pp = PAGES_PER_STEP if np_ % PAGES_PER_STEP == 0 else 1
    steps = np_ // pp

    def page_block(i):
        return pl.BlockSpec(
            (1, 1, 1, page, w),
            lambda b, j, pt, ln: (layer, pt[b, j * pp + i], 0, 0, 0),
        )

    def row(b, j, pt, ln):
        return (b, 0, 0)

    return pl.pallas_call(
        functools.partial(
            _kernel, pp=pp, page=page, scale=scale, steps=steps
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, steps),
            in_specs=[pl.BlockSpec((1, heads, w), row)]
            + [page_block(i) for i in range(pp)],
            out_specs=pl.BlockSpec((1, heads, w), row),
            scratch_shapes=[
                pltpu.VMEM((heads, w), jnp.float32),
                pltpu.VMEM((heads, _LANES), jnp.float32),
                pltpu.VMEM((heads, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, heads, w), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=pallas_interpret(),
        name="mla_decode_fwd",
    )(
        jnp.asarray(page_table, jnp.int32), jnp.asarray(lengths, jnp.int32),
        q.astype(pool.dtype), *([pool] * pp),
    )
