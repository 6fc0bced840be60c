"""Pallas TPU paged decode attention — the single-query serving kernel.

The serving half of ``flash_attention.py``: where the training kernel
tiles a (Sq, Sk) score matrix, autoregressive decode has exactly ONE
query row per sequence and a KV history that lives in the paged cache
(:mod:`apex_tpu.serve.cache`) — block-pooled pages scattered through a
shared pool, addressed by a per-sequence page table.  This kernel reads
the pages IN PLACE via scalar-prefetched page-table indexing
(``pltpu.PrefetchScalarGridSpec``: the BlockSpec index map looks the
page id up before the DMA issues), so decode attention never gathers
the history into a contiguous buffer — memory stays O(live tokens) and
the HBM traffic is exactly one read of each live page.

Reuses the flash-attention block machinery: the same online-softmax
(running max / sum / accumulator in VMEM scratch across the page grid
dimension), the same finite ``MASK_VALUE`` masking discipline, and the
same lane-broadcast scratch layout.  Differences, all decode-specific:

- the grid is ``(B, num_pages)`` — one program per (sequence, page);
  the query "tile" is the single (H, D) row, kept resident in VMEM for
  the whole page walk;
- **fused RoPE**: the query row is rotated in-kernel from per-sequence
  cos/sin rows, so the per-layer q-rotation costs no extra HBM
  round-trip (the cached keys were rotated at append time);
- **int8 KV**: pages may carry blockwise int8 codes (one f32 scale per
  (head, token) row, the ``parallel/comm.py`` codec's layout) —
  dequantized on the VPU right after the page DMA, so the wire/HBM
  format is int8 end to end;
- scores run on the VPU (a batched mat-vec cannot feed the MXU); decode
  attention is HBM-bound, so the page reads — not the flops — set the
  roofline.

Page layout is ``(P, H, page, D)`` (heads OUTSIDE the page dim): the
in-kernel q·K and p·V contractions are then head-batched over the
leading block axis with no transposes.  Positions ``>= length`` (the
padded tail of the last live page) mask at ``MASK_VALUE``; pages whose
base position is beyond ``length`` are dead and skipped entirely
(``pl.when``), so a sequence pays only ``ceil(length / page)`` page
reads.  A sequence with ``length == 0`` (an idle decode slot) produces
exactly zeros.

The jnp reference and the public dispatching wrapper live in
:mod:`apex_tpu.ops.paged_attention`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._dispatch import pallas_interpret
from apex_tpu.ops.pallas import introspect
from apex_tpu.ops.pallas.flash_attention import (
    _LANES,
    MASK_VALUE,
    _dot_precision,
)
# the ONE rotate_half (pure jnp split/concat — lowers fine inside the
# kernel body), so serving can never drift from the training rotation
from apex_tpu.ops.rope import rotate_half

__all__ = ["paged_decode_fwd", "kernel_specs"]


# ---------------------------------------------------------------------------
# Call plan — shared by dispatch and the static analyzer's
# kernel_specs() export (see flash_attention.py's plan section).
# ---------------------------------------------------------------------------


def _decode_plan(
    b, h, d, p_, page, np_, dtype, kv_dtype, *, has_scales, has_rope,
):
    in_specs = [
        pl.BlockSpec((1, 1, h, d), lambda b, j, pt, ln: (b, 0, 0, 0)),
        pl.BlockSpec(
            (1, h, page, d), lambda b, j, pt, ln: (pt[b, j], 0, 0, 0)
        ),
        pl.BlockSpec(
            (1, h, page, d), lambda b, j, pt, ln: (pt[b, j], 0, 0, 0)
        ),
    ]
    in_names = ["q", "k_pages", "v_pages"]
    in_shapes = [(b, 1, h, d), (p_, h, page, d), (p_, h, page, d)]
    in_dtypes = [dtype, kv_dtype, kv_dtype]
    if has_scales:
        in_specs += [
            pl.BlockSpec(
                (1, h, page), lambda b, j, pt, ln: (pt[b, j], 0, 0)
            ),
            pl.BlockSpec(
                (1, h, page), lambda b, j, pt, ln: (pt[b, j], 0, 0)
            ),
        ]
        in_names += ["k_scale", "v_scale"]
        in_shapes += [(p_, h, page), (p_, h, page)]
        in_dtypes += [jnp.float32, jnp.float32]
    if has_rope:
        in_specs += [
            pl.BlockSpec((1, 1, d), lambda b, j, pt, ln: (b, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda b, j, pt, ln: (b, 0, 0)),
        ]
        in_names += ["rope_cos", "rope_sin"]
        in_shapes += [(b, 1, d), (b, 1, d)]
        in_dtypes += [dtype, dtype]
    return dict(
        grid=(b, np_),
        in_specs=in_specs,
        in_names=in_names,
        in_shapes=in_shapes,
        in_dtypes=in_dtypes,
        out_specs=[pl.BlockSpec(
            (1, 1, h, d), lambda b, j, pt, ln: (b, 0, 0, 0)
        )],
        out_names=["o"],
        out_shape=[jax.ShapeDtypeStruct((b, 1, h, d), dtype)],
        scratch_shapes=[
            pltpu.VMEM((h, d), jnp.float32),
            pltpu.VMEM((h, _LANES), jnp.float32),
            pltpu.VMEM((h, _LANES), jnp.float32),
        ],
        dimension_semantics=("parallel", "arbitrary"),
    )


def kernel_specs(
    b, h, d, *, pool_pages, page, pages_per_seq, dtype=jnp.bfloat16,
    kv_wire="f32", rope=True, page_table=None,
):
    """Export the paged-decode kernel's :class:`introspect.KernelSpec`
    without compiling.  The page-table indirection is resolved against
    ``page_table`` (B, pages_per_seq) when given, else a synthetic
    round-robin table over ``pool_pages`` — either way the index maps
    under analysis are the REAL scalar-prefetch maps, evaluated on a
    concrete table (the coverage pass proves every referenced page id
    stays inside the pool)."""
    import numpy as np

    dtype = jnp.dtype(dtype)
    kv_dtype = jnp.dtype(jnp.int8 if kv_wire == "int8" else dtype)
    if page_table is None:
        page_table = (
            np.arange(b * pages_per_seq).reshape(b, pages_per_seq)
            % max(pool_pages - 1, 1)
        ) + 1  # skip the reserved null page 0, like live allocations
    page_table = np.asarray(page_table)
    lengths = np.full((b,), pages_per_seq * page, np.int32)
    plan = _decode_plan(
        b, h, d, pool_pages, page, pages_per_seq, dtype, kv_dtype,
        has_scales=kv_wire == "int8", has_rope=rope,
    )
    # close the scalar-prefetch operands over the concrete table so the
    # analyzer can call maps with grid indices alone
    for key in ("in_specs", "out_specs"):
        plan[key] = [
            pl.BlockSpec(
                spec.block_shape,
                (lambda m: lambda b, j: m(b, j, page_table, lengths))(
                    spec.index_map
                ),
            )
            for spec in plan[key]
        ]
    spec = introspect.from_plan(
        "paged_decode_fwd",
        plan,
        # head-batched q.K and p.V mat-vecs on the VPU
        flops_per_cell=4.0 * h * page * d,
        intermediates=(((h, page), jnp.float32), ((h, page), jnp.float32)),
    )
    # no matmul_dims meta: the score/PV contractions here are
    # head-batched MAT-VECS on the VPU (module docstring) — the MXU
    # 128-alignment lint does not apply, decode is HBM-bound by design
    return [spec]


def _decode_kernel(
    pt_ref, len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, cos_ref, sin_ref,
    o_ref, acc_ref, m_ref, l_ref,
    *, scale, page, np_, rope, prec,
):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    # dead page: every position in it is >= length (idle slots have
    # length 0 — ALL their pages are dead and the output is zeros)
    live = j * page < length

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (H, D)
        if rope:
            cos = cos_ref[0].astype(jnp.float32)  # (1, D)
            sin = sin_ref[0].astype(jnp.float32)
            q = q * cos + rotate_half(q) * sin
        k = k_ref[0].astype(jnp.float32)  # (H, page, D)
        v = v_ref[0].astype(jnp.float32)
        if ks_ref is not None:
            # blockwise int8 codes: one f32 scale per (head, token) row
            k = k * ks_ref[0].astype(jnp.float32)[..., None]
            v = v * vs_ref[0].astype(jnp.float32)[..., None]
        # head-batched mat-vec on the VPU: s[h, t] = q[h, :] . k[h, t, :]
        s = jax.lax.dot_general(
            q[:, None, :], k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32, precision=prec,
        )[:, 0, :] * scale  # (H, page)
        pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * page
        s = jnp.where(pos < length, s, MASK_VALUE)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # (H, page)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        # o[h, d] += p[h, :] . v[h, :, d]
        pv = jax.lax.dot_general(
            p[:, None, :], v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32, precision=prec,
        )[:, 0, :]  # (H, D)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == np_ - 1)
    def _finalize():
        l = l_ref[:, :1]
        # an idle slot (length 0) never accumulated: l == 0 there, and
        # the contract is zeros, not 0/0
        o = jnp.where(l > 0, acc_ref[...] / jnp.maximum(l, 1e-30), 0.0)
        o_ref[...] = o.astype(o_ref.dtype)[None, None]


def _decode_entry(*refs, has_scales, has_rope, **kw):
    pt_ref, len_ref, q_ref, k_ref, v_ref = refs[:5]
    i = 5
    ks_ref = vs_ref = cos_ref = sin_ref = None
    if has_scales:
        ks_ref, vs_ref = refs[i], refs[i + 1]
        i += 2
    if has_rope:
        cos_ref, sin_ref = refs[i], refs[i + 1]
        i += 2
    o_ref, acc_ref, m_ref, l_ref = refs[i:]
    _decode_kernel(
        pt_ref, len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
        cos_ref, sin_ref, o_ref, acc_ref, m_ref, l_ref, **kw
    )


@functools.partial(jax.jit, static_argnames=("scale",))
def paged_decode_fwd(
    q, k_pages, v_pages, page_table, lengths, *,
    scale, k_scale=None, v_scale=None, rope_cos=None, rope_sin=None,
):
    """Single-query attention over the paged KV cache.

    - ``q`` (B, H, D): the current token's (pre-RoPE) query rows;
    - ``k_pages`` / ``v_pages`` (P, H, page, D): the shared page pool —
      f32/bf16, or int8 codes when ``k_scale``/``v_scale`` (P, H, page)
      carry the blockwise f32 scales;
    - ``page_table`` (B, NP) int32: page ids per sequence in context
      order (entries beyond the live count may point anywhere — dead
      pages are skipped by ``lengths``);
    - ``lengths`` (B,) int32: live KV positions per sequence, INCLUDING
      the current token (whose k/v the caller appended before calling);
    - ``rope_cos`` / ``rope_sin`` (B, D): the rotation rows of each
      sequence's current position — fused onto ``q`` in-kernel.

    Returns (B, H, D) in ``q.dtype``; rows with ``lengths == 0`` are
    exactly zero.
    """
    b, h, d = q.shape
    p_, _, page, _ = k_pages.shape
    np_ = page_table.shape[1]
    has_scales = k_scale is not None
    has_rope = rope_cos is not None
    if has_scales != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be given together")
    if has_rope != (rope_sin is not None):
        raise ValueError("rope_cos and rope_sin must be given together")
    if has_rope and not (rope_cos.shape == rope_sin.shape == (b, d)):
        # a half-width (B, D/2) table compiles silently at D=128
        raise ValueError(
            f"rope_cos/rope_sin must be (B, D) = {(b, d)}, got "
            f"{rope_cos.shape} and {rope_sin.shape}"
        )

    # q as (B, 1, H, D) so its block carries an (H, D) tile per program
    plan = _decode_plan(
        b, h, d, p_, page, np_, q.dtype, k_pages.dtype,
        has_scales=has_scales, has_rope=has_rope,
    )
    args = [q[:, None], k_pages, v_pages]
    if has_scales:
        args += [k_scale, v_scale]
    if has_rope:
        args += [rope_cos[:, None], rope_sin[:, None]]

    kernel = functools.partial(
        _decode_entry, scale=scale, page=page, np_=np_,
        rope=has_rope, has_scales=has_scales, has_rope=has_rope,
        # f32 queries get true-f32 products like the flash kernel's: at
        # DEFAULT the compiled kernel sat 3.5e-3 abs off the f32
        # reference on v5e (PR 21); the bf16 serving path is unchanged
        prec=_dot_precision(q.dtype),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=plan["grid"],
        in_specs=plan["in_specs"],
        out_specs=plan["out_specs"][0],
        scratch_shapes=plan["scratch_shapes"],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=plan["out_shape"][0],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=plan["dimension_semantics"],
        ),
        interpret=pallas_interpret(),
    )(
        jnp.asarray(page_table, jnp.int32),
        jnp.asarray(lengths, jnp.int32),
        *args,
    )
    return out[:, 0]
