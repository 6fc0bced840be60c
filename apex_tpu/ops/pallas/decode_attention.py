"""Pallas TPU paged decode attention — the single-query serving kernel.

The serving half of ``flash_attention.py``: where the training kernel
tiles a (Sq, Sk) score matrix, autoregressive decode has exactly ONE
query row per sequence and a KV history that lives in the paged cache
(:mod:`apex_tpu.serve.cache`) — block-pooled pages scattered through a
shared pool, addressed by a per-sequence page table.  This kernel reads
the pages IN PLACE via scalar-prefetched page-table indexing
(``pltpu.PrefetchScalarGridSpec``: the BlockSpec index map looks the
page id up before the DMA issues), so decode attention never gathers
the history into a contiguous buffer — memory stays O(live tokens).
Dead pages (beyond ``length``) are skipped in COMPUTE only: their page
table entries still drive the index map, so each distinct dead entry is
still fetched (consecutive null-page entries re-use the resident block).
Clamping the walk to the live pages is a named follow-up, not done here.

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

The operand is the WHOLE pool ``(L, P, H/G, page, D·G)`` plus a layer
index (a third scalar-prefetch operand): the serving programs carry the
pool through their layer loop as one buffer and never slice a layer out
(docs/serving.md "The KV pool").  ``G`` heads sit side by side in one
lane row so the minor dimension is lane-dense (``G = 128 // D`` when
``D < 128``; :func:`heads_per_row`) — read here
from the operands' shapes (``G = H // pool.shape[2]``), never from a
flag.  Heads stay OUTSIDE the page dim: the q·K and p·V contractions
are head-row-batched over the leading block axis with no transposes,
and the ``G`` heads of a row are taken apart by masking the query's
lanes (scores) and selecting each head's lanes of the ``p·V`` product;
softmax state is per head.  Positions ``>= length`` (the
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

__all__ = ["paged_decode_fwd", "kernel_specs", "heads_per_row"]


# ---------------------------------------------------------------------------
# Call plan — shared by dispatch and the static analyzer's
# kernel_specs() export (see flash_attention.py's plan section).
# ---------------------------------------------------------------------------


def _decode_plan(
    b, h, d, layers, p_, page, np_, dtype, kv_dtype, *,
    groups, has_scales, has_rope,
):
    hg, dg = h // groups, d * groups

    def row(b, j, pt, ln, ly):
        return (b, 0, 0, 0)

    def page_of(b, j, pt, ln, ly):
        return (ly[0], pt[b, j], 0, 0, 0)

    pool = (layers, p_, hg, page, dg)
    in_specs = [
        pl.BlockSpec((1, 1, hg, dg), row),
        pl.BlockSpec((1, 1, hg, page, dg), page_of),
        pl.BlockSpec((1, 1, hg, page, dg), page_of),
    ]
    in_names = ["q", "k_pages", "v_pages"]
    in_shapes = [(b, 1, hg, dg), pool, pool]
    in_dtypes = [dtype, kv_dtype, kv_dtype]
    if has_scales:
        in_specs += [pl.BlockSpec((1, 1, hg, page, groups), page_of)] * 2
        in_names += ["k_scale", "v_scale"]
        in_shapes += [pool[:-1] + (groups,)] * 2
        in_dtypes += [jnp.float32, jnp.float32]
    if has_rope:
        in_specs += [
            pl.BlockSpec((1, 1, dg), lambda b, j, pt, ln, ly: (b, 0, 0))
        ] * 2
        in_names += ["rope_cos", "rope_sin"]
        in_shapes += [(b, 1, dg), (b, 1, dg)]
        in_dtypes += [dtype, dtype]
    return dict(
        grid=(b, np_),
        in_specs=in_specs,
        in_names=in_names,
        in_shapes=in_shapes,
        in_dtypes=in_dtypes,
        out_specs=[pl.BlockSpec((1, 1, hg, dg), row)],
        out_names=["o"],
        out_shape=[jax.ShapeDtypeStruct((b, 1, hg, dg), dtype)],
        scratch_shapes=[
            pltpu.VMEM((hg, dg), jnp.float32),
            pltpu.VMEM((groups, hg, _LANES), jnp.float32),
            pltpu.VMEM((groups, hg, _LANES), jnp.float32),
        ],
        dimension_semantics=("parallel", "arbitrary"),
    )


def heads_per_row(num_heads: int, head_dim: int) -> int:
    """``G``: how many heads the KV pool lays side by side in one lane
    row — as many as fill the 128 lanes when ``head_dim`` is narrower
    and ``num_heads`` divides evenly, else 1.  A minor dimension that is
    a multiple of 128 lanes is what lets XLA:TPU keep the pool in plain
    row-major layout, the one layout every serving program and this
    kernel agree on (docs/serving.md "The KV pool")."""
    g = _LANES // head_dim if _LANES % head_dim == 0 else 1
    return g if num_heads % g == 0 else 1


def kernel_specs(
    b, h, d, *, pool_pages, page, pages_per_seq, dtype=jnp.bfloat16,
    kv_wire="f32", rope=True, page_table=None,
):
    """Export the paged-decode kernel's :class:`introspect.KernelSpec`
    without compiling (a one-layer pool in the serving layout).  The
    page-table indirection is resolved against ``page_table`` (B,
    pages_per_seq) when given, else a synthetic round-robin table over
    ``pool_pages`` — either way the index maps under analysis are the
    REAL scalar-prefetch maps, evaluated on a concrete table (the
    coverage pass proves every referenced page id stays inside the
    pool)."""
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
    layer = np.zeros((1,), np.int32)
    plan = _decode_plan(
        b, h, d, 1, pool_pages, page, pages_per_seq, dtype, kv_dtype,
        groups=heads_per_row(h, d),
        has_scales=kv_wire == "int8", has_rope=rope,
    )
    # close the scalar-prefetch operands over the concrete table so the
    # analyzer can call maps with grid indices alone
    for key in ("in_specs", "out_specs"):
        plan[key] = [
            pl.BlockSpec(
                spec.block_shape,
                (lambda m: lambda b, j: m(b, j, page_table, lengths, layer))(
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


def _rotate_half_rows(x, d):
    """:func:`rotate_half` inside each ``d``-lane head of a lane row."""
    return jnp.concatenate(
        [rotate_half(x[:, i:i + d]) for i in range(0, x.shape[-1], d)],
        axis=-1,
    )


def _decode_kernel(
    pt_ref, len_ref, layer_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
    cos_ref, sin_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale, page, np_, groups, rope, prec,
):
    del layer_ref  # consumed by the page index map
    b = pl.program_id(0)
    j = pl.program_id(1)
    hg, dg = acc_ref.shape
    d = dg // groups
    # lane -> which of the row's heads it belongs to
    head_of = jax.lax.broadcasted_iota(jnp.int32, (1, dg), 1) // d

    def spread(per_head):
        """``groups`` per-head columns ``(..., 1)`` -> one lane row
        ``(..., D*G)``, each head's value across its own ``D`` lanes."""
        out = per_head[0]
        for g in range(1, groups):
            out = jnp.where(head_of == g, per_head[g], out)
        return out

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
        q = q_ref[0, 0].astype(jnp.float32)  # (H/G, D*G)
        if rope:
            cos = cos_ref[0].astype(jnp.float32)  # (1, D*G)
            sin = sin_ref[0].astype(jnp.float32)
            q = q * cos + _rotate_half_rows(q, d) * sin
        k = k_ref[0, 0].astype(jnp.float32)  # (H/G, page, D*G)
        v = v_ref[0, 0].astype(jnp.float32)
        if ks_ref is not None:
            # blockwise int8 codes: one f32 scale per (head, token) row
            ks = ks_ref[0, 0].astype(jnp.float32)  # (H/G, page, G)
            vs = vs_ref[0, 0].astype(jnp.float32)
            k = k * spread([ks[..., g:g + 1] for g in range(groups)])
            v = v * spread([vs[..., g:g + 1] for g in range(groups)])
        pos = jax.lax.broadcasted_iota(jnp.int32, (hg, page), 1) + j * page
        alphas, pvs = [], []
        for g in range(groups):
            # one head of every row: its query lanes, the others zeroed
            qg = q if groups == 1 else jnp.where(head_of == g, q, 0.0)
            # row-batched mat-vec: s[h, t] = q[h, :] . k[h, t, :]
            s = jax.lax.dot_general(
                qg[:, None, :], k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32, precision=prec,
            )[:, 0, :] * scale  # (H/G, page)
            s = jnp.where(pos < length, s, MASK_VALUE)

            m_prev = m_ref[g, :, :1]
            l_prev = l_ref[g, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)  # (H/G, page)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            # o[h, :] += p[h, :] . v[h, :, :] (this head's lanes kept)
            pvs.append(jax.lax.dot_general(
                p[:, None, :], v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32, precision=prec,
            )[:, 0, :])  # (H/G, D*G)
            alphas.append(alpha)
            m_ref[g] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[g] = jnp.broadcast_to(l_new, l_ref.shape[1:])
        acc_ref[...] = acc_ref[...] * spread(alphas) + spread(pvs)

    @pl.when(j == np_ - 1)
    def _finalize():
        l = spread([l_ref[g, :, :1] for g in range(groups)])
        # an idle slot (length 0) never accumulated: l == 0 there, and
        # the contract is zeros, not 0/0
        o = jnp.where(l > 0, acc_ref[...] / jnp.maximum(l, 1e-30), 0.0)
        o_ref[...] = o.astype(o_ref.dtype)[None, None]


def _decode_entry(*refs, has_scales, has_rope, **kw):
    pt_ref, len_ref, layer_ref, q_ref, k_ref, v_ref = refs[:6]
    i = 6
    ks_ref = vs_ref = cos_ref = sin_ref = None
    if has_scales:
        ks_ref, vs_ref = refs[i], refs[i + 1]
        i += 2
    if has_rope:
        cos_ref, sin_ref = refs[i], refs[i + 1]
        i += 2
    o_ref, acc_ref, m_ref, l_ref = refs[i:]
    _decode_kernel(
        pt_ref, len_ref, layer_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
        cos_ref, sin_ref, o_ref, acc_ref, m_ref, l_ref, **kw
    )


@functools.partial(jax.jit, static_argnames=("scale",))
def paged_decode_fwd(
    q, k_pages, v_pages, page_table, lengths, layer, *,
    scale, k_scale=None, v_scale=None, rope_cos=None, rope_sin=None,
):
    """Single-query attention over one layer of the paged KV pool.

    - ``q`` (B, H, D): the current token's (pre-RoPE) query rows;
    - ``k_pages`` / ``v_pages`` (L, P, H/G, page, D*G): the whole pool
      in the serving layout (module docstring) — f32/bf16, or int8
      codes when ``k_scale``/``v_scale`` (L, P, H/G, page, G) carry the
      blockwise f32 scales;
    - ``page_table`` (B, NP) int32: page ids per sequence in context
      order (entries beyond the live count may point anywhere — dead
      pages are skipped by ``lengths``);
    - ``lengths`` (B,) int32: live KV positions per sequence, INCLUDING
      the current token (whose k/v the caller appended before calling);
    - ``layer`` () int32: which layer of the pool to read;
    - ``rope_cos`` / ``rope_sin`` (B, D): the rotation rows of each
      sequence's current position — fused onto ``q`` in-kernel.

    Returns (B, H, D) in ``q.dtype``; rows with ``lengths == 0`` are
    exactly zero.
    """
    b, h, d = q.shape
    layers, p_, hg, page, dg = k_pages.shape
    groups = h // hg
    if hg * groups != h or dg != d * groups:
        raise ValueError(
            f"pool rows {(hg, dg)} do not hold {h} heads of {d} lanes"
        )
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

    # q as (B, 1, H/G, D*G): a token's (H, D) row IS its lane rows
    plan = _decode_plan(
        b, h, d, layers, p_, page, np_, q.dtype, k_pages.dtype,
        groups=groups, has_scales=has_scales, has_rope=has_rope,
    )
    args = [q.reshape(b, 1, hg, dg), k_pages, v_pages]
    if has_scales:
        args += [k_scale, v_scale]
    if has_rope:
        args += [
            jnp.tile(rope_cos, (1, groups))[:, None],
            jnp.tile(rope_sin, (1, groups))[:, None],
        ]

    kernel = functools.partial(
        _decode_entry, scale=scale, page=page, np_=np_, groups=groups,
        rope=has_rope, has_scales=has_scales, has_rope=has_rope,
        # f32 queries get true-f32 products like the flash kernel's: at
        # DEFAULT the compiled kernel sat 3.5e-3 abs off the f32
        # reference on v5e (PR 21); the bf16 serving path is unchanged
        prec=_dot_precision(q.dtype),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
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
        # the trace's name for the custom call (benchmark/readers.py)
        name="paged_decode_fwd",
    )(
        jnp.asarray(page_table, jnp.int32),
        jnp.asarray(lengths, jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        *args,
    )
    return out.reshape(b, h, d)
