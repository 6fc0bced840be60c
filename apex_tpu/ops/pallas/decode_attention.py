"""Pallas TPU paged decode attention — the single-query serving kernel.

The serving half of ``flash_attention.py``: where the training kernel
tiles a (Sq, Sk) score matrix, autoregressive decode has exactly ONE
query row per sequence and a KV history that lives in the paged cache
(:mod:`apex_tpu.serve.cache`) — block-pooled pages scattered through a
shared pool, addressed by a per-sequence page table.  This kernel reads
the pages IN PLACE: the pool stays in HBM, and the kernel copies a
sequence's pages into VMEM itself, page ids looked up in the
scalar-prefetched table — decode attention never gathers the history
into a contiguous buffer, and both its memory and its time are O(live
tokens).

**The walk follows the live pages.**  The grid is ``(B,)``, one program
a sequence.  A program loops ``ceil(length / (K * page))`` times; a step
copies ``K`` pages of K and of V (``K`` = :func:`pages_per_step`: 128
positions a step) into one of two buffer slots while the step before is
attended, joins them into one ``(H/G, K*page, W)`` block and makes ONE
online-softmax update over its ``K * page`` positions.  Every page id
goes through :func:`_live_page`, which clamps the table index to the
sequence's last live page: the tail of a sequence's last step re-reads
that page (masked), entries past the live count are never read whatever
they hold, and a slot with ``length == 0`` copies nothing, attends
nothing and writes exact zeros.  (Until PR 36 the grid was ``(B, NP)``:
one 16-row page a grid step, every table entry walked whether live or
not, 2,048 steps a layer at the serving shape for ~22 live ones.  A
static grid of ``K`` page operands a step was measured too and is no
better than that: what a dead step costs is its operands' index maps,
and there are as many of them.  CHANGES.md, PR 36.)

Reuses the flash-attention block machinery: the same online-softmax
(running max / sum / accumulator, here the loop's carry), the same finite
``MASK_VALUE`` masking discipline.  Differences, all decode-specific:

- the query "tile" is the single (H, D) row, resident for the walk;
- **fused RoPE**: the query row is rotated in-kernel from per-sequence
  cos/sin rows, so the per-layer q-rotation costs no extra HBM
  round-trip (the cached keys were rotated at append time);
- **int8 KV**: pages may carry blockwise int8 codes (one f32 scale per
  (head, token) row, the ``parallel/comm.py`` codec).  The codes are
  copied like any page, and so are their scales: the scale planes ``(L,
  P, 1, page, lane_width(H))`` hold a token a row and a head a lane —
  whole 128-lane tiles, which a copy out of HBM can slice — so a step
  copies its ``K`` scale pages in beside its code pages, turns the joined
  ``(K*page, lanes)`` slab over (positions along the lanes, like the
  scores) and takes each lane row's ``G`` heads out of it.  A head's K
  scale is a factor of its score and its V scale a factor of its weight,
  so the block itself is never rescaled, and nothing is gathered outside
  the kernel: the int8 call's time follows the live tokens too;
- decode attention is HBM-bound: the page reads — not the flops — set
  the roofline.

The operand is the WHOLE pool ``(L, P, H/G, page, W)`` plus a layer
index (a third scalar-prefetch operand): the serving programs carry the
pool through their layer loop as one buffer and never slice a layer out
(docs/serving.md "The KV pool").  ``G`` heads sit side by side in one
lane row (``G = 128 // D`` when ``D < 128`` and the heads pair up;
:func:`heads_per_row`) — read here from the operands' shapes (``G = H //
pool.shape[2]``), never from a flag — and the row is **lane-dense**: ``W
= lane_width(D * G)``, whole 128-lane tiles, the lanes past ``D * G``
zero (25 heads of 64 lanes, or heads of 80 or 96: ``G = 1`` and a row
padded to 128).  Mosaic copies an array out of HBM by whole 128-lane
rows only, and a narrower minor dimension makes XLA:TPU relay the pool
around every program (docs/serving.md "The KV pool"), so whole tiles
are the one layout the kernel's copies and the serving programs agree
on: ``serve.cache.init_kv_pages`` builds every pool so, and the public
op pads a narrower array it is handed (a copy of it).  Heads stay
OUTSIDE the page dim: the q·K and p·V contractions
are head-row-batched over the leading block axis with no transposes.
The ``G`` heads of a row go through ONE contraction each way: the query
enters as ``G`` rows, row ``g`` holding head ``g``'s lanes and zeros
elsewhere, so the block is passed over once for the scores of all ``G``
heads and once for their ``p·V`` products, of which row ``g`` keeps head
``g``'s lanes at the end; softmax state is per head.  Positions ``>=
length`` (the tail of the last live step) mask at ``MASK_VALUE``.

The jnp reference and the public dispatching wrapper live in
:mod:`apex_tpu.ops.paged_attention`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
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

__all__ = [
    "paged_decode_fwd", "kernel_specs", "heads_per_row", "lane_width",
    "pad_lanes", "pages_per_step", "walk_live_share",
]


# ---------------------------------------------------------------------------
# The walk's step: how many pages the kernel takes at a time
# ---------------------------------------------------------------------------

#: positions one step of the walk attends: swept on v5e at GPT-2 Large's
#: serving shape, K = 4 to 32 pages of 16 (B=32, H=20, D=64, NP=64:
#: CHANGES.md, PR 36)
_STEP_ROWS = 128
#: a guard, not a swept value: the bytes of K and V one buffer slot may
#: hold, so two slots and their f32 copies stay inside VMEM for a model
#: whose rows are wide (it binds from 4 KiB a position's K row upward)
_STEP_BYTES = 2 << 20


def lane_width(n: int) -> int:
    """``n`` lanes rounded up to whole 128-lane tiles: the width of a
    pool row that holds ``n`` lanes of heads."""
    return -(-n // _LANES) * _LANES


def pages_per_step(page: int, row_bytes: int, np_: int) -> int:
    """``K``: the pages of one sequence a step of the walk copies in and
    attends together, from what a call can see — the page size, the bytes
    of one position's K row over all heads, the table's width."""
    by_rows = _STEP_ROWS // page
    by_bytes = _STEP_BYTES // (2 * page * row_bytes)
    return max(1, min(by_rows, by_bytes, np_))


def _pool_step(pool, np_: int):
    """``(page, K)`` of the walk over ``pool`` (a pool plane, or its
    shape and dtype) under a table ``np_`` entries wide."""
    hg, page, w = pool.shape[2:]
    row_bytes = hg * w * jnp.dtype(pool.dtype).itemsize
    return page, pages_per_step(page, row_bytes, np_)


def walk_live_share(lengths, pool, np_: int):
    """Of the pages one call's walk over ``pool`` copies in under a table
    ``np_`` entries wide — ``K`` a step, the last step's tail re-reading
    its sequence's last live page — the share that hold live positions;
    ``None`` for a call with nothing live.  Host arithmetic on the
    ``lengths`` a caller holds in numpy; ``K`` is the kernel's own."""
    page, pp = _pool_step(pool, np_)
    live = -(-np.asarray(lengths) // page)
    copied = pp * int((-(-live // pp)).sum())
    return int(live.sum()) / copied if copied else None


def _live_page(pt, ln, b, n, page):
    """Entry ``n`` of sequence ``b``'s page table, clamped to its last
    live page: past ``ceil(len / page)`` entries the walk stops moving,
    so what the dead entries hold is never read."""
    last = jnp.maximum((ln[b] + (page - 1)) // page - 1, 0)
    return pt[b, jnp.minimum(n, last)]


# ---------------------------------------------------------------------------
# Call plan — shared by dispatch and the static analyzer's
# kernel_specs() export (see flash_attention.py's plan section).
# ---------------------------------------------------------------------------


def _decode_plan(
    b, h, d, layers, p_, page, np_, dtype, kv_dtype, *,
    groups, has_scales, has_rope, rep=1,
):
    """The ``pallas_call``'s arguments: grid ``(B,)``, the pool (and the
    int8 wire's scale planes) left in HBM, the step's buffers as
    scratch.  ``rep`` query heads read each of the pool's ``h / rep`` KV
    heads (grouped-query attention; 1: every head its own K/V)."""
    hg, w = h // (groups * rep), lane_width(d * groups)
    pool = (layers, p_, hg, page, w)
    _, pp = _pool_step(jax.ShapeDtypeStruct(pool, kv_dtype), np_)
    # a token's scales a row, a head a lane, whole tiles like a page's
    scales = (layers, p_, 1, page, lane_width(h))

    def row(b, pt, ln, ly):
        return (b, 0, 0, 0)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, rep, hg, w), row), hbm, hbm]
    in_names = ["q", "k_pages", "v_pages"]
    in_shapes = [(b, rep, hg, w), pool, pool]
    in_dtypes = [dtype, kv_dtype, kv_dtype]
    if has_scales:
        in_specs += [hbm, hbm]
        in_names += ["k_scale", "v_scale"]
        in_shapes += [scales, scales]
        in_dtypes += [jnp.float32, jnp.float32]
    if has_rope:
        in_specs += [
            pl.BlockSpec((1, 1, w), lambda b, pt, ln, ly: (b, 0, 0))
        ] * 2
        in_names += ["rope_cos", "rope_sin"]
        in_shapes += [(b, 1, w), (b, 1, w)]
        in_dtypes += [dtype, dtype]
    # two slots of K pages for every plane left in HBM, a DMA semaphore
    # a copy
    paged = [
        (shape, dt) for shape, dt, spec in zip(in_shapes, in_dtypes, in_specs)
        if spec is hbm
    ]
    return dict(
        pages_per_step=pp,
        grid=(b,),
        in_specs=in_specs,
        in_names=in_names,
        in_shapes=in_shapes,
        in_dtypes=in_dtypes,
        out_specs=[pl.BlockSpec((1, rep, hg, w), row)],
        out_names=["o"],
        out_shape=[jax.ShapeDtypeStruct((b, rep, hg, w), dtype)],
        scratch_shapes=[
            pltpu.VMEM((2, pp) + shape[2:], dt) for shape, dt in paged
        ] + [pltpu.SemaphoreType.DMA((2, len(paged), pp))],
        dimension_semantics=("parallel",),
    )


def heads_per_row(num_heads: int, head_dim: int) -> int:
    """``G``: how many heads the KV pool lays side by side in one lane
    row — as many as fill the 128 lanes when ``head_dim`` is narrower
    and ``num_heads`` divides evenly, else 1 (the row is then padded to
    whole tiles: :func:`lane_width`).  A minor dimension that is a
    multiple of 128 lanes is what lets XLA:TPU keep the pool in plain
    row-major layout, the one layout every serving program and this
    kernel agree on (docs/serving.md "The KV pool")."""
    g = _LANES // head_dim if _LANES % head_dim == 0 else 1
    return g if num_heads % g == 0 else 1


def kernel_specs(
    b, h, d, *, pool_pages, page, pages_per_seq, dtype=jnp.bfloat16,
    kv_wire="f32", rope=True, page_table=None, lengths=None, kv_heads=None,
):
    """Export the paged-decode kernel's :class:`introspect.KernelSpec`
    without compiling (a one-layer pool in the serving layout).  The
    copies the kernel makes are described in BlockSpec form: grid ``(B,
    ceil(NP / K))``, ``K`` page operands a plane a step (the pools and
    the int8 wire's scale planes alike), each through :func:`_live_page`
    — the function the kernel's copies call.  The page-table indirection is
    resolved against ``page_table`` (B, pages_per_seq) and ``lengths``
    (B,) when given, else a synthetic round-robin table over
    ``pool_pages`` at full lengths — either way the index maps under
    analysis are the REAL ones, the clamp to the live pages included,
    evaluated on a concrete table (the coverage pass proves every page id
    the copies reach stays inside the pool)."""
    dtype = jnp.dtype(dtype)
    kv_dtype = jnp.dtype(jnp.int8 if kv_wire == "int8" else dtype)
    if page_table is None:
        page_table = (
            np.arange(b * pages_per_seq).reshape(b, pages_per_seq)
            % max(pool_pages - 1, 1)
        ) + 1  # skip the reserved null page 0, like live allocations
    page_table = np.asarray(page_table)
    if lengths is None:
        lengths = np.full((b,), pages_per_seq * page, np.int32)
    lengths = np.asarray(lengths, np.int32)
    hkv = h if kv_heads is None else kv_heads
    groups = heads_per_row(hkv, d)
    plan = _decode_plan(
        b, h, d, 1, pool_pages, page, pages_per_seq, dtype, kv_dtype,
        groups=groups, has_scales=kv_wire == "int8", has_rope=rope,
        rep=h // hkv,
    )
    pp = plan.pop("pages_per_step")
    steps = -(-pages_per_seq // pp)

    def page_block(shape, i):
        return pl.BlockSpec(
            (1, 1) + shape[2:],
            lambda b, j: (
                0, _live_page(page_table, lengths, b, j * pp + i, page),
                0, 0, 0,
            ),
        )

    # the rows ride the call's own pipeline, one block a sequence; each
    # plane left in HBM (the pools, the int8 wire's scale planes) becomes
    # the K page operands a step copies, which the analyzer's double
    # buffering counts as the call's two scratch slots
    operands = []
    for name, shape, dt, spec in zip(
        plan["in_names"], plan["in_shapes"], plan["in_dtypes"],
        plan["in_specs"],
    ):
        if spec.block_shape is not None:
            m = spec.index_map
            operands.append((name, shape, dt, pl.BlockSpec(
                spec.block_shape,
                lambda b, j, m=m: m(b, page_table, lengths, None),
            )))
        else:
            operands += [
                (f"{name}[{i}]", shape, dt, page_block(shape, i))
                for i in range(pp)
            ]
    names, shapes, dtypes, specs = zip(*operands)
    plan.update(
        grid=(b, steps),
        dimension_semantics=("parallel", "arbitrary"),
        in_names=names, in_shapes=shapes, in_dtypes=dtypes, in_specs=specs,
        out_specs=[pl.BlockSpec(
            plan["out_specs"][0].block_shape, lambda b, j: (b, 0, 0, 0)
        )],
        scratch_shapes=[],
    )
    rows, w = pp * page, lane_width(d * groups)
    spec = introspect.from_plan(
        "paged_decode_fwd",
        plan,
        # one (G, W) x (W, rows) score and one (G, rows) x (rows, W)
        # context product a lane row
        flops_per_cell=4.0 * h * rows * w,
        # the joined f32 K and V blocks, scores and probabilities
        intermediates=(
            ((hkv // groups, rows, w), jnp.float32),
            ((hkv // groups, rows, w), jnp.float32),
            ((h, rows), jnp.float32), ((h, rows), jnp.float32),
        ),
    )
    spec.meta["pages_per_step"] = pp
    # no matmul_dims meta: the score/PV contractions carry G query rows
    # (module docstring) — the MXU 128-alignment lint does not apply,
    # decode is HBM-bound by design
    return [spec]


def _rotate_half_rows(x, d, groups):
    """:func:`rotate_half` inside each ``d``-lane head of a lane row;
    the row's padding lanes stay zero."""
    parts = [rotate_half(x[:, g * d:(g + 1) * d]) for g in range(groups)]
    if x.shape[-1] > groups * d:
        parts.append(jnp.zeros_like(x[:, groups * d:]))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)


def _step_copies(pt_ref, len_ref, layer, b, planes, sem, step, slot,
                 *, pp, page):
    """The copies of step ``step`` of sequence ``b`` into buffer slot
    ``slot``: ``pp`` pages of every plane, HBM -> VMEM."""
    out = []
    for i in range(pp):
        pid = _live_page(pt_ref, len_ref, b, step * pp + i, page)
        for n, (pool, buf) in enumerate(planes):
            out.append(pltpu.make_async_copy(
                pool.at[layer, pid], buf.at[slot, i], sem.at[slot, n, i]
            ))
    return out


def _join_pages(buf, slot, pp):
    """A slot's ``pp`` pages as one f32 ``(R, pp*page, W)`` block."""
    pages = [buf[slot, i].astype(jnp.float32) for i in range(pp)]
    return pages[0] if pp == 1 else jnp.concatenate(pages, axis=1)


def _scales_by_position(buf, slot, pp, hg, groups):
    """A slot's ``pp`` scale pages — a token a row, a head a lane — as
    ``(H/G, G, pp*page)``: a lane row's heads down, positions along the
    lanes, the layout of the scores."""
    heads = _join_pages(buf, slot, pp)[0].T  # (lanes, pp*page)
    return jnp.stack(
        [heads[h * groups:(h + 1) * groups] for h in range(hg)]
    )


def _decode_kernel(
    pt_ref, len_ref, layer_ref, q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm,
    cos_ref, sin_ref, o_ref, k_buf, v_buf, ks_buf, vs_buf, sem,
    *, scale, page, pp, groups, d, prec,
):
    b = pl.program_id(0)
    rep, hg, w = q_ref.shape[1:]
    rows = pp * page
    length = len_ref[b]
    planes = [(k_hbm, k_buf), (v_hbm, v_buf)]
    if ks_hbm is not None:
        planes += [(ks_hbm, ks_buf), (vs_hbm, vs_buf)]
    copies = functools.partial(
        _step_copies, pt_ref, len_ref, layer_ref[0], b, planes, sem,
        pp=pp, page=page,
    )
    # the G heads of a lane row as G query rows: row g owns head g's
    # lanes, and no row the padding past the last head
    shape = (1, groups, w)
    own = (
        jax.lax.broadcasted_iota(jnp.int32, shape, 2) // d
        == jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    )

    @pl.when(length == 0)
    def _idle():
        # an idle slot copies nothing and attends nothing: exact zeros
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length > 0)
    def _walk():
        steps = (length + (rows - 1)) // rows
        for c in copies(0, 0):
            c.start()
        # a KV head's ``rep`` query heads (grouped-query attention) are
        # ``rep`` more query rows over the same block: row ``i * G + g``
        # is query ``i`` of the lane row's head ``g``, so a page is copied
        # in once for all of them
        qs = []
        for i in range(rep):
            q = q_ref[0, i].astype(jnp.float32)  # (H/G, W)
            if cos_ref is not None:
                cos = cos_ref[0].astype(jnp.float32)  # (1, W)
                sin = sin_ref[0].astype(jnp.float32)
                q = q * cos + _rotate_half_rows(q, d, groups) * sin
            qs.append(jnp.where(own, q[:, None, :], 0.0))  # (H/G, G, W)
        qs = qs[0] if rep == 1 else jnp.concatenate(qs, axis=1)

        def step(j, carry):
            m, l, acc = carry
            slot = j % 2

            @pl.when(j + 1 < steps)
            def _next():
                for c in copies(j + 1, 1 - slot):
                    c.start()

            for c in copies(j, slot):
                c.wait()
            # s[h, g, t] = q_g[h, :] . k[h, t, :]: every head of the lane
            # row from one pass over the block
            s = jax.lax.dot_general(
                qs, _join_pages(k_buf, slot, pp),
                (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32, precision=prec,
            ) * scale  # (H/G, G, rows)
            if ks_buf is not None:
                # blockwise int8 codes: one f32 scale per (head, token),
                # and row g holds head g alone — the scale of K is a
                # factor of the score, that of V a factor of the weight
                s = s * _scales_by_position(ks_buf, slot, pp, hg, groups)
            pos = jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, rows), 2
            ) + j * rows
            s = jnp.where(pos < length, s, MASK_VALUE)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            if vs_buf is not None:
                p = p * _scales_by_position(vs_buf, slot, pp, hg, groups)
            # row g's product is right in head g's lanes (kept at the
            # end) and a finite by-product in the others'
            acc = acc * alpha + jax.lax.dot_general(
                p, _join_pages(v_buf, slot, pp),
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32, precision=prec,
            )  # (H/G, G, W)
            return m_new, l, acc

        m, l, acc = jax.lax.fori_loop(0, steps, step, (
            jnp.full((hg, rep * groups, 1), -jnp.inf, jnp.float32),
            jnp.zeros((hg, rep * groups, 1), jnp.float32),
            jnp.zeros((hg, rep * groups, w), jnp.float32),
        ))
        # length > 0: position 0 is live, so l >= 1
        if rep == 1:
            o = jnp.sum(jnp.where(own, acc / l, 0.0), axis=1)
            o_ref[...] = o.astype(o_ref.dtype)[None, None]
        else:
            o = acc / l
            for i in range(rep):
                rows_i = o[:, i * groups:(i + 1) * groups]
                o_ref[0, i] = jnp.sum(
                    jnp.where(own, rows_i, 0.0), axis=1).astype(o_ref.dtype)


def _decode_entry(*refs, has_scales, has_rope, **kw):
    pt_ref, len_ref, layer_ref, q_ref, k_hbm, v_hbm = refs[:6]
    i = 6
    ks_hbm = vs_hbm = cos_ref = sin_ref = ks_buf = vs_buf = None
    if has_scales:
        ks_hbm, vs_hbm = refs[i], refs[i + 1]
        i += 2
    if has_rope:
        cos_ref, sin_ref = refs[i], refs[i + 1]
        i += 2
    o_ref, k_buf, v_buf = refs[i:i + 3]
    if has_scales:
        ks_buf, vs_buf = refs[i + 3:i + 5]
    _decode_kernel(
        pt_ref, len_ref, layer_ref, q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm,
        cos_ref, sin_ref, o_ref, k_buf, v_buf, ks_buf, vs_buf, refs[-1],
        **kw
    )


def pad_lanes(x, w):
    """``x`` with its minor dimension zero-padded to ``w`` lanes."""
    if x.shape[-1] == w:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, w - x.shape[-1])])


@functools.partial(jax.jit, static_argnames=("scale", "kv_heads"))
def paged_decode_fwd(
    q, k_pages, v_pages, page_table, lengths, layer, *,
    scale, k_scale=None, v_scale=None, rope_cos=None, rope_sin=None,
    kv_heads=None,
):
    """Single-query attention over one layer of the paged KV pool.

    - ``q`` (B, H, D): the current token's (pre-RoPE) query rows;
    - ``k_pages`` / ``v_pages`` (L, P, H/G, page, W): the whole pool
      in the serving layout (module docstring), ``W = lane_width(D*G)``
      — f32/bf16, or int8 codes when ``k_scale``/``v_scale`` (L, P, 1,
      page, lane_width(H)) carry the blockwise f32 scales, a token a
      row and a head a lane;
    - ``page_table`` (B, NP) int32: page ids per sequence in context
      order (entries beyond the live count may point anywhere in the
      pool — the walk never reads the pages they name);
    - ``lengths`` (B,) int32: live KV positions per sequence, INCLUDING
      the current token (whose k/v the caller appended before calling);
    - ``layer`` () int32: which layer of the pool to read;
    - ``rope_cos`` / ``rope_sin`` (B, D): the rotation rows of each
      sequence's current position — fused onto ``q`` in-kernel;
    - ``kv_heads`` (static): the heads the pool holds when fewer than
      ``H`` (grouped-query attention: query head ``i`` reads KV head ``i //
      (H / kv_heads)``; a page is copied in once for all the query heads
      of its KV heads).  None: ``H``.

    Returns (B, H, D) in ``q.dtype``; rows with ``lengths == 0`` are
    exactly zero.
    """
    b, h, d = q.shape
    layers, p_, hg, page, w = k_pages.shape
    hkv = h if kv_heads is None else kv_heads
    rep, groups = h // hkv, hkv // hg
    if hkv * rep != h or hg * groups != hkv or w != lane_width(d * groups):
        raise ValueError(
            f"pool rows {(hg, w)} do not hold {hkv} heads of {d} lanes in "
            f"whole 128-lane tiles (for {h} query heads)"
        )
    np_ = page_table.shape[1]
    has_scales = k_scale is not None
    has_rope = rope_cos is not None
    if has_scales != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be given together")
    if has_scales and rep > 1:
        raise ValueError(
            "the int8 KV wire is not written for grouped-query heads")
    planes = (layers, p_, 1, page, lane_width(h))
    if has_scales and not (k_scale.shape == v_scale.shape == planes):
        raise ValueError(
            f"k_scale/v_scale must be {planes}: a token a row, a head a "
            f"lane, got {k_scale.shape} and {v_scale.shape}"
        )
    if has_rope != (rope_sin is not None):
        raise ValueError("rope_cos and rope_sin must be given together")
    if has_rope and not (rope_cos.shape == rope_sin.shape == (b, d)):
        # a half-width (B, D/2) table compiles silently at D=128
        raise ValueError(
            f"rope_cos/rope_sin must be (B, D) = {(b, d)}, got "
            f"{rope_cos.shape} and {rope_sin.shape}"
        )

    # q as (B, 1, H/G, W): a token's (H, D) row IS its lane rows
    plan = _decode_plan(
        b, h, d, layers, p_, page, np_, q.dtype, k_pages.dtype,
        groups=groups, has_scales=has_scales, has_rope=has_rope, rep=rep,
    )
    pp = plan["pages_per_step"]
    page_table = jnp.asarray(page_table, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)
    if rep == 1:
        rows = q.reshape(b, 1, hg, d * groups)
    else:
        # query i of KV head (r, g) to row i of lane row r, head g's lanes
        rows = jnp.transpose(
            q.reshape(b, hg, groups, rep, d), (0, 3, 1, 2, 4)
        ).reshape(b, rep, hg, d * groups)
    args = [pad_lanes(rows, w), k_pages, v_pages]
    if has_scales:
        args += [k_scale, v_scale]
    if has_rope:
        args += [
            pad_lanes(jnp.tile(rope_cos, (1, groups)), w)[:, None],
            pad_lanes(jnp.tile(rope_sin, (1, groups)), w)[:, None],
        ]

    kernel = functools.partial(
        _decode_entry, scale=scale, page=page, pp=pp, groups=groups, d=d,
        has_scales=has_scales, has_rope=has_rope,
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
    )(page_table, lengths, layer.reshape(1), *args)
    if w != d * groups:
        out = out[..., :d * groups]
    if rep > 1:
        out = jnp.transpose(
            out.reshape(b, rep, hg, groups, d), (0, 2, 3, 1, 4))
    return out.reshape(b, h, d)
