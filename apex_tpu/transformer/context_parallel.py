"""Context parallelism — long-context attention over a mesh axis.

**No reference analog** (SURVEY §2.3: CP/ring/Ulysses are ABSENT in the
reference — its max context is bounded by one device's memory).  This
module is the TPU-native extension that makes long context first-class:

- :func:`ring_attention` — blockwise ring attention (Liu et al. 2023) over
  the ``cp`` mesh axis: q stays put, (k, v) blocks rotate ring-wise via
  ``jax.lax.ppermute`` over ICI neighbors, and per-block flash results are
  folded with the running online-softmax merge.  Sequence length scales
  linearly with the ring size at O(S_local²) compute per hop; compute and
  the permute overlap (XLA schedules the collective-permute concurrently
  with the previous block's matmuls).
- :func:`ulysses_attention` — DeepSpeed-Ulysses-style all-to-all: scatter
  heads / gather sequence (``jax.lax.all_to_all``), run ordinary (flash)
  attention on full sequences with H/cp local heads, all-to-all back.
  Cheaper than the ring when H ≥ cp and sequence fits once gathered.

Both are differentiable: Ulysses through ``all_to_all``'s transpose, the
ring through the scanned ``ppermute`` (per-hop recompute via
``jax.checkpoint`` — the standard ring-attention backward, so residual
memory stays O(S_local) per hop rather than O(S²)).

Layouts match the attention stack: q, k, v are ``(B, H, S_local, D)``
shards.  With the default ``layout="contiguous"`` rank r holds rows
``[r·S_local, (r+1)·S_local)``; with ``layout="zigzag"`` (causal
load balancing) rank r holds global chunks ``r`` and ``2cp−1−r`` — use
:func:`zigzag_split` / :func:`zigzag_merge` to convert.  Causal masking
honors global positions in both layouts.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu import parallel_state as ps

__all__ = [
    "ring_attention",
    "ulysses_attention",
    "zigzag_shard",
    "zigzag_split",
    "zigzag_merge",
]

_CP = ps.CONTEXT_PARALLEL_AXIS


def _block_attend(q, k, v, scale, *, causal=False, dropout_p=0.0,
                  dropout_rng=None, bias=None):
    """One (q-block × kv-block) flash block: returns (o (f32), lse).

    o is the block-normalized output, lse the row logsumexp — exactly the
    pair the online-softmax merge needs.  Dispatches through
    ``flash_attention_with_lse`` (its backward consumes the lse cotangent
    the merge produces): the Pallas kernel path — which never materializes
    the (S_local, S_local) score matrix in HBM — is taken on TPU when
    S_local >= 1024 (or the dispatch is forced); shorter hops use the jnp
    composition, whose transient score block XLA wins on anyway at those
    sizes (see ops.attention._pallas_eligible).  ``causal`` covers the
    ring's diagonal (self) block.
    """
    from apex_tpu.ops.attention import flash_attention_with_lse

    o, lse = flash_attention_with_lse(
        q, k, v, bias, causal=causal, scale=scale, dropout_p=dropout_p,
        dropout_rng=dropout_rng,
    )
    return o.astype(jnp.float32), lse


def _merge_block(carry, block):
    """Fold one (o, lse) block into the running online-softmax state
    ``(acc, m, l)``.  Block o is block-normalized (mass 1·β); a skipped
    block's ``lse = -inf`` folds to exactly zero weight against any
    finite running max.  THE merge for every ring layout — the max-shift
    / rescale / renormalize here is the numerically subtle core, so it
    exists exactly once."""
    acc, m, l = carry
    o_b, lse_b = block
    m_new = jnp.maximum(m, lse_b)
    alpha = jnp.exp(m - m_new)
    beta = jnp.exp(lse_b - m_new)
    l_new = l * alpha + beta
    acc_new = (
        acc * (l * alpha)[..., None] + o_b * beta[..., None]
    ) / l_new[..., None]
    return acc_new, m_new, l_new


def _skipped_block(b, h, rows, d):
    """(o, lse) of a fully-masked (causal-future) block: zero mass —
    both einsums skipped entirely."""
    return (
        jnp.zeros((b, h, rows, d), jnp.float32),
        jnp.full((b, h, rows), -jnp.inf, jnp.float32),
    )


def ring_attention(
    q,
    k,
    v,
    bias=None,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    dropout_p: float = 0.0,
    dropout_rng=None,
    layout: str = "contiguous",
    axis_name: str = _CP,
):
    """Blockwise ring attention over ``axis_name``.

    q, k, v: ``(B, H, S_local, D)`` — this rank's sequence chunk.
    Returns ``(B, H, S_local, D)`` in q's dtype, equal (within numerics)
    to full attention over the gathered sequence.

    Causal mode skips the block compute entirely for hops whose kv chunk
    lies in this rank's causal future (``lax.switch`` on the chunk order);
    the permute still runs every hop, so the ring stays in lockstep.  Note
    contiguous chunking makes causal work *imbalanced* across ranks (rank 0
    computes 1 block, rank cp-1 computes cp) — the wall-clock cost per hop
    is set by the busiest rank.  ``layout="zigzag"`` fixes that: each
    rank holds global chunks ``r`` and ``2cp−1−r`` (use
    :func:`zigzag_split` / :func:`zigzag_merge` for the layout), pairing
    a cheap early chunk with an expensive late one so every rank computes
    ~2 half-blocks per hop — halving causal ring wall on real hardware
    (Megatron-LM's cp layout).  Zigzag requires ``causal=True``.

    ``bias``: a per-rank KEY-PADDING mask of shape ``(B, 1, 1,
    S_local)`` (additive, non-trainable, MASK_VALUE-clamped) covering
    this rank's OWN kv chunk — in the rank's configured layout, so
    under ``layout="zigzag"`` its halves cover the rank's two global
    chunks (``zigzag_shard`` the global mask along its key axis).  It
    rotates around the ring with (k, v), so every hop masks the padded
    keys of whichever chunk it attends.  Variable-length long-document
    batches are the use case; each query row must keep at least one
    unmasked key globally.  Query-dependent bias shapes are rejected
    (they cannot rotate with kv; fold such terms into the model
    instead).

    ``dropout_p`` > 0 (with ``dropout_rng``) applies attention dropout
    that composes exactly with the ring merge: each (q-rank, kv-chunk)
    block draws an independent mask (``dropout_rng`` folded with
    ``rank·cp + src``), the block's PV contribution is masked +
    rescaled while its lse stays the full undropped statistic, and the
    merge weights blocks by true softmax mass — the result equals
    full-sequence attention under the block-assembled mask.  Masks
    regenerate deterministically in backward (the hop is
    ``jax.checkpoint``-ed with the same folded rng).
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if dropout_p > 0.0 and dropout_rng is None:
        raise ValueError("dropout_p > 0 requires dropout_rng")
    if bias is not None:
        if bias.ndim < 4:
            bias = bias.reshape((1,) * (4 - bias.ndim) + bias.shape)
        if bias.shape[1] != 1 or bias.shape[2] != 1:
            raise ValueError(
                "ring_attention only rotates a key-padding bias of "
                f"shape (B, 1, 1, S_local); got {bias.shape} — "
                "query-dependent bias cannot rotate with kv"
            )
        if bias.shape[-1] not in (1, k.shape[-2]):
            raise ValueError(
                f"ring_attention bias covers {bias.shape[-1]} keys but "
                f"this rank's kv chunk has {k.shape[-2]} — pass the "
                "RANK-LOCAL slice of the global mask (it rotates with "
                "kv), not the global mask itself"
            )
    if layout == "zigzag":
        if not causal:
            raise ValueError(
                "layout='zigzag' exists to balance CAUSAL ring work; "
                "non-causal rings are already balanced — use the "
                "contiguous layout"
            )
        return _ring_attention_zigzag(
            q, k, v, bias, scale, dropout_p, dropout_rng, axis_name
        )
    if layout != "contiguous":
        raise ValueError(f"unknown ring layout {layout!r}")
    world = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    b, h, s_local, d = q.shape
    qf = q.astype(jnp.float32)
    perm = [(i, (i + 1) % world) for i in range(world)]

    @jax.checkpoint
    def hop(qf, kv, src):
        """(o, lse) for this rank's q against the kv chunk from ``src``."""
        kb, vb, bias_b = kv
        kw = {} if bias_b is None else dict(bias=bias_b)
        if dropout_p > 0.0:
            kw.update(
                dropout_p=dropout_p,
                dropout_rng=jax.random.fold_in(
                    dropout_rng, rank * world + src
                ),
            )
        if not causal:
            return _block_attend(qf, kb, vb, scale, **kw)

        def self_block(_):
            return _block_attend(qf, kb, vb, scale, causal=True, **kw)

        def past_block(_):
            return _block_attend(qf, kb, vb, scale, **kw)

        def future_block(_):
            return _skipped_block(b, h, s_local, d)

        branch = jnp.where(src == rank, 0, jnp.where(src < rank, 1, 2))
        return jax.lax.switch(branch, [self_block, past_block, future_block], None)

    # hop 0 is always the self block — no permute needed before it, and it
    # seeds the running max with a finite lse (so -inf skipped hops merge
    # to exactly zero weight)
    kv0 = (k, v, bias)
    o0, lse0 = hop(qf, kv0, rank)
    carry = (o0, lse0, jnp.ones((b, h, s_local), jnp.float32))

    def body(state, step):
        kv, carry = state
        # rotate FIRST: world-1 permutes total, none wasted on the last
        # hop; the key-padding bias rides the same rotation as (k, v)
        kv = jax.tree_util.tree_map(
            lambda x: jax.lax.ppermute(x, axis_name, perm), kv
        )
        src = (rank - step) % world
        carry = _merge_block(carry, hop(qf, kv, src))
        return (kv, carry), None

    if world > 1:
        (_, carry), _ = jax.lax.scan(
            body, (kv0, carry), jnp.arange(1, world)
        )
    acc, _, _ = carry
    return acc.astype(q.dtype)


def zigzag_shard(x, rank, cp: int, axis: int = 0):
    """ONE rank's zigzag shard of a GLOBAL array: the concatenation of
    global chunks ``rank`` and ``2cp−1−rank`` along ``axis`` (``rank``
    may be traced, e.g. ``jax.lax.axis_index``).  THE definition of the
    zigzag layout contract for in-shard_map use — models, examples and
    tests slice through here so the chunk math exists once; whole-array
    host-side conversion is :func:`zigzag_split` / :func:`zigzag_merge`.
    Raises unless the axis divides into ``2·cp`` chunks (a remainder
    would silently drop trailing tokens)."""
    size = x.shape[axis]
    if size % (2 * cp):
        raise ValueError(
            f"zigzag layout needs the sequence ({size}) divisible by "
            f"2*cp ({2 * cp}); a remainder would silently drop tokens"
        )
    sc = size // (2 * cp)
    lo = jax.lax.dynamic_slice_in_dim(x, rank * sc, sc, axis)
    hi = jax.lax.dynamic_slice_in_dim(x, (2 * cp - 1 - rank) * sc, sc, axis)
    return jnp.concatenate([lo, hi], axis=axis)


def zigzag_split(x, cp: int, axis: int = 2):
    """Global → zigzag layout: split ``axis`` into ``2·cp`` chunks and
    stack per-rank locals ``(cp, ..., S/cp, ...)`` where rank ``r`` holds
    the concatenation of chunks ``r`` and ``2cp−1−r``.  This pairs an
    early (cheap) causal chunk with a late (expensive) one, balancing
    causal ring work across ranks (Megatron-LM's cp layout)."""
    chunks = jnp.split(x, 2 * cp, axis=axis)
    return jnp.stack(
        [
            jnp.concatenate([chunks[r], chunks[2 * cp - 1 - r]], axis=axis)
            for r in range(cp)
        ]
    )


def zigzag_merge(locals_, cp: int, axis: int = 2):
    """Inverse of :func:`zigzag_split`: ``(cp, ..., S/cp, ...)`` stacked
    per-rank zigzag locals → the global-order array."""
    out = [None] * (2 * cp)
    for r in range(cp):
        lo, hi = jnp.split(locals_[r], 2, axis=axis)
        out[r] = lo
        out[2 * cp - 1 - r] = hi
    return jnp.concatenate(out, axis=axis)


def _ring_attention_zigzag(q, k, v, bias, scale, dropout_p, dropout_rng,
                           axis_name):
    """Causal ring attention over the zigzag layout: this rank's
    ``S_local`` rows are [global chunk ``r``; global chunk ``2cp−1−r``].

    Work per hop is balanced by construction: the lo half attends only lo
    kv halves (one half-block, skipped for future sources), the hi half
    attends every lo half (always) plus non-future hi halves — every rank
    computes ~2 half-blocks per hop instead of the contiguous layout's
    worst-rank full block, halving causal ring wall on real hardware.
    """
    world = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    b, h, s_local, d = q.shape
    if s_local % 2:
        raise ValueError("zigzag layout needs an even local sequence")
    half = s_local // 2
    qf = q.astype(jnp.float32)
    q_lo, q_hi = qf[:, :, :half], qf[:, :, half:]
    perm = [(i, (i + 1) % world) for i in range(world)]

    skip = _skipped_block(b, h, half, d)

    def _drop(src, pair):
        if dropout_p == 0.0:
            return {}
        return dict(
            dropout_p=dropout_p,
            dropout_rng=jax.random.fold_in(
                dropout_rng, (rank * world + src) * 4 + pair
            ),
        )

    @jax.checkpoint
    def hop(q_lo, q_hi, kv, src):
        k_lo, v_lo, k_hi, v_hi, b_lo, b_hi = kv
        blo = {} if b_lo is None else dict(bias=b_lo)
        bhi = {} if b_hi is None else dict(bias=b_hi)
        # lo (global chunk rank) vs lo' (global chunk src)
        lo = jax.lax.switch(
            jnp.where(src == rank, 0, jnp.where(src < rank, 1, 2)),
            [
                lambda _: _block_attend(
                    q_lo, k_lo, v_lo, scale, causal=True,
                    **blo, **_drop(src, 0)
                ),
                lambda _: _block_attend(
                    q_lo, k_lo, v_lo, scale, **blo, **_drop(src, 0)
                ),
                lambda _: skip,
            ],
            None,
        )
        # hi (chunk 2cp−1−rank) vs lo' (chunk src < cp): always past
        hi_lo = _block_attend(
            q_hi, k_lo, v_lo, scale, **blo, **_drop(src, 1)
        )
        # hi vs hi' (chunk 2cp−1−src): past iff src > rank
        hi_hi = jax.lax.switch(
            jnp.where(src == rank, 0, jnp.where(src > rank, 1, 2)),
            [
                lambda _: _block_attend(
                    q_hi, k_hi, v_hi, scale, causal=True,
                    **bhi, **_drop(src, 2)
                ),
                lambda _: _block_attend(
                    q_hi, k_hi, v_hi, scale, **bhi, **_drop(src, 2)
                ),
                lambda _: skip,
            ],
            None,
        )
        return lo, hi_lo, hi_hi

    b_lo = b_hi = None
    if bias is not None:
        # the (B, 1, 1, S_local) key-padding mask splits into the two
        # chunk halves and rotates with them; a broadcast (..., 1) mask
        # applies to both halves as-is
        if bias.shape[-1] == 1:
            b_lo = b_hi = bias
        else:
            b_lo, b_hi = bias[..., :half], bias[..., half:]
    kv0 = (
        k[:, :, :half], v[:, :, :half],
        k[:, :, half:], v[:, :, half:],
        b_lo, b_hi,
    )
    lo0, hi_lo0, hi_hi0 = hop(q_lo, q_hi, kv0, rank)
    ones = jnp.ones((b, h, half), jnp.float32)
    c_lo = (lo0[0], lo0[1], ones)
    c_hi = _merge_block((hi_lo0[0], hi_lo0[1], ones), hi_hi0)

    def body(state, step):
        kv, c_lo, c_hi = state
        kv = jax.tree_util.tree_map(
            lambda x: jax.lax.ppermute(x, axis_name, perm), kv
        )
        src = (rank - step) % world
        lo, hi_lo, hi_hi = hop(q_lo, q_hi, kv, src)
        c_lo = _merge_block(c_lo, lo)
        c_hi = _merge_block(_merge_block(c_hi, hi_lo), hi_hi)
        return (kv, c_lo, c_hi), None

    if world > 1:
        (_, c_lo, c_hi), _ = jax.lax.scan(
            body, (kv0, c_lo, c_hi), jnp.arange(1, world)
        )
    return jnp.concatenate([c_lo[0], c_hi[0]], axis=2).astype(q.dtype)


def ulysses_attention(
    q,
    k,
    v,
    bias=None,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    dropout_p: float = 0.0,
    dropout_rng=None,
    axis_name: str = _CP,
):
    """All-to-all (Ulysses) sequence parallelism.

    q, k, v: ``(B, H, S_local, D)`` with the FULL head count; requires
    ``H % axis_size == 0``.  all-to-all → ``(B, H/cp, S, D)`` → ordinary
    flash attention with H/cp local heads → all-to-all back to
    ``(B, H, S_local, D)``.

    ``bias``: only a head-independent key-padding bias of local shape
    ``(B, 1, 1, S_local)`` is accepted (it is all-gathered along the
    sequence to match the gathered scores); other shapes would need both
    score dims reassembled and are rejected — precompute a global bias
    and fold it into the model instead.

    ``dropout_rng`` is folded with the cp rank so each rank's H/cp head
    group draws an independent mask (statistically identical to unsharded
    dropout, not bit-identical).
    """
    from apex_tpu.ops.attention import flash_attention

    world = jax.lax.axis_size(axis_name)
    h = q.shape[1]
    if h % world:
        raise ValueError(
            f"ulysses_attention needs num_heads ({h}) divisible by the "
            f"axis size ({world})"
        )
    if bias is not None:
        if bias.ndim < 4:
            bias = bias.reshape((1,) * (4 - bias.ndim) + bias.shape)
        if bias.shape[1] != 1 or bias.shape[2] != 1:
            raise ValueError(
                "ulysses_attention only redistributes a key-padding bias "
                f"of shape (B, 1, 1, S_local); got {bias.shape}"
            )
        bias = jax.lax.all_gather(bias, axis_name, axis=3, tiled=True)
    if dropout_rng is not None:
        dropout_rng = jax.random.fold_in(
            dropout_rng, jax.lax.axis_index(axis_name)
        )

    def scatter_heads(x):
        # (B, H, S_local, D) -> (B, H/cp, S, D): split heads, concat seq
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    def gather_heads(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    o = flash_attention(
        scatter_heads(q), scatter_heads(k), scatter_heads(v), bias,
        causal=causal, scale=scale, dropout_p=dropout_p,
        dropout_rng=dropout_rng,
    )
    return gather_heads(o)
