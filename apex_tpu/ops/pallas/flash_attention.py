"""Pallas TPU flash attention — forward + backward kernels.

TPU-native equivalent of the reference's fused-attention natives:
``apex/contrib/csrc/multihead_attn/*.cu`` (strided-batched-GEMM + warp
softmax + dropout pipeline) and ``apex/contrib/csrc/fmha/`` (fixed-seqlen
flash kernels, seq ≤ 512).  Where those hand-schedule cuBLAS GEMMs and
softmax kernels per architecture, the TPU version is a single online-softmax
(flash) kernel family tiled for the MXU: never materializes the (Sq, Sk)
score matrix in HBM, carries running (max, sum, acc) in VMEM scratch across
the key-block grid dimension, and saves only the logsumexp for backward.

Unlike the reference's fmha (seq ∈ {128,256,384,512} hardcoded per kernel),
block shapes here are chosen at trace time and any Sq/Sk multiple of the
block size works; long-context is handled above this kernel by ring/context
parallelism (apex_tpu.transformer.context_parallel).

Layout: q (BH, Sq, D), k/v (BH, Sk, D) with batch*heads pre-flattened and D
sublane-aligned by the caller (apex_tpu.ops.attention): D <= 128 is only
padded to a multiple of 8 and the tile covers the whole head dim (D = 64
stays 64 — half the FLOPs/HBM of lane-padding it); D > 128 pads to a lane
multiple.
Bias, when present, is (G, RS, Sk) with G ∈ {1, B, BH} (BH % G == 0; the
index map folds the flattened batch-head index as b // (BH/G)) and
RS ∈ {1, Sq} — RS = 1 is the key-padding case, kept as a single row per
batch so the (Sq, Sk) mask matrix is never materialized in HBM.  Additive,
applied after scaling, same semantics as the reference's additive mask path
(``apex/contrib/multihead_attn`` ``mask_additive`` mode).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._dispatch import pallas_interpret
from apex_tpu.ops.pallas import introspect, tune_cache

# Large negative finite (not -inf: keeps exp() well-defined in f32 after the
# running-max subtraction, same trick as the reference's softmax kernels).
MASK_VALUE = -1e9
# Strictly below MASK_VALUE: what padded-to-tile key columns carry.  A row
# whose REAL keys are all masked at MASK_VALUE then still softmaxes to a
# uniform average over the real keys only — exp(PAD_VALUE - MASK_VALUE)
# underflows to exactly 0 — matching the unpadded reference.  The kernels'
# defense clamp floors at PAD_VALUE (not MASK_VALUE) so the distinction
# survives into the score matrix.
PAD_VALUE = -1.5e9

_LANES = 128


def _dot_precision(dtype):
    """MXU precision for the in-kernel f32 dots.

    Inputs are cast to f32 before every dot; with DEFAULT precision the MXU
    does single-pass bf16 multiplies — right for bf16 inputs (their
    information fits), but for f32 inputs it loses ~8 mantissa bits vs the
    XLA reference path (which decomposes f32 dots into multi-pass form).
    HIGHEST matches the reference at f32; bf16 keeps the fast path.
    """
    return (
        jax.lax.Precision.HIGHEST
        if dtype == jnp.float32
        else jax.lax.Precision.DEFAULT
    )



# Per-shape tuned tile sizes — ≙ the reference's per-shape kernel-traits
# tables (fmha's fixed-seqlen kernels / multihead_attn launch configs),
# and the same pattern as layer_norm._TUNED_BLOCK_ROWS.  A SOURCE-level
# table: commit tools/attn_tune.py winners here (the entry points are
# jitted with static tile args, so runtime mutation would not retrace
# already-compiled shapes); absent shapes fall back to the _auto_block
# heuristic.  Keys: (sq, d, causal) -> {"fwd": (bq, bk),
# "bwd": (bq, bk), "bwd_dq": (bq, bk)}; the "bwd_dq" pair feeds
# flash_bwd's independent dq-call tiles.
_TUNED_TILES: dict = {
    # tools/attn_tune.py on v5e, 2026-08-01 (figures transcribed in
    # docs/flash-roofline.md).  Long-context bench shape: fwd 30.1 -> 43.3
    # TFLOP/s, fwd+bwd 45.7 -> 60.2 at the shared (1024, 1024) winner;
    # bwd-only phase-2 confirmed the dq call's optimum coincides
    # (49.9 TFLOP/s).  The heuristic's (512, 512) loses ~25% at long
    # sequence: tile-grid fixed costs amortize all the way up to
    # 1024-wide blocks on this kernel.
    (16384, 128, True): {
        "fwd": (1024, 1024),
        "bwd": (1024, 1024),
        "bwd_dq": (1024, 1024),
    },
    # BASELINE #4 mha microbench shape: fwd 6.0 -> 6.9 TFLOP/s.  The
    # bwd pair is the best of the 9 bwd-only cells that sweep measured
    # (9.2 TFLOP/s at (256, 1024) vs 3.8 at (128, 128)); the
    # (512|1024, *) rows are unmeasured.
    (2048, 64, True): {
        "fwd": (1024, 1024),
        "bwd": (256, 1024),
    },
}


def _tuned_tile(mode, sq, sk, d, causal, dtype=None):
    """(bq, bk) from the tuning cache or the source table, or
    (None, None) → heuristic.

    Lookup order (docs/flash-roofline.md "tuning flow"): the on-disk
    ``APEX_TPU_TUNE_CACHE`` artifact (``tune_cache.flash_tiles`` —
    winners ``tools/attn_tune.py --cache-out`` persisted, keyed by
    (shape, dtype, causal, backend)) wins over the committed
    ``_TUNED_TILES`` source table.  Either way the table is keyed on
    the q-side shape; a tile is only returned if it divides the ACTUAL
    axis it will tile (the kernels have no partial-tile masking), so a
    self-attention-tuned entry can never hand a non-dividing bk to a
    cross-attention call's sk."""
    pair = tune_cache.flash_tiles(mode, sq, d, causal, dtype)
    if pair is None:
        pair = _TUNED_TILES.get((sq, d, causal), {}).get(mode)
    tq, tk = pair or (None, None)
    if tq and sq % tq:
        tq = None
    if tk and sk % tk:
        tk = None
    return tq, tk


def _resolve_tiles(mode, sq, sk, d, causal, dtype, block_q, block_k):
    """The ONE dispatch-time tile resolution — explicit override →
    tuning cache / ``_TUNED_TILES`` → ``_auto_block`` heuristic —
    shared by :func:`flash_fwd`, :func:`flash_bwd`, and the analyzer's
    :func:`kernel_specs` export, so analysis can never resolve a
    different tile than dispatch."""
    tq, tk = _tuned_tile(mode, sq, sk, d, causal, dtype)
    bq = min(block_q or tq, sq) if (block_q or tq) else _auto_block(sq, d)
    bk = min(block_k or tk, sk) if (block_k or tk) else _auto_block(sk, d)
    return bq, bk


def _resolve_dq_tiles(
    sq, sk, d, causal, dtype, block_q, block_k, bq, bk,
    block_q_dq, block_k_dq,
):
    """The dq call's independent tiles (see :func:`flash_bwd`): an
    explicit shared-tile choice suppresses the bwd_dq table entry so
    tuner phase-1 sweeps measure what they pin."""
    if block_q or block_k:
        tq_dq = tk_dq = None
    else:
        tq_dq, tk_dq = _tuned_tile("bwd_dq", sq, sk, d, causal, dtype)
    return (
        min(block_q_dq or tq_dq or bq, sq),
        min(block_k_dq or tk_dq or bk, sk),
    )


def padded_head_dim(d):
    """Kernel-side head dim for a model-side ``d`` — the pure-int form
    of ``ops.attention._pad_head_dim``'s padding contract (D ≤ 128
    pads to the sublane quantum, wider pads to a lane multiple); the
    analyzer and tuner derive kernel specs through this so they can
    never disagree with the dispatcher's padding."""
    return d + ((-d) % 8 if d <= _LANES else (-d) % _LANES)


def _auto_block(seq, d):
    """Default tile size: large enough to amortize per-tile grid overhead.

    At (128, 128) tiles a 2048-seq 128-batched-head causal case is ~33k
    tiles whose fixed cost dominates (~2x slower than unfused XLA on v5e);
    (512, 512) cuts the tile count 16x and is still < ~4 MB VMEM of f32
    score/accumulator buffers for d <= 128.  Wider heads halve the tile to
    keep VMEM bounded.  The kernels have no partial-tile masking, so the
    tile must divide seq exactly — fall through to smaller powers of two.
    """
    cap = 512 if d <= 128 else 256
    for b in (512, 256, 128):
        if b <= cap and b <= seq and seq % b == 0:
            return b
    return seq  # seq < 128 (callers guarantee seq % min(128, seq) == 0)

def _bias_spec(bias_shape, bh, bq, bk, order):
    """BlockSpec for a (G, RS, Sk) bias (module docstring's layout).

    ``order`` is the grid layout: "ij" = (b, qblock, kblock) grids
    (forward, dq), "ji" = (b, kblock, qblock) (dk/dv).
    """
    g, rs, _ = bias_shape
    if bh % g:
        raise ValueError(f"bias batch group {g} must divide BH={bh}")
    div = bh // g
    rb = bq if rs != 1 else 1
    if order == "ji":
        return pl.BlockSpec(
            (1, rb, bk),
            lambda b, j, i, _d=div, _rb=rb: (b // _d, i if _rb != 1 else 0, j),
        )
    return pl.BlockSpec(
        (1, rb, bk),
        lambda b, i, j, _d=div, _rb=rb: (b // _d, i if _rb != 1 else 0, j),
    )


def _dropout_keep_block(seed, bh, i, j, bq, bk, dropout_p):
    """Deterministic keep-mask for tile (i, j) of batch-head ``bh``.

    ≙ the reference's fused philox dropout (multihead_attn ``philox.cuh``/
    ``dropout.cuh``): a counter-based PRNG keyed on (seed, bh, element
    coordinates), so the SAME mask regenerates in every backward kernel
    with zero state.  The hardware PRNG (pltpu.prng_*) has no interpret-
    mode lowering, so this is a pure-uint32 murmur3-finalizer hash over
    the element index — portable, vectorized on the VPU, and independent
    of grid iteration order.  Keep probability = 1 - dropout_p.
    """
    u32 = jnp.uint32
    rows = jax.lax.broadcasted_iota(jnp.uint32, (bq, bk), 0) + u32(i * bq)
    cols = jax.lax.broadcasted_iota(jnp.uint32, (bq, bk), 1) + u32(j * bk)
    key = (
        seed.astype(jnp.uint32)
        + bh.astype(jnp.uint32) * u32(0x9E37_79B9)
    )

    def fmix(h, mul):
        h = h ^ (h >> u32(16))
        h = h * mul
        h = h ^ (h >> u32(13))
        h = h * u32(0x27D4_EB2F)
        h = h ^ (h >> u32(16))
        return h + key
    # Keyed two-round hash of the (row, col) PAIR — mix the row first,
    # then fold the column in and mix again.  A single linear row*C+col
    # counter would alias once a seq dim exceeded the constant (correlated
    # dropout at long context); hashing the coordinates separately leaves
    # only accidental (birthday-level) collisions at any Sq/Sk.
    h = fmix(rows ^ key, u32(0x85EB_CA6B))
    h = fmix(h ^ cols, u32(0xC2B2_AE35))
    threshold = u32(min(int(dropout_p * 2**32), 2**32 - 1))
    return h >= threshold


def _causal_mask_block(i, j, bq, bk, offset):
    # Bottom-right-aligned causal mask: query row r sees keys <= r + offset
    # where offset = Sk - Sq (matches jnp.tril(..., k=sk-sq) in the
    # reference composition; identical to the standard convention when
    # Sq == Sk).
    rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + i * bq
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + j * bk
    return rows + offset >= cols


# ---------------------------------------------------------------------------
# Call plans — the pallas_call arguments as pure functions of static
# parameters.  flash_fwd/flash_bwd dispatch through these, and
# kernel_specs() exports the SAME plans to the static analyzer
# (apex_tpu.analysis.kernels), so the analyzed specs can never drift
# from the dispatched ones.
# ---------------------------------------------------------------------------


def _fwd_plan(bh, sq, sk, d, dtype, *, bq, bk, bias_shape=None,
              has_seed=False):
    nq, nk = pl.cdiv(sq, bq), pl.cdiv(sk, bk)
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
    ]
    in_names = ["q", "k", "v"]
    in_shapes = [(bh, sq, d), (bh, sk, d), (bh, sk, d)]
    in_dtypes = [dtype, dtype, dtype]
    if bias_shape is not None:
        in_specs.append(_bias_spec(bias_shape, bh, bq, bk, "ij"))
        in_names.append("bias")
        in_shapes.append(tuple(bias_shape))
        in_dtypes.append(jnp.float32)
    if has_seed:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        in_names.append("seed")
        in_shapes.append((1,))
        in_dtypes.append(jnp.int32)
    return dict(
        grid=(bh, nq, nk),
        in_specs=in_specs,
        in_names=in_names,
        in_shapes=in_shapes,
        in_dtypes=in_dtypes,
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_names=["o", "lse"],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), dtype),
            jax.ShapeDtypeStruct((bh, sq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )


def _dkdv_plan(bh, sq, sk, d, dtypes, *, bq, bk, bias_shape=None,
               has_seed=False):
    """Grid (BH, nk, nq) — q innermost; dtypes = (q, k, v) dtypes."""
    qd, kd, vd = dtypes
    nq, nk = pl.cdiv(sq, bq), pl.cdiv(sk, bk)
    q_spec_i = pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0))
    k_spec_j = pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0))
    row_spec_i = pl.BlockSpec((1, bq, _LANES), lambda b, j, i: (b, i, 0))
    in_specs = [
        q_spec_i, k_spec_j, k_spec_j, q_spec_i, row_spec_i, row_spec_i,
    ]
    in_names = ["q", "k", "v", "do", "lse", "delta"]
    in_shapes = [
        (bh, sq, d), (bh, sk, d), (bh, sk, d), (bh, sq, d),
        (bh, sq, _LANES), (bh, sq, _LANES),
    ]
    in_dtypes = [qd, kd, vd, qd, jnp.float32, jnp.float32]
    if bias_shape is not None:
        in_specs.append(_bias_spec(bias_shape, bh, bq, bk, "ji"))
        in_names.append("bias")
        in_shapes.append(tuple(bias_shape))
        in_dtypes.append(jnp.float32)
    if has_seed:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        in_names.append("seed")
        in_shapes.append((1,))
        in_dtypes.append(jnp.int32)
    return dict(
        grid=(bh, nk, nq),
        in_specs=in_specs,
        in_names=in_names,
        in_shapes=in_shapes,
        in_dtypes=in_dtypes,
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
        ],
        out_names=["dk", "dv"],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), kd),
            jax.ShapeDtypeStruct((bh, sk, d), vd),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )


def _dq_plan(bh, sq, sk, d, dtypes, *, bq, bk, bias_shape=None,
             has_seed=False):
    """Grid (BH, nq, nk) — k innermost; dtypes = (q, k, v) dtypes."""
    qd, kd, vd = dtypes
    nq, nk = pl.cdiv(sq, bq), pl.cdiv(sk, bk)
    q_spec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
    k_spec = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0))
    row_spec = pl.BlockSpec((1, bq, _LANES), lambda b, i, j: (b, i, 0))
    in_specs = [q_spec, k_spec, k_spec, q_spec, row_spec, row_spec]
    in_names = ["q", "k", "v", "do", "lse", "delta"]
    in_shapes = [
        (bh, sq, d), (bh, sk, d), (bh, sk, d), (bh, sq, d),
        (bh, sq, _LANES), (bh, sq, _LANES),
    ]
    in_dtypes = [qd, kd, vd, qd, jnp.float32, jnp.float32]
    if bias_shape is not None:
        in_specs.append(_bias_spec(bias_shape, bh, bq, bk, "ij"))
        in_names.append("bias")
        in_shapes.append(tuple(bias_shape))
        in_dtypes.append(jnp.float32)
    if has_seed:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        in_names.append("seed")
        in_shapes.append((1,))
        in_dtypes.append(jnp.int32)
    return dict(
        grid=(bh, nq, nk),
        in_specs=in_specs,
        in_names=in_names,
        in_shapes=in_shapes,
        in_dtypes=in_dtypes,
        out_specs=[pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))],
        out_names=["dq"],
        out_shape=[jax.ShapeDtypeStruct((bh, sq, d), qd)],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )


_plan_spec = introspect.from_plan


def kernel_specs(
    bh, sq, sk, d, *, dtype=jnp.bfloat16, causal=True, block_q=None,
    block_k=None, block_q_dq=None, block_k_dq=None, bias_shape=None,
    dropout=False, causal_offset=None, modes=("fwd", "dkdv", "dq"),
):
    """Export :class:`introspect.KernelSpec` records for a flash
    attention call — the static analyzer's view of exactly the
    pallas_calls :func:`flash_fwd` / :func:`flash_bwd` would dispatch
    at this configuration, without tracing or compiling anything.

    Tile sizes resolve exactly like dispatch does (explicit override →
    tuning cache → ``_TUNED_TILES`` → ``_auto_block``), so analyzing
    the DEFAULT config analyzes what the bench actually runs.  ``d``
    is the kernel-side head dim (callers pad via
    ``ops.attention._pad_head_dim``); ``bias_shape`` is the kernel's
    (G, RS, Sk) layout.  ``modes`` selects among "fwd", "dkdv", "dq".
    """
    dtype = jnp.dtype(dtype)
    offset = causal_offset if causal_offset is not None else sk - sq
    specs = []

    def causal_meta(q_axis, k_axis, bq, bk, include_fully_masked):
        if not causal:
            return None
        return {
            "q_axis": q_axis, "k_axis": k_axis, "bq": bq, "bk": bk,
            "offset": offset,
            "include_fully_masked": include_fully_masked,
        }

    common = dict(bias_shape=bias_shape, has_seed=dropout)
    if "fwd" in modes:
        bq, bk = _resolve_tiles(
            "fwd", sq, sk, d, causal, dtype, block_q, block_k
        )
        spec = _plan_spec(
            "flash_fwd",
            _fwd_plan(bh, sq, sk, d, dtype, bq=bq, bk=bk, **common),
            flops_per_cell=4.0 * bq * bk * d,
            # ONE (bq, bk) f32 score value at steady state: s is dead
            # once p = exp(s - m) is formed (elementwise, buffer
            # reusable), unlike the backward kernels where p must stay
            # live across the dp dot.  Matches the measured fact that
            # a (1024, 2048) fwd tile (8 MiB score) fits v5e
            # (docs/flash-roofline.md) — 2x here would wrongly prune
            # the ROADMAP's beyond-the-sweep-edge probe.
            intermediates=(((bq, bk), jnp.float32),),
            causal=causal_meta(1, 2, bq, bk, True),
        )
        spec.meta["matmul_dims"] = {"block_q": bq, "block_k": bk,
                                    "head_dim": d}
        specs.append(spec)
    if "dkdv" in modes or "dq" in modes:
        bq, bk = _resolve_tiles(
            "bwd", sq, sk, d, causal, dtype, block_q, block_k
        )
        bq_dq, bk_dq = _resolve_dq_tiles(
            sq, sk, d, causal, dtype, block_q, block_k, bq, bk,
            block_q_dq, block_k_dq,
        )
        dtypes = (dtype, dtype, dtype)
        if "dkdv" in modes:
            spec = _plan_spec(
                "flash_bwd_dkdv",
                _dkdv_plan(bh, sq, sk, d, dtypes, bq=bq, bk=bk, **common),
                # recompute s + (dv, dp, dk) dots = 4 MXU passes
                flops_per_cell=8.0 * bq * bk * d,
                # peak concurrent (bq, bk) f32 values is 2 (p stays
                # live across the dp dot; ds reuses dp's buffer) —
                # the measured (1024, 1024) v5e config must fit
                intermediates=(
                    ((bq, bk), jnp.float32), ((bq, bk), jnp.float32),
                ),
                causal=causal_meta(2, 1, bq, bk, True),
            )
            spec.meta["matmul_dims"] = {"block_q": bq, "block_k": bk,
                                        "head_dim": d}
            specs.append(spec)
        if "dq" in modes:
            spec = _plan_spec(
                "flash_bwd_dq",
                _dq_plan(
                    bh, sq, sk, d, dtypes, bq=bq_dq, bk=bk_dq, **common
                ),
                flops_per_cell=6.0 * bq_dq * bk_dq * d,
                intermediates=(
                    ((bq_dq, bk_dq), jnp.float32),
                    ((bq_dq, bk_dq), jnp.float32),
                ),
                causal=causal_meta(1, 2, bq_dq, bk_dq, False),
            )
            spec.meta["matmul_dims"] = {"block_q": bq_dq, "block_k": bk_dq,
                                        "head_dim": d}
            specs.append(spec)
    return specs


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _causal_block_live(i, j, bq, bk, offset, include_fully_masked):
    """Whether the (i, j) tile has any work under the causal mask.

    A tile is dead when every (row, col) in it violates the mask; skipping
    dead tiles halves the causal grid's compute (the reference's fmha
    kernels get the same effect from their triangular loop bounds).
    ``include_fully_masked`` additionally keeps tiles whose rows see NO key
    at all (Sq > Sk bottom-right alignment) — those rows still produce the
    uniform-average output / dv, so their tiles must run.
    """
    live = (i * bq + bq - 1 + offset) >= (j * bk)
    if include_fully_masked:
        live = live | ((i * bq + offset) < 0)
    return live


def _fwd_kernel(
    q_ref, k_ref, v_ref, bias_ref, seed_ref, o_ref, lse_ref,
    acc_ref, m_ref, l_ref,
    *, scale, causal, bq, bk, nk, offset, prec, dropout_p,
):
    bh = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = (
        _causal_block_live(i, j, bq, bk, offset, include_fully_masked=True)
        if causal
        else True
    )

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )
        s = s * scale
        if bias_ref is not None:
            # Defense-in-depth clamp (the public API pre-clamps): a -inf
            # bias would pin m_new at -inf and alpha = exp(-inf - -inf) =
            # NaN would poison the whole row.  Clamped, the finite-value
            # invariant below holds for direct flash_fwd callers too.  The
            # floor is PAD_VALUE (< MASK_VALUE) so padded key columns stay
            # strictly below masked real keys.  bias_ref[0] is (bq, bk) or
            # (1, bk) (key-padding row); broadcasting covers both.
            s = s + jnp.maximum(bias_ref[0].astype(jnp.float32), PAD_VALUE)
        if causal:
            s = jnp.where(
                _causal_mask_block(i, j, bq, bk, offset), s, MASK_VALUE
            )

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        # The softmax DENOMINATOR accumulates the full p (dropout acts on
        # the normalized probabilities, not the row sum); only the PV
        # contribution is masked + 1/(1-p)-rescaled — elementwise, so it
        # commutes with the final /l normalization.
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if dropout_p > 0.0:
            keep = _dropout_keep_block(
                seed_ref[0], bh, i, j, bq, bk, dropout_p
            )
            p_v = jnp.where(keep, p * (1.0 / (1.0 - dropout_p)), 0.0)
        else:
            p_v = p
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p_v, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nk - 1)
    def _finalize():
        # MASK_VALUE is finite, so even a fully-masked row has p = 1 at its
        # row max and l >= 1: no divide-by-zero, and such a row yields a
        # uniform average of V — identical to the jnp reference (softmax of
        # constant scores), not zeros.
        l = l_ref[:, :1]
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)[None]
        lse = m_ref[:, :1] + jnp.log(l)
        # lse carries a broadcast 128-lane dim — Mosaic requires the last
        # two block dims tile-aligned, so a (1, bq) row block is not
        # lowerable; (bq, 128) is (same layout as jax's reference TPU
        # flash attention).
        lse_ref[...] = jnp.broadcast_to(lse, (bq, _LANES))[None]


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "causal", "block_q", "block_k", "causal_offset",
        "dropout_p",
    ),
)
def flash_fwd(
    q, k, v, bias, *, scale, causal, block_q=None, block_k=None,
    causal_offset=None, dropout_p=0.0, dropout_seed=None,
):
    """Returns (o, lse).  q (BH,Sq,D), k/v (BH,Sk,D).

    lse is f32 (BH, Sq, 128) — the row logsumexp broadcast across a lane
    dim so its blocks are TPU-tileable; consumers read lane 0.

    ``causal_offset`` overrides the bottom-right alignment offset
    (default ``Sk - Sq``) — callers that pad Sq/Sk to tile multiples pass
    the UNPADDED ``sk - sq`` so valid rows keep their original mask.

    ``dropout_p`` > 0 fuses attention-probability dropout into the PV
    accumulation (≙ the reference's in-kernel philox dropout), keyed by
    the int32 scalar ``dropout_seed`` — the identical mask regenerates in
    every backward kernel from (seed, bh, element coords).
    """
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq, bk = _resolve_tiles(
        "fwd", sq, sk, d, causal, q.dtype, block_q, block_k
    )
    nk = pl.cdiv(sk, bk)
    offset = causal_offset if causal_offset is not None else sk - sq
    if dropout_p > 0.0 and dropout_seed is None:
        raise ValueError("dropout_p > 0 requires dropout_seed")

    plan = _fwd_plan(
        bh, sq, sk, d, q.dtype, bq=bq, bk=bk,
        bias_shape=None if bias is None else bias.shape,
        has_seed=dropout_p > 0.0,
    )
    args = [q, k, v]
    common = dict(
        scale=scale, causal=causal, bq=bq, bk=bk, nk=nk, offset=offset,
        prec=_dot_precision(q.dtype), dropout_p=dropout_p,
        has_bias=bias is not None, has_seed=dropout_p > 0.0,
    )
    if bias is not None:
        args.append(bias)
    # The seed operand exists ONLY on dropout runs, so the (on-chip
    # proven) no-dropout kernels keep their exact operand signature.
    if dropout_p > 0.0:
        args.append(jnp.asarray(dropout_seed, jnp.int32).reshape(1))
    kernel = functools.partial(_fwd_entry, **common)

    return pl.pallas_call(
        kernel,
        grid=plan["grid"],
        in_specs=plan["in_specs"],
        out_specs=plan["out_specs"],
        out_shape=plan["out_shape"],
        scratch_shapes=plan["scratch_shapes"],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=plan["dimension_semantics"],
        ),
        interpret=pallas_interpret(),
    )(*args)


def _fwd_entry(*refs, has_bias, has_seed, **kw):
    """Adapter: optional bias/seed operands -> fixed kernel signature."""
    i = 3
    bias_ref = refs[i] if has_bias else None
    i += int(has_bias)
    seed_ref = refs[i] if has_seed else None
    i += int(has_seed)
    o_ref, lse_ref, acc, m, l = refs[i:]
    _fwd_kernel(
        refs[0], refs[1], refs[2], bias_ref, seed_ref, o_ref, lse_ref,
        acc, m, l, **kw
    )


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _recompute_p(
    q, k, bias_blk, lse, i, j, bq, bk, scale, causal, offset, prec, sk_total
):
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=prec,
    ) * scale
    if bias_blk is not None:
        # Same -inf clamp as the forward kernel, so the recomputed p
        # matches it bit-for-bit.
        s = s + jnp.maximum(bias_blk, PAD_VALUE)
    mask = None
    if causal:
        mask = _causal_mask_block(i, j, bq, bk, offset)
        s = jnp.where(mask, s, MASK_VALUE)
    p = jnp.exp(s - lse)
    if causal:
        # FULLY-masked rows (Sq > Sk bottom-right-aligned causal: rows with
        # row + offset < 0 see no keys) need exact handling: their saved
        # lse is MASK_VALUE + log(Sk), which f32 rounds back to MASK_VALUE
        # (ulp(1e9) = 64), so exp(s - lse) would give 1 instead of the true
        # uniform 1/Sk and inflate dv by Sk x.  Substitute the closed form;
        # rows with >= 1 real key are untouched (their lse is O(1) and the
        # masked entries' exp underflow to exactly 0).  This matches the
        # jnp reference, whose softmax over an all-MASK_VALUE row is
        # exactly uniform and backprops that row's cotangent into dv.
        row_ids = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + i * bq
        fully_masked = (row_ids + offset) < 0
        p = jnp.where(fully_masked, 1.0 / sk_total, p)
    return p, mask


def _dkdv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref, seed_ref,
    dk_ref, dv_ref, dk_acc, dv_acc,
    *, scale, causal, bq, bk, nq, offset, prec, sk_total, dropout_p,
):
    bh = pl.program_id(0)
    i = pl.program_id(2)  # q-block index (inner loop)
    j = pl.program_id(1)  # k-block index

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # fully-masked q rows still contribute their uniform p to dv, so their
    # tiles stay live (include_fully_masked=True)
    live = (
        _causal_block_live(i, j, bq, bk, offset, include_fully_masked=True)
        if causal
        else True
    )

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        bias_blk = (
            None if bias_ref is None else bias_ref[0].astype(jnp.float32)
        )

        p, mask = _recompute_p(
            q, k, bias_blk, lse, i, j, bq, bk, scale, causal, offset, prec,
            sk_total,
        )
        # With fused dropout D = keep/(1-p): o = (D ⊙ p̃) V, so
        # dv = (D⊙p)ᵀ do and ds = p ⊙ (D⊙dp − delta) — delta already
        # carries the D factor through rowsum(do·o).  Mask regenerated
        # bit-identically from (seed, bh, coords).
        if dropout_p > 0.0:
            keep = _dropout_keep_block(
                seed_ref[0], bh, i, j, bq, bk, dropout_p
            )
            drop = jnp.where(keep, 1.0 / (1.0 - dropout_p), 0.0)
            p_v = p * drop
        else:
            drop = None
            p_v = p
        # dv += (D⊙p)^T @ do
        dv_acc[...] += jax.lax.dot_general(
            p_v, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )
        # dp = do @ v^T ; ds = p * (D⊙dp - delta)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )
        if drop is not None:
            dp = dp * drop
        ds = p * (dp - delta)
        if mask is not None:
            # the causal mask is a where() on s: no gradient flows through
            # the masked branch to q/k (dv, by contrast, takes the full p)
            ds = jnp.where(mask, ds, 0.0)
        # dk += ds^T @ q * scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        ) * scale

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)[None]
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)[None]


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref, seed_ref,
    dq_ref, dq_acc,
    *, scale, causal, bq, bk, nk, offset, prec, sk_total, dropout_p,
):
    bh = pl.program_id(0)
    i = pl.program_id(1)  # q-block index
    j = pl.program_id(2)  # k-block index (inner loop)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    # dq of a fully-masked row is exactly 0 (the mask's where() blocks the
    # gradient), so those tiles are dead here — no include_fully_masked
    live = (
        _causal_block_live(i, j, bq, bk, offset, include_fully_masked=False)
        if causal
        else True
    )

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        bias_blk = (
            None if bias_ref is None else bias_ref[0].astype(jnp.float32)
        )

        p, mask = _recompute_p(
            q, k, bias_blk, lse, i, j, bq, bk, scale, causal, offset, prec,
            sk_total,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )
        if dropout_p > 0.0:
            keep = _dropout_keep_block(
                seed_ref[0], bh, i, j, bq, bk, dropout_p
            )
            dp = dp * jnp.where(keep, 1.0 / (1.0 - dropout_p), 0.0)
        ds = p * (dp - delta)
        if mask is not None:
            ds = jnp.where(mask, ds, 0.0)
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        ) * scale

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)[None]


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "causal", "block_q", "block_k", "causal_offset",
        "dropout_p", "block_q_dq", "block_k_dq",
    ),
)
def flash_bwd(
    q, k, v, o, lse, do, bias, *, scale, causal, block_q=None, block_k=None,
    dlse=None, causal_offset=None, dropout_p=0.0, dropout_seed=None,
    block_q_dq=None, block_k_dq=None,
):
    """Returns (dq, dk, dv).  Recomputation backward: only lse was saved.

    ``dlse`` (f32, (BH, Sq)) is an optional cotangent for the forward's
    logsumexp output — used by consumers that differentiate through lse
    (ring attention's online-softmax merge).  The math folds it into the
    existing kernels: with p = exp(s - lse),

        ds_ij = p_ij * (dp_ij - delta_i) + p_ij * dlse_i
              = p_ij * (dp_ij - (delta_i - dlse_i)),

    so passing ``delta - dlse`` where the kernels expect delta yields the
    dq/dk that include the lse contribution; dv = pᵀ do is lse-independent.

    ``causal_offset`` serves padded-shape callers: the causal alignment
    uses the UNPADDED geometry (default: ``sk - sq``).  The fully-masked-
    row closed form keeps ``sk`` itself — callers never pad Sk in the
    Sq > Sk causal geometry where it applies (``_pallas_eligible``).

    ``block_q_dq``/``block_k_dq`` override the tile sizes of the **dq**
    pallas_call independently of the dkdv one (default: same as
    ``block_q``/``block_k``).  The two backward kernels iterate the
    grid transposed (dkdv: k-tiles outer, q inner; dq: q outer, k
    inner), so their optimal tiles can differ; ``tools/attn_tune.py
    --bwd-only`` sweeps them.  Safe under dropout: the keep-mask hash
    keys on absolute element coordinates, not tile geometry.
    """
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq, bk = _resolve_tiles(
        "bwd", sq, sk, d, causal, q.dtype, block_q, block_k
    )
    nq, nk = pl.cdiv(sq, bq), pl.cdiv(sk, bk)
    bq_dq, bk_dq = _resolve_dq_tiles(
        sq, sk, d, causal, q.dtype, block_q, block_k, bq, bk,
        block_q_dq, block_k_dq,
    )
    nq_dq, nk_dq = pl.cdiv(sq, bq_dq), pl.cdiv(sk, bk_dq)
    offset = causal_offset if causal_offset is not None else sk - sq
    sk_total = sk
    if dropout_p > 0.0 and dropout_seed is None:
        raise ValueError("dropout_p > 0 requires dropout_seed")
    has_seed = dropout_p > 0.0
    seed_args = (
        [jnp.asarray(dropout_seed, jnp.int32).reshape(1)] if has_seed else []
    )

    # delta_i = rowsum(do * o) — the softmax-jacobian correction term
    # (≙ the reference bwd kernels' row reduction before the ds GEMM).
    # Broadcast over a 128-lane dim like lse so blocks are tile-aligned.
    delta_rows = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )
    if dlse is not None:
        delta_rows = delta_rows - dlse.astype(jnp.float32)
    delta = jnp.broadcast_to(delta_rows[..., None], lse.shape)

    common = [q, k, v, do, lse, delta]
    dtypes = (q.dtype, k.dtype, v.dtype)
    bias_shape = None if bias is None else bias.shape
    kern_kw = dict(
        scale=scale, causal=causal, bq=bq, bk=bk,
        prec=_dot_precision(q.dtype), sk_total=sk_total,
        dropout_p=dropout_p, has_bias=bias is not None, has_seed=has_seed,
    )

    # --- dk/dv: grid (BH, nk, nq), q innermost ---
    plan = _dkdv_plan(
        bh, sq, sk, d, dtypes, bq=bq, bk=bk, bias_shape=bias_shape,
        has_seed=has_seed,
    )
    args = list(common)
    if bias is not None:
        args.append(bias)
    args += seed_args
    dkdv_kernel = functools.partial(
        _dkdv_entry, nq=nq, offset=offset, **kern_kw
    )
    dk, dv = pl.pallas_call(
        dkdv_kernel,
        grid=plan["grid"],
        in_specs=plan["in_specs"],
        out_specs=plan["out_specs"],
        out_shape=plan["out_shape"],
        scratch_shapes=plan["scratch_shapes"],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=plan["dimension_semantics"],
        ),
        interpret=pallas_interpret(),
    )(*args)

    # --- dq: grid (BH, nq, nk), k innermost; independent tile sizes ---
    kern_kw_dq = dict(kern_kw, bq=bq_dq, bk=bk_dq)
    plan = _dq_plan(
        bh, sq, sk, d, dtypes, bq=bq_dq, bk=bk_dq, bias_shape=bias_shape,
        has_seed=has_seed,
    )
    args = list(common)
    if bias is not None:
        args.append(bias)
    args += seed_args
    dq_kernel = functools.partial(
        _dq_entry, nk=nk_dq, offset=offset, **kern_kw_dq
    )
    dq = pl.pallas_call(
        dq_kernel,
        grid=plan["grid"],
        in_specs=plan["in_specs"],
        out_specs=plan["out_specs"][0],
        out_shape=plan["out_shape"][0],
        scratch_shapes=plan["scratch_shapes"],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=plan["dimension_semantics"],
        ),
        interpret=pallas_interpret(),
    )(*args)
    return dq, dk, dv


def _dkdv_entry(*refs, has_bias, has_seed, **kw):
    i = 6
    bias_ref = refs[i] if has_bias else None
    i += int(has_bias)
    seed_ref = refs[i] if has_seed else None
    i += int(has_seed)
    dk, dv, dka, dva = refs[i:]
    _dkdv_kernel(
        *refs[:6], bias_ref, seed_ref, dk, dv, dka, dva, **kw
    )


def _dq_entry(*refs, has_bias, has_seed, **kw):
    i = 6
    bias_ref = refs[i] if has_bias else None
    i += int(has_bias)
    seed_ref = refs[i] if has_seed else None
    i += int(has_seed)
    dq, dqa = refs[i:]
    _dq_kernel(*refs[:6], bias_ref, seed_ref, dq, dqa, **kw)


# ---------------------------------------------------------------------------
# Bias gradient (trainable additive bias, e.g. relative-position biases)
# ---------------------------------------------------------------------------


def _dbias_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref, seed_ref,
    dbias_ref, acc_ref,
    *, scale, causal, bq, bk, offset, prec, sk_total, inner_total, rs1, div,
    dropout_p,
):
    j = pl.program_id(2)
    t = pl.program_id(3)
    # rs1 folds (q-block, group-member) into the inner grid dim; the full
    # per-row case keeps the q-block as its own (parallel) grid dim.
    i = (t // div) if rs1 else pl.program_id(1)
    # the flattened batch-head index this step works on (dropout seeding
    # must match the fwd/dq/dkdv kernels, which key on bh)
    bh_idx = pl.program_id(0) * div + (t % div if rs1 else t)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # dbias = ds, and ds is 0 wherever the causal where() masks — including
    # every entry of a fully-masked row — so dead tiles stay dead here.
    live = (
        _causal_block_live(i, j, bq, bk, offset, include_fully_masked=False)
        if causal
        else True
    )

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        bias_blk = bias_ref[0].astype(jnp.float32)

        p, mask = _recompute_p(
            q, k, bias_blk, lse, i, j, bq, bk, scale, causal, offset, prec,
            sk_total,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )
        if dropout_p > 0.0:
            keep = _dropout_keep_block(
                seed_ref[0], bh_idx, i, j, bq, bk, dropout_p
            )
            dp = dp * jnp.where(keep, 1.0 / (1.0 - dropout_p), 0.0)
        ds = p * (dp - delta)
        if mask is not None:
            ds = jnp.where(mask, ds, 0.0)
        if rs1:
            # key-padding layout: dbias row g is the sum of ds over ALL q
            # rows of every group member — keep a broadcast row accumulator
            acc_ref[...] += jnp.broadcast_to(
                jnp.sum(ds, axis=0, keepdims=True), acc_ref.shape
            )
        else:
            acc_ref[...] += ds

    @pl.when(t == inner_total - 1)
    def _finalize():
        if rs1:
            dbias_ref[...] = acc_ref[:1].astype(dbias_ref.dtype)[None]
        else:
            dbias_ref[...] = acc_ref[...].astype(dbias_ref.dtype)[None]


def _dbias_entry(*refs, has_seed, **kw):
    i = 7
    seed_ref = refs[i] if has_seed else None
    i += int(has_seed)
    dbias_ref, acc_ref = refs[i:]
    _dbias_kernel(*refs[:7], seed_ref, dbias_ref, acc_ref, **kw)


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "causal", "block_q", "block_k", "causal_offset",
        "dropout_p",
    ),
)
def flash_dbias(
    q, k, v, o, lse, do, bias, *, scale, causal, block_q=None, block_k=None,
    causal_offset=None, dropout_p=0.0, dropout_seed=None,
):
    """Gradient of the additive bias: dbias (same (G, RS, Sk) layout).

    ≙ the reference's trainable-bias fused MHA backward (SURVEY §2.6
    multihead_attn :: self_attn_bias additive-bias variants) — there a
    strided-batched GEMM epilogue accumulates ds into dbias; here a third
    recompute pass reduces ds over the bias's broadcast group:

        dbias[g, r, c] = Σ_{m ∈ group g} Σ_{rows folded by RS} ds[m·.., r, c]

    with ds = p · (dp − delta), exactly the dk/dv kernels' recomputation.
    The group reduction (BH/G members, and all Sq rows when RS = 1) runs
    in the innermost "arbitrary" grid dim accumulating in VMEM scratch, so
    nothing larger than the bias itself ever hits HBM.
    """
    bh, sq, d = q.shape
    sk = k.shape[1]
    g, rs, _ = bias.shape
    if bh % g:
        raise ValueError(f"bias batch group {g} must divide BH={bh}")
    div = bh // g
    bq = min(block_q, sq) if block_q else _auto_block(sq, d)
    bk = min(block_k, sk) if block_k else _auto_block(sk, d)
    nq, nk = pl.cdiv(sq, bq), pl.cdiv(sk, bk)
    offset = causal_offset if causal_offset is not None else sk - sq
    rs1 = rs == 1
    if dropout_p > 0.0 and dropout_seed is None:
        raise ValueError("dropout_p > 0 requires dropout_seed")
    has_seed = dropout_p > 0.0

    delta_rows = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )
    delta = jnp.broadcast_to(delta_rows[..., None], lse.shape)

    if rs1:
        grid = (g, 1, nk, nq * div)
        inner_total = nq * div

        def bh_idx(b, _, j, t):
            return (b * div + t % div, t // div, 0)

        def row_idx(b, _, j, t):
            return (b * div + t % div, t // div, 0)

        bias_spec = pl.BlockSpec((1, 1, bk), lambda b, _, j, t: (b, 0, j))
        out_spec = pl.BlockSpec((1, 1, bk), lambda b, _, j, t: (b, 0, j))
        out_shape = jax.ShapeDtypeStruct((g, 1, sk), bias.dtype)
        acc_shape = pltpu.VMEM((8, bk), jnp.float32)
    else:
        grid = (g, nq, nk, div)
        inner_total = div

        def bh_idx(b, i, j, t):
            return (b * div + t, i, 0)

        def row_idx(b, i, j, t):
            return (b * div + t, i, 0)

        bias_spec = pl.BlockSpec((1, bq, bk), lambda b, i, j, t: (b, i, j))
        out_spec = pl.BlockSpec((1, bq, bk), lambda b, i, j, t: (b, i, j))
        out_shape = jax.ShapeDtypeStruct((g, sq, sk), bias.dtype)
        acc_shape = pltpu.VMEM((bq, bk), jnp.float32)

    def k_idx(b, i, j, t):
        return ((b * div + (t % div if rs1 else t)), j, 0)

    kernel = functools.partial(
        _dbias_entry, scale=scale, causal=causal, bq=bq, bk=bk,
        offset=offset, prec=_dot_precision(q.dtype), sk_total=sk,
        inner_total=inner_total, rs1=rs1, div=div, dropout_p=dropout_p,
        has_seed=has_seed,
    )
    in_specs = [
        pl.BlockSpec((1, bq, d), bh_idx),
        pl.BlockSpec((1, bk, d), k_idx),
        pl.BlockSpec((1, bk, d), k_idx),
        pl.BlockSpec((1, bq, d), bh_idx),
        pl.BlockSpec((1, bq, _LANES), row_idx),
        pl.BlockSpec((1, bq, _LANES), row_idx),
        bias_spec,
    ]
    args = [q, k, v, do, lse, delta, bias]
    if has_seed:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(jnp.asarray(dropout_seed, jnp.int32).reshape(1))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[acc_shape],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary"
            ),
        ),
        interpret=pallas_interpret(),
    )(*args)
