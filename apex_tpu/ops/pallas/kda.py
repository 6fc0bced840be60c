"""Pallas TPU kernels for Kimi Delta Attention (KDA) — the two places its
recurrent state is carried through.

KDA's state is one ``(d_k, d_v)`` f32 matrix per head and sequence,

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T,   o_t = S_t^T q_t

with one decay ``a_t`` per key channel.  Both kernels hold the state
TRANSPOSED, ``(d_v, d_k)`` — key channels along the lanes — so that the
decay is a lane-wise row scale and every contraction is a matmul form the
MXU takes without a transpose (``q @ S^T``: NT; ``u^T k``: TN).  The
state is the model's memory and stays f32: the prefill kernel multiplies
at ``HIGHEST``, the decode kernel with operands split into exact bf16
parts (a bf16 pass alone would round the state's every read).

- :func:`kda_step_fwd` — decode: one token per sequence against the
  per-slot state slab ``(L, B, H, d_v, d_k)``, updated IN PLACE
  (``input_output_aliases``): a step reads and writes each live state once,
  which is the kernel's roofline.
- :func:`kda_chunk_fwd` — prefill: the inter-chunk recurrence of the
  chunked (WY) form.  Everything that does not depend on the incoming
  state (the decayed Gram matrices, the triangular solve) is batched XLA
  in :mod:`apex_tpu.ops.kda`; this kernel walks the chunks with the state
  resident in VMEM.

The jnp forms of the same arithmetic, and the dispatch, live in
:mod:`apex_tpu.ops.kda`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._dispatch import pallas_interpret

__all__ = ["kda_step_fwd", "kda_chunk_fwd"]

_HI = jax.lax.Precision.HIGHEST
#: rows a vector is broadcast to before it meets the MXU (one f32 tile)
_ROWS = 8


def _nt(a, b):
    """``a (m, k) . b (n, k)^T -> (m, n)``"""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HI,
    )


def _tn(a, b):
    """``a (k, m)^T . b (k, n) -> (m, n)``"""
    return jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HI,
    )


def _heads_per_step(h: int) -> int:
    for hb in (8, 4, 2):
        if h % hb == 0:
            return hb
    return 1


# ---------------------------------------------------------------------------
# decode: one token against the slab
# ---------------------------------------------------------------------------


def _bf16_parts(x):
    """``x`` f32 as ``hi + lo``, both exactly bf16 (16 mantissa bits kept),
    returned in f32."""
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _dot(a, b, dims):
    """One bf16 MXU pass, f32 accumulation."""
    return jax.lax.dot_general(
        a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), (dims, ((), ())),
        preferred_element_type=jnp.float32,
    )


#: rows of the small operand a head's vectors are laid into (one packed
#: bf16 tile)
_VEC_ROWS = 16


def _step_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, o_ref, so_ref,
                 *, hb):
    """Per head, three single-pass bf16 matmuls with the operands split into
    exact bf16 parts (``x = hi + lo``), which keeps ~16 mantissa bits where
    a ``HIGHEST`` f32 matmul spends six passes on 24: (1) ``[k; q] S'^T``
    against the decayed state's hi part, (2) against its lo part, (3) the
    rank-one update ``u (x) k`` with its four hi/lo products laid along the
    contraction.  ``o = q S_new = q S' + (q.k) u`` reuses (1)-(2)."""
    row = jax.lax.broadcasted_iota(jnp.int32, (_VEC_ROWS, 1), 0)
    outs = []
    for i in range(hb):
        vec = lambda ref: ref[0, i:i + 1, :]  # noqa: E731  (1, d)
        k, q = vec(k_ref), vec(q_ref)
        st = s_ref[0, 0, i] * jnp.exp(vec(g_ref))           # Diag(a) S
        st_hi, st_lo = _bf16_parts(st)
        # rows 0, 1: the hi parts of k, q; rows 2, 3: their lo parts
        kq = jnp.where(row % 2 == 0, k, q)
        kq_hi, kq_lo = _bf16_parts(kq)
        kq = jnp.where(row < 2, kq_hi, jnp.where(row < 4, kq_lo, 0.0))
        nt = ((1,), (1,))
        prod = _dot(kq, st_hi, nt) + _dot(kq, st_lo, nt)    # (16, d_v)
        ks, qs = prod[0:1] + prod[2:3], prod[1:2] + prod[3:4]
        u = vec(b_ref) * (vec(v_ref) - ks)                  # (1, d_v)
        u_hi, u_lo = _bf16_parts(u)
        k_hi, k_lo = _bf16_parts(k)
        # u (x) k = (u_hi + u_lo) (x) (k_hi + k_lo), one product a row
        us = jnp.where(row < 2, u_hi, jnp.where(row < 4, u_lo, 0.0))
        ks4 = jnp.where(row >= 4, 0.0, jnp.where(row % 2 == 0, k_hi, k_lo))
        st = st + _dot(us, ks4, ((0,), (0,)))
        so_ref[0, 0, i] = st
        outs.append(qs + jnp.sum(q * k, axis=-1, keepdims=True) * u)
    o_ref[0] = jnp.concatenate(outs, axis=0)


@functools.partial(jax.jit, static_argnames=("layer",))
def kda_step_fwd(state, q, k, v, g, beta, *, layer: int):
    """One KDA token per sequence against layer ``layer`` of ``state``
    ``(L, B, H, d_v, d_k)`` f32, in place.  ``q, k, v, g`` ``(B, H, d)``
    f32 (``g`` the log-decay, <= 0), ``beta`` ``(B, H, d)`` f32 (each
    head's value across its lanes).  A row with ``beta = 0, g = 0`` leaves
    its state as it was.  Returns ``(o (B, H, d_v), state)``."""
    _, b, h, dv, dk = state.shape
    hb = _heads_per_step(h)
    vec = pl.BlockSpec((1, hb, dk), lambda i, j: (i, j, 0))
    slab = pl.BlockSpec(
        (1, 1, hb, dv, dk), lambda i, j: (layer, i, j, 0, 0)
    )
    o, state = pl.pallas_call(
        functools.partial(_step_kernel, hb=hb),
        grid=(b, h // hb),
        in_specs=[vec] * 5 + [slab],
        out_specs=[pl.BlockSpec((1, hb, dv), lambda i, j: (i, j, 0)), slab],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, dv), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=pallas_interpret(),
        name="kda_step_fwd",
    )(q, k, v, g, beta, state)
    return o, state


# ---------------------------------------------------------------------------
# prefill: the inter-chunk recurrence
# ---------------------------------------------------------------------------


def _chunk_kernel(w_ref, y_ref, q_ref, kh_ref, bm_ref, gam_ref,
                  o_ref, st_ref, *, hb):
    @pl.when(pl.program_id(1) == 0)
    def _zero():
        st_ref[...] = jnp.zeros_like(st_ref)

    for i in range(hb):
        st = st_ref[i]                                     # (d_v, d_k)
        u = w_ref[i, 0] - _nt(y_ref[i, 0], st)             # (C, d_v)
        o_ref[i, 0] = _nt(q_ref[i, 0], st) + jnp.dot(
            bm_ref[i, 0], u, preferred_element_type=jnp.float32,
            precision=_HI,
        )
        st_ref[i] = st * gam_ref[i, 0] + _tn(u, kh_ref[i, 0])


@jax.jit
def kda_chunk_fwd(w, y, q, kh, bm, gam):
    """Walk the chunks of one sequence from a zero state.  Per head and
    chunk (``c`` rows): ``w (c, d_v)`` and ``y (c, d_k)`` the solved
    pseudo-values and their state coupling, ``q (c, d_k)`` the queries
    decayed from the chunk's start, ``kh (c, d_k)`` the keys decayed to the
    chunk's end, ``bm (c, c)`` the causal decayed query-key Gram matrix,
    ``gam (1, d_k)`` the chunk's whole decay — all ``(H, NC, ...)`` f32
    (:func:`apex_tpu.ops.kda._intra_chunk`).  Returns ``(o (H, NC, c,
    d_v), state^T (H, d_v, d_k))``."""
    h, nc, c, dv = w.shape
    dk = y.shape[-1]
    hb = _heads_per_step(h)

    def rows(width):
        return pl.BlockSpec((hb, 1, c, width), lambda i, j: (i, j, 0, 0))

    return pl.pallas_call(
        functools.partial(_chunk_kernel, hb=hb),
        grid=(h // hb, nc),
        in_specs=[
            rows(dv), rows(dk), rows(dk), rows(dk), rows(c),
            pl.BlockSpec((hb, 1, 1, dk), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=[
            rows(dv),
            pl.BlockSpec((hb, dv, dk), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((h, nc, c, dv), jnp.float32),
            jax.ShapeDtypeStruct((h, dv, dk), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=pallas_interpret(),
        name="kda_chunk_fwd",
    )(w, y, q, kh, bm, gam)
