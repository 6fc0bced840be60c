"""Pallas TPU kernels for the Mamba-2 state-space mixer — the two places its
recurrent state is carried through.

Per head, with ``S (P, N)`` f32 (``P`` the head's channels, ``N`` the state
size), zero at a sequence's start, a scalar step ``dt_t > 0`` and decay
rate ``A < 0``:

    S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) B_t^T,   y_t = S_t C_t

The state is the model's memory and stays f32 (the rule
:mod:`apex_tpu.ops.pallas.kda` states and keeps): the decode kernel
multiplies with operands split into exact bf16 parts, the prompt kernel at
``HIGHEST``.

- :func:`ssm_step_fwd` — decode: one token per sequence against the per-slot
  state slab ``(L, B, H, P, N)``, updated IN PLACE
  (``input_output_aliases``): a step reads and writes each live state once,
  which is the kernel's roofline.  A row with ``dt = 0`` (``decay = 1``,
  ``dt x = 0``) leaves its state bit for bit as it was: that is how an idle
  slot is written.
- :func:`ssd_chunk_fwd` — prompt: the inter-chunk recurrence of the chunked
  (SSD) form.  Everything that does not depend on the incoming state (the
  decayed ``C B^T`` products inside a chunk, each chunk's own state) is
  batched XLA in :mod:`apex_tpu.ops.ssm`; this kernel walks the chunks with
  the state resident in VMEM.

The jnp forms of the same arithmetic, and the dispatch, live in
:mod:`apex_tpu.ops.ssm`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._dispatch import pallas_interpret
from apex_tpu.ops.pallas.kda import _VEC_ROWS, _bf16_parts, _dot, _nt

__all__ = ["ssm_step_fwd", "ssd_chunk_fwd"]

_HI = jax.lax.Precision.HIGHEST
#: bytes of state one grid step holds (in, and again out): 8 heads of
#: 128 x 256 f32
_STEP_BYTES = 1 << 20


def _heads_per_step(h: int, head_bytes: int) -> int:
    for hb in (8, 4, 2):
        if h % hb == 0 and hb * head_bytes <= _STEP_BYTES:
            return hb
    return 1


# ---------------------------------------------------------------------------
# decode: one token against the slab
# ---------------------------------------------------------------------------


def _step_kernel(u_ref, a_ref, b_ref, c_ref, s_ref, y_ref, so_ref, *, hb):
    """Per head, three single-pass bf16 matmuls with the operands split
    into exact bf16 parts (``x = hi + lo``: ~16 mantissa bits where a
    ``HIGHEST`` f32 matmul spends six passes on 24): ``C S'^T`` against the
    decayed state's hi part and against its lo part, and the rank-one update
    ``u (x) B`` with its four hi/lo products laid along the contraction.
    ``y = C S_new = C S' + (C.B) u`` reuses the first two."""
    row = jax.lax.broadcasted_iota(jnp.int32, (_VEC_ROWS, 1), 0)
    outs = []
    for i in range(hb):
        vec = lambda ref: ref[0, i:i + 1, :]  # noqa: E731  (1, width)
        u, b, c = vec(u_ref), vec(b_ref), vec(c_ref)
        st = s_ref[0, 0, i] * vec(a_ref)                    # exp(dt A) S
        st_hi, st_lo = _bf16_parts(st)
        c_hi, c_lo = _bf16_parts(c)
        cs = jnp.where(row == 0, c_hi, jnp.where(row == 1, c_lo, 0.0))
        nt = ((1,), (1,))
        prod = _dot(cs, st_hi, nt) + _dot(cs, st_lo, nt)    # (16, P)
        u_hi, u_lo = _bf16_parts(u)
        b_hi, b_lo = _bf16_parts(b)
        # u (x) B = (u_hi + u_lo) (x) (b_hi + b_lo), one product a row
        us = jnp.where(row < 2, u_hi, jnp.where(row < 4, u_lo, 0.0))
        bs = jnp.where(row >= 4, 0.0, jnp.where(row % 2 == 0, b_hi, b_lo))
        so_ref[0, 0, i] = st + _dot(us, bs, ((0,), (0,)))
        outs.append(prod[0:1] + prod[1:2]
                    + jnp.sum(c * b, axis=-1, keepdims=True) * u)
    y_ref[0] = jnp.concatenate(outs, axis=0)


@functools.partial(jax.jit, static_argnames=("layer",))
def ssm_step_fwd(state, u, decay, b, c, *, layer: int):
    """One state-space token per sequence against layer ``layer`` of
    ``state`` ``(L, B, H, P, N)`` f32, in place.  ``u`` ``(B, H, P)`` is
    ``dt x``; ``decay`` ``(B, H, N)`` is ``exp(dt A)`` across the head's
    lanes; ``b, c`` ``(B, H, N)`` each head's (its group's) input and
    output vectors — all f32.  A row with ``decay = 1, u = 0`` leaves its
    state as it was.  Returns ``(y (B, H, P), state)``."""
    _, bsz, h, p, n = state.shape
    hb = _heads_per_step(h, 4 * p * n)

    def vec(width):
        return pl.BlockSpec((1, hb, width), lambda i, j: (i, j, 0))

    slab = pl.BlockSpec((1, 1, hb, p, n), lambda i, j: (layer, i, j, 0, 0))
    y, state = pl.pallas_call(
        functools.partial(_step_kernel, hb=hb),
        grid=(bsz, h // hb),
        in_specs=[vec(p), vec(n), vec(n), vec(n), slab],
        out_specs=[vec(p), slab],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, p), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=pallas_interpret(),
        name="ssm_step_fwd",
    )(u, decay, b, c, state)
    return y, state


# ---------------------------------------------------------------------------
# prompt: the inter-chunk recurrence
# ---------------------------------------------------------------------------


def _chunk_kernel(cd_ref, own_ref, gam_ref, y_ref, st_ref, *, hb):
    @pl.when(pl.program_id(1) == 0)
    def _zero():
        st_ref[...] = jnp.zeros_like(st_ref)

    for i in range(hb):
        st = st_ref[i]                                      # (P, N)
        y_ref[i, 0] = _nt(cd_ref[i, 0], st)                 # (C, P)
        st_ref[i] = st * gam_ref[i, 0] + own_ref[i, 0]


@jax.jit
def ssd_chunk_fwd(cd, own, gam):
    """Walk the chunks of one sequence from a zero state.  Per head and
    chunk (``c`` rows): ``cd (c, N)`` the output vectors decayed from the
    chunk's start, ``own (P, N)`` the state the chunk's own rows leave at
    its end, ``gam (1, N)`` the chunk's whole decay across the lanes — all
    ``(H, NC, ...)`` f32 (:func:`apex_tpu.ops.ssm._intra_chunk`).  Returns
    ``(y (H, NC, c, P)`` — what the incoming state adds to each row's
    output — ``, state (H, P, N))``."""
    h, nc, c, n = cd.shape
    p = own.shape[2]
    hb = _heads_per_step(h, 4 * p * n)
    return pl.pallas_call(
        functools.partial(_chunk_kernel, hb=hb),
        grid=(h // hb, nc),
        in_specs=[
            pl.BlockSpec((hb, 1, c, n), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((hb, 1, p, n), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((hb, 1, 1, n), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((hb, 1, c, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((hb, p, n), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((h, nc, c, p), jnp.float32),
            jax.ShapeDtypeStruct((h, p, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=pallas_interpret(),
        name="ssd_chunk_fwd",
    )(cd, own, gam)
