"""Kimi Delta Attention (KDA) — a gated delta rule with one decay per key
channel — in the two forms serving needs, each with a Pallas kernel and a
jnp form of the same arithmetic (``_dispatch``).

Per head, with state ``S (d_k, d_v)`` f32, zero at a sequence's start:

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

``g = log a <= 0`` is what the callers hand over.  A row with ``b = 0`` and
``g = 0`` is the identity on the state: that is how a prompt's bucket
padding and an idle decode slot are written (the state after a padded
prompt is bit for bit the state at its true length).

The state is held transposed, ``(d_v, d_k)`` (:mod:`apex_tpu.ops.pallas.kda`
says why).

- :func:`kda_chunked` — a whole prompt in chunks of ``chunk`` rows (the WY
  form): with ``G`` the running sum of ``g`` inside a chunk and ``S_0`` the
  incoming state,

      U = T (b V) - T (b K e^G) S_0,   T = (I + b tril(A, -1))^-1
      O = (Q e^G) S_0 + tril(B) U
      S_C = Diag(e^{G_C}) S_0 + (K e^{G_C - G})^T U

  where ``A_tj = sum_d k_t k_j e^{G_t - G_j}`` and ``B`` the same with
  ``q_t``.  ``e^{-G_j}`` alone overflows after 18 rows at the decay floor
  of -5 a row, so the Gram matrices are built in sub-blocks of 16 rows,
  each row and column decayed from its sub-block's start (exponents in
  [-80, 80] where they are kept, f32-safe).
- :func:`kda_step` — one token per sequence against the per-slot slab.
- :func:`kda_recurrent` — the recurrence itself, row by row: the oracle
  the tests hold both against.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from apex_tpu.ops import _dispatch

__all__ = ["kda_chunked", "kda_step", "kda_recurrent"]

_HI = jax.lax.Precision.HIGHEST
#: rows of a sub-block: 16 * 5 = 80 < log(f32 max) = 88.7
_SUB = 16


def kda_recurrent(q, k, v, g, beta, state=None):
    """The oracle: ``q, k, v, g`` ``(S, H, d)``, ``beta`` ``(S, H)``, row by
    row from ``state`` ``(H, d_v, d_k)`` (zeros).  Returns ``(o (S, H,
    d_v), state)``."""
    h, dk, dv = q.shape[1], k.shape[-1], v.shape[-1]
    if state is None:
        state = jnp.zeros((h, dv, dk), jnp.float32)

    def step(st, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        st = st * jnp.exp(g_t)[:, None, :]
        ks = jnp.einsum("hvk,hk->hv", st, k_t, precision=_HI)
        u = b_t[:, None] * (v_t - ks)
        st = st + u[:, :, None] * k_t[:, None, :]
        return st, jnp.einsum("hvk,hk->hv", st, q_t, precision=_HI)

    state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    return o, state


def _intra_chunk(q, k, v, g, beta, c):
    """Everything of the chunked form that does not depend on the incoming
    state, for all chunks at once: ``(w, y, q~, k^, bm, gam)`` as
    :func:`apex_tpu.ops.pallas.kda.kda_chunk_fwd` takes them."""
    s, h, d = k.shape
    nc = s // c
    sub = min(_SUB, c)
    ns = c // sub

    def chunks(x):  # (S, H, w) -> (H, NC, C, w)
        return jnp.transpose(x.reshape(nc, c, h, x.shape[-1]), (2, 0, 1, 3))

    q, k, v, g = (chunks(x.astype(jnp.float32)) for x in (q, k, v, g))
    b = chunks(beta.astype(jnp.float32)[..., None])        # (H, NC, C, 1)
    big = jnp.cumsum(g, axis=2)                             # G, inclusive
    blocks = big.reshape(h, nc, ns, sub, d)
    # each sub-block's reference: G at the row before its first
    ref = jnp.concatenate(
        [jnp.zeros_like(blocks[:, :, :1, 0]), blocks[:, :, :-1, -1]], axis=2
    )                                                       # (H, NC, NS, d)
    row_f = jnp.exp(blocks - ref[:, :, :, None])            # <= 1
    # column j as sub-block a's rows see it: e^{ref_a - G_j}, kept for the
    # columns at or before a's own sub-block (the rest is masked below)
    col_of = jnp.arange(c) // sub
    seen = col_of[None, :] <= jnp.arange(ns)[:, None]       # (NS, C)
    col_f = jnp.exp(jnp.where(
        seen[:, :, None], ref[:, :, :, None] - big[:, :, None], -jnp.inf
    ))                                                      # (H,NC,NS,C,d)
    k_cols = k[:, :, None] * col_f

    def gram(x):
        x = x.reshape(h, nc, ns, sub, d) * row_f
        return jnp.einsum(
            "hnasd,hnajd->hnasj", x, k_cols, precision=_HI
        ).reshape(h, nc, c, c)

    t_idx = jnp.arange(c)
    a = jnp.where(t_idx[:, None] > t_idx[None, :], gram(k), 0.0)
    bm = jnp.where(t_idx[:, None] >= t_idx[None, :], gram(q), 0.0)
    # T (b [V | K e^G]) by one unit-lower-triangular solve
    decay = jnp.exp(big)
    rhs = b * jnp.concatenate([v, k * decay], axis=-1)
    wy = jax.scipy.linalg.solve_triangular(
        jnp.eye(c, dtype=jnp.float32) + b * a, rhs,
        lower=True, unit_diagonal=True,
    )
    last = big[:, :, -1:]                                   # (H, NC, 1, d)
    return (
        wy[..., : v.shape[-1]], wy[..., v.shape[-1]:], q * decay,
        k * jnp.exp(last - big), bm, jnp.exp(last),
    )


def _chunk_scan(w, y, q, kh, bm, gam):
    """jnp form of :func:`~apex_tpu.ops.pallas.kda.kda_chunk_fwd`."""
    h, _, _, dv = w.shape

    def step(st, xs):
        w_c, y_c, q_c, kh_c, bm_c, gam_c = xs
        u = w_c - jnp.einsum("hck,hvk->hcv", y_c, st, precision=_HI)
        o = jnp.einsum("hck,hvk->hcv", q_c, st, precision=_HI) + jnp.einsum(
            "hcj,hjv->hcv", bm_c, u, precision=_HI)
        st = st * gam_c + jnp.einsum("hcv,hck->hvk", u, kh_c, precision=_HI)
        return st, o

    xs = tuple(jnp.swapaxes(x, 0, 1) for x in (w, y, q, kh, bm, gam))
    st, o = jax.lax.scan(
        step, jnp.zeros((h, dv, y.shape[-1]), jnp.float32), xs
    )
    return jnp.swapaxes(o, 0, 1), st


def kda_chunked(q, k, v, g, beta, *, chunk: int = 64):
    """A whole sequence from a zero state: ``q, k, v, g`` ``(S, H, d)``,
    ``beta`` ``(S, H)``; ``S`` a multiple of the chunk (``min(chunk, S)``).
    Returns ``(o (S, H, d_v) f32, state^T (H, d_v, d_k) f32)``."""
    s, h, _ = k.shape
    c = min(chunk, s)
    if s % c or c % min(_SUB, c):
        raise ValueError(f"sequence of {s} rows is not whole chunks of {c}")
    with jax.named_scope("kda_intra_chunk"):
        parts = _intra_chunk(q, k, v, g, beta, c)
    if _dispatch.use_pallas():
        from apex_tpu.ops.pallas.kda import kda_chunk_fwd

        _dispatch.record_path("kda_chunk", "pallas")
        o, st = kda_chunk_fwd(*parts)
    else:
        _dispatch.record_path("kda_chunk", "jnp")
        o, st = _chunk_scan(*parts)
    return jnp.transpose(o, (1, 2, 0, 3)).reshape(s, h, -1), st


def kda_step(state, layer: int, q, k, v, g, beta):
    """One token per sequence against layer ``layer`` (static) of the slab
    ``state`` ``(L, B, H, d_v, d_k)`` f32: ``q, k, v, g`` ``(B, H, d)``,
    ``beta`` ``(B, H)``.  Returns ``(o (B, H, d_v) f32, state)``."""
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    if _dispatch.use_pallas():
        from apex_tpu.ops.pallas.kda import kda_step_fwd

        _dispatch.record_path("kda_step", "pallas")
        return kda_step_fwd(
            state, q, k, v, g,
            jnp.broadcast_to(beta[..., None], v.shape), layer=layer,
        )
    _dispatch.record_path("kda_step", "jnp")
    st = state[layer] * jnp.exp(g)[:, :, None, :]
    ks = jnp.einsum("bhvk,bhk->bhv", st, k, precision=_HI)
    u = beta[..., None] * (v - ks)
    st = st + u[..., None] * k[:, :, None, :]
    o = jnp.einsum("bhvk,bhk->bhv", st, q, precision=_HI)
    return o, state.at[layer].set(st)
