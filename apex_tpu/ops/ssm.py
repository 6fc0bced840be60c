"""The Mamba-2 state-space recurrence (SSD) in the two forms serving needs,
each with a Pallas kernel and a jnp form of the same arithmetic
(``_dispatch``).

Per head, with state ``S (P, N)`` f32, zero at a sequence's start, a step
``dt_t >= 0`` and a decay rate ``A < 0`` (one scalar a head):

    S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) B_t^T
    y_t = S_t C_t

``B_t, C_t (N,)`` are shared by the heads of a group (``G`` groups, the
heads split over them in order).  A row with ``dt = 0`` is the identity on
the state: that is how a prompt's bucket padding and an idle decode slot are
written (the state after a padded prompt is bit for bit the state at its
true length).  The skip ``D x``, the gate and the norm are the caller's.

- :func:`ssd_chunked` — a whole prompt in chunks of ``chunk`` rows: with
  ``g`` the running sum of ``dt A`` inside a chunk and ``S_0`` the incoming
  state,

      Y = (tril(e^{g_t - g_s}) * (C B^T)) (dt X) + (C e^{g}) S_0^T
      S_C = e^{g_C} S_0 + ((dt X) e^{g_C - g})^T B

  every exponent is ``<= 0`` where it is kept (the decay is a scalar a
  head, so a chunk needs no sub-blocks).
- :func:`ssm_step` — one token per sequence against the per-slot slab.
- :func:`ssm_recurrent` — the recurrence itself, row by row: the oracle the
  tests hold both against.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from apex_tpu.ops import _dispatch

__all__ = ["ssd_chunked", "ssm_step", "ssm_recurrent"]

_HI = jax.lax.Precision.HIGHEST


def _per_head(v, h):
    """A group's vectors ``(..., G, N)`` as each head's ``(..., H, N)``."""
    return jnp.repeat(v, h // v.shape[-2], axis=-2)


def ssm_recurrent(x, dt, a, b, c, state=None):
    """The oracle: ``x`` ``(S, H, P)``, ``dt`` ``(S, H)``, ``a`` ``(H,)``,
    ``b, c`` ``(S, G, N)``, row by row from ``state`` ``(H, P, N)``
    (zeros).  Returns ``(y (S, H, P), state)``."""
    h, p = x.shape[1:]
    if state is None:
        state = jnp.zeros((h, p, b.shape[-1]), jnp.float32)

    def step(st, xs):
        x_t, dt_t, b_t, c_t = xs
        st = st * jnp.exp(dt_t * a)[:, None, None] + (
            (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return st, jnp.sum(st * c_t[:, None, :], axis=-1)

    state, y = jax.lax.scan(
        step, state, (x, dt, _per_head(b, h), _per_head(c, h)))
    return y, state


def _intra_chunk(x, dt, a, b, c, ch):
    """Everything of the chunked form that does not depend on the incoming
    state, for all chunks at once: ``(y_intra (H, NC, C, P), cd, own,
    gam)``, the last three as :func:`apex_tpu.ops.pallas.ssm.ssd_chunk_fwd`
    takes them."""
    s, h, p = x.shape
    g, n = b.shape[1:]
    nc, hg = s // ch, h // g

    def chunks(v):  # (S, ...) -> (NC, C, ...)
        return v.reshape((nc, ch) + v.shape[1:])

    la = chunks(dt * a)                                     # (NC, C, H)
    cum = jnp.cumsum(la, axis=1)                            # inclusive
    u = chunks(x * dt[..., None]).reshape(nc, ch, g, hg, p)
    b, c = chunks(b), chunks(c)                             # (NC, C, G, N)
    cb = jnp.einsum("ztgn,zsgn->zgts", c, b, precision=_HI)
    t = jnp.arange(ch)
    diff = cum[:, :, None, :] - cum[:, None, :, :]          # (NC, t, s, H)
    decay = jnp.exp(jnp.where(
        (t[:, None] >= t[None, :])[None, :, :, None], diff, -jnp.inf))
    m = cb[:, :, None] * jnp.transpose(decay, (0, 3, 1, 2)).reshape(
        nc, g, hg, ch, ch)
    y_intra = jnp.einsum("zgkts,zsgkp->gkztp", m, u, precision=_HI)
    last = cum[:, -1:, :]                                   # (NC, 1, H)
    to_end = jnp.exp(last - cum).reshape(nc, ch, g, hg)
    own = jnp.einsum(
        "zsgkp,zsgn->gkzpn", u * to_end[..., None], b, precision=_HI)
    cd = c[:, :, :, None, :] * jnp.exp(cum).reshape(nc, ch, g, hg)[..., None]
    cd = jnp.transpose(cd, (2, 3, 0, 1, 4))                 # (G, hg, NC, C, N)
    gam = jnp.broadcast_to(
        jnp.exp(jnp.transpose(last, (2, 0, 1)))[..., None], (h, nc, 1, n))
    return (y_intra.reshape(h, nc, ch, p), cd.reshape(h, nc, ch, n),
            own.reshape(h, nc, p, n), gam)


def _chunk_scan(cd, own, gam):
    """jnp form of :func:`~apex_tpu.ops.pallas.ssm.ssd_chunk_fwd`."""
    h, _, p, n = own.shape

    def step(st, xs):
        cd_c, own_c, gam_c = xs
        y = jnp.einsum("hcn,hpn->hcp", cd_c, st, precision=_HI)
        return st * gam_c + own_c, y

    xs = tuple(jnp.swapaxes(v, 0, 1) for v in (cd, own, gam))
    st, y = jax.lax.scan(step, jnp.zeros((h, p, n), jnp.float32), xs)
    return jnp.swapaxes(y, 0, 1), st


def ssd_chunked(x, dt, a, b, c, *, chunk: int = 128):
    """A whole sequence from a zero state: ``x`` ``(S, H, P)``, ``dt``
    ``(S, H)``, ``a`` ``(H,)``, ``b, c`` ``(S, G, N)``; ``S`` a multiple of
    the chunk (``min(chunk, S)``).  Returns ``(y (S, H, P) f32, state (H,
    P, N) f32)``."""
    x, dt, a, b, c = (v.astype(jnp.float32) for v in (x, dt, a, b, c))
    s, h, p = x.shape
    ch = min(chunk, s)
    if s % ch:
        raise ValueError(f"sequence of {s} rows is not whole chunks of {ch}")
    with jax.named_scope("ssd_intra_chunk"):
        y_intra, cd, own, gam = _intra_chunk(x, dt, a, b, c, ch)
    if _dispatch.use_pallas():
        from apex_tpu.ops.pallas.ssm import ssd_chunk_fwd

        _dispatch.record_path("ssd_chunk", "pallas")
        y_inter, st = ssd_chunk_fwd(cd, own, gam)
    else:
        _dispatch.record_path("ssd_chunk", "jnp")
        y_inter, st = _chunk_scan(cd, own, gam)
    y = y_intra + y_inter                                   # (H, NC, C, P)
    return jnp.transpose(y, (1, 2, 0, 3)).reshape(s, h, p), st


def ssm_step(state, layer: int, x, dt, a, b, c):
    """One token per sequence against layer ``layer`` (static) of the slab
    ``state`` ``(L, B, H, P, N)`` f32: ``x`` ``(B, H, P)``, ``dt`` ``(B,
    H)``, ``a`` ``(H,)``, ``b, c`` ``(B, G, N)``.  Returns ``(y (B, H, P)
    f32, state)``."""
    x, dt, a, b, c = (v.astype(jnp.float32) for v in (x, dt, a, b, c))
    h, n = x.shape[1], b.shape[-1]
    u = x * dt[..., None]
    decay = jnp.exp(dt * a)
    b, c = _per_head(b, h), _per_head(c, h)
    if _dispatch.use_pallas():
        from apex_tpu.ops.pallas.ssm import ssm_step_fwd

        _dispatch.record_path("ssm_step", "pallas")
        return ssm_step_fwd(
            state, u, jnp.broadcast_to(decay[..., None], decay.shape + (n,)),
            b, c, layer=layer,
        )
    _dispatch.record_path("ssm_step", "jnp")
    st = state[layer] * decay[..., None, None] + (
        u[..., None] * b[:, :, None, :])
    y = jnp.einsum("bhpn,bhn->bhp", st, c, precision=_HI)
    return y, state.at[layer].set(st)
