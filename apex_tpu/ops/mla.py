"""Latent-attention (MLA) decode over the latent page pool: the Pallas
kernel on TPU (:mod:`apex_tpu.ops.pallas.mla_decode`), a gather-the-pages
jnp form of the same arithmetic elsewhere."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from apex_tpu.ops import _dispatch

__all__ = ["latent_row_width", "mla_decode_attention"]

_LANES = 128


def latent_row_width(kv_lora_rank: int, rope_dim: int) -> int:
    """Lanes of one cached row ``[c | k_r | 0]``: whole 128-lane tiles."""
    return -(-(kv_lora_rank + rope_dim) // _LANES) * _LANES


def mla_decode_attention(q, pool, page_table, lengths, *, layer: int, scale):
    """Absorbed-form single-query attention.  ``q`` ``(B, heads, W)``
    ``[q_n W_b^K | q_r | 0]``, ``pool`` ``(L, P, 1, page, W)``,
    ``page_table`` ``(B, NP)``, ``lengths`` ``(B,)`` (0 = idle: zeros
    out).  Returns the latent-space context ``(B, heads, W)`` f32."""
    if _dispatch.use_pallas():
        from apex_tpu.ops.pallas.mla_decode import mla_decode_fwd

        _dispatch.record_path("mla_decode", "pallas")
        return mla_decode_fwd(
            q, pool, page_table, lengths, layer=layer, scale=float(scale)
        )
    _dispatch.record_path("mla_decode", "jnp")
    with jax.named_scope("mla_decode_fwd"):
        b, _, w = q.shape
        kv = pool[layer][page_table][:, :, 0].reshape(b, -1, w)
        s = jnp.einsum(
            "bhw,btw->bht", q.astype(pool.dtype), kv,
            preferred_element_type=jnp.float32,
        ) * scale
        live = jnp.arange(kv.shape[1])[None, :] < lengths[:, None]
        s = jnp.where(live[:, None, :], s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.where(live[:, None, :], jnp.exp(s - jnp.where(
            jnp.isfinite(m), m, 0.0)), 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        ctx = jnp.einsum(
            "bht,btw->bhw", p.astype(pool.dtype), kv,
            preferred_element_type=jnp.float32,
        )
        return jnp.where(l > 0, ctx / jnp.maximum(l, 1e-30), 0.0)
