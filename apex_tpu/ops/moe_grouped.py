"""Grouped SwiGLU expert matmul over rows sorted by expert: the Pallas
kernel on TPU (:mod:`apex_tpu.ops.pallas.moe_grouped`), a gather-and-einsum
jnp form elsewhere (it copies an expert's matrices per tile: tests and tiny
shapes only)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from apex_tpu.ops import _dispatch

__all__ = ["grouped_swiglu"]


def grouped_swiglu(x, tile_expert, live_tiles, gate, up, down, *, tile: int):
    """Row ``r`` of ``x`` ``(M, H)`` through the SwiGLU expert
    ``tile_expert[r // tile]``; tiles from ``live_tiles`` on are not
    computed (their rows come back unspecified).  ``gate, up`` ``(E, H,
    I)``, ``down`` ``(E, I, H)``; f32 accumulation, ``x.dtype`` out."""
    if _dispatch.use_pallas():
        from apex_tpu.ops.pallas.moe_grouped import moe_grouped_fwd

        _dispatch.record_path("moe_grouped", "pallas")
        return moe_grouped_fwd(
            x, tile_expert.astype(jnp.int32),
            jnp.reshape(live_tiles, (1,)).astype(jnp.int32),
            gate, up, down, tile=tile,
        )
    _dispatch.record_path("moe_grouped", "jnp")
    with jax.named_scope("moe_grouped_fwd"):
        tiles = x.reshape(-1, tile, x.shape[-1])
        f32 = dict(preferred_element_type=jnp.float32)
        a = jnp.einsum("tmh,thi->tmi", tiles, gate[tile_expert], **f32)
        b = jnp.einsum("tmh,thi->tmi", tiles, up[tile_expert], **f32)
        h = (a * jax.nn.sigmoid(a) * b).astype(x.dtype)
        out = jnp.einsum("tmi,tih->tmh", h, down[tile_expert], **f32)
        return out.astype(x.dtype).reshape(x.shape)
