"""Paged single-query decode attention — public API and dispatch.

The serving counterpart of :mod:`apex_tpu.ops.attention`: one query row
per sequence against a KV history living in the block-pooled paged
cache (:mod:`apex_tpu.serve.cache`).  Two numerically-identical
implementations behind the usual :mod:`apex_tpu.ops._dispatch` policy:

- **jnp path** — gathers the live pages into a contiguous history and
  runs masked softmax attention; XLA-fused, the correctness reference,
  and what CPU serving uses by default (the gather is a device-side
  ``take``, no host transfer);
- **Pallas path** (:func:`apex_tpu.ops.pallas.decode_attention.
  paged_decode_fwd`) — reads the pages IN PLACE through
  scalar-prefetched (layer, page-table) indexing of the whole pool: no
  gather materialization, no layer slice, with the per-layer query RoPE
  rotation and the int8-KV dequant fused into the same kernel.  It
  walks every pool: rows are lane-dense (``lane_width``), and an array
  handed over with narrower rows is padded first.

Both paths share the semantics: positions ``>= lengths[b]`` are masked,
an idle slot (``lengths[b] == 0``) returns exactly zeros, and RoPE is
applied to the query INSIDE the attention op (the cached keys were
rotated at append time).  No backward: decode is inference-only, and
the op is wrapped in ``stop_gradient`` to make that explicit.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.ops import _dispatch
from apex_tpu.ops.pallas.decode_attention import (
    heads_per_row,
    lane_width,
    pad_lanes,
    paged_decode_fwd,
    pages_per_step,
    walk_live_share,
)
from apex_tpu.ops.pallas.flash_attention import MASK_VALUE
from apex_tpu.ops.rope import rotate_half

__all__ = [
    "gather_history",
    "heads_per_row",
    "lane_width",
    "pad_lanes",
    "paged_decode_attention",
    "paged_decode_attention_reference",
    "pages_per_step",
    "walk_live_share",
]


def _as_pool(k_pages, v_pages, k_scale, v_scale, layer):
    """Normalize the operands to the serving layout: the whole pool
    ``(L, P, H/G, page, W)`` (scales ``(L, P, 1, page, lane_width(H))``,
    a token a row and a head a lane) plus a layer index, every row whole
    128-lane tiles.  A 4-D ``(P, H, page, D)`` page array (scales ``(P,
    H, page)``) is a one-layer pool at ``G = 1``; rows narrower than
    their tiles (such pages at ``D = 64``, a pool not built by
    ``init_kv_pages``) are zero-padded, which copies the array: the
    serving pool is built dense and never pays it."""
    if k_pages.ndim == 5:
        if layer is None:
            raise ValueError("a 5-D KV pool needs its layer index")
    else:
        if layer is not None:
            raise ValueError("layer indexes a 5-D pool; pages here are 4-D")
        if k_scale is not None:
            # (P, H, page) -> (1, P, 1, page, H): a token a row
            k_scale, v_scale = (
                jnp.swapaxes(x, 1, 2)[None, :, None]
                for x in (k_scale, v_scale)
            )
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    w = lane_width(k_pages.shape[-1])
    if k_scale is not None:
        hl = lane_width(k_scale.shape[-1])
        k_scale, v_scale = pad_lanes(k_scale, hl), pad_lanes(v_scale, hl)
    return (
        pad_lanes(k_pages, w), pad_lanes(v_pages, w), k_scale, v_scale,
        layer,
    )


def gather_history(pool, scale, layer, page_table, heads, head_dim=None):
    """One layer's pages of ``pool`` through ``page_table`` (B, NP) as a
    contiguous f32 history ``(B, H, NP*page, D)`` (dequantized when
    ``scale`` is given).  ``head_dim`` says where a row's heads end when
    the row is padded to whole tiles (``lane_width``); by default the
    row is all heads."""
    b, np_ = page_table.shape
    hg, page, w = pool.shape[2:]
    g = heads // hg
    d = w // g if head_dim is None else head_dim
    # (B, NP, H/G, page, D*G): the row's heads, less its padding lanes
    x = pool[layer, page_table][..., :d * g].astype(jnp.float32)
    if scale is not None:
        # (B, NP, page, H), a head a lane -> a factor a lane of the rows
        sc = scale[layer, page_table][:, :, 0, :, :heads]
        sc = jnp.swapaxes(sc.reshape(b, np_, page, hg, g), 2, 3)
        x = x * jnp.repeat(sc.astype(jnp.float32), d, axis=-1)
    # lanes back into (G, D); heads (H/G, G) and positions (NP, page) join
    x = x.reshape(b, np_, hg, page, g, d)
    return jnp.transpose(x, (0, 2, 4, 1, 3, 5)).reshape(
        b, heads, np_ * page, d
    )


def paged_decode_attention_reference(
    q, k_pages, v_pages, page_table, lengths, *,
    layer=None, scale: Optional[float] = None,
    k_scale=None, v_scale=None, rope_cos=None, rope_sin=None,
    kv_heads: Optional[int] = None,
):
    """Gather-then-attend jnp composition — the correctness reference.

    Same signature and semantics as :func:`paged_decode_attention`.
    """
    k_pages, v_pages, k_scale, v_scale, layer = _as_pool(
        k_pages, v_pages, k_scale, v_scale, layer
    )
    b, h, d = q.shape
    np_ = page_table.shape[1]
    page = k_pages.shape[3]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    qf = q.astype(jnp.float32)
    if rope_cos is not None:
        cos = rope_cos.astype(jnp.float32)[:, None, :]  # (B, 1, D)
        sin = rope_sin.astype(jnp.float32)[:, None, :]
        qf = qf * cos + rotate_half(qf) * sin
    hkv = h if kv_heads is None else kv_heads
    k = gather_history(k_pages, k_scale, layer, page_table, hkv, d)
    v = gather_history(v_pages, v_scale, layer, page_table, hkv, d)
    if hkv != h:
        # grouped-query heads: query head i reads KV head i // (H / kv)
        k, v = (jnp.repeat(x, h // hkv, axis=1) for x in (k, v))
    s = jnp.einsum("bhd,bhtd->bht", qf, k) * scale
    pos = jnp.arange(np_ * page, dtype=jnp.int32)
    valid = pos[None, :] < lengths[:, None]  # (B, T)
    s = jnp.where(valid[:, None, :], s, MASK_VALUE)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bht,bhtd->bhd", p / jnp.maximum(l, 1e-30), v)
    # idle slots: softmax over an all-masked row would be a uniform
    # average of garbage pages — the contract is zeros
    o = jnp.where(lengths[:, None, None] > 0, o, 0.0)
    return o.astype(q.dtype)


def paged_decode_attention(
    q, k_pages, v_pages, page_table, lengths, *,
    layer=None, scale: Optional[float] = None,
    k_scale=None, v_scale=None, rope_cos=None, rope_sin=None,
    kv_heads: Optional[int] = None,
):
    """Single-query attention over the paged KV cache.

    - ``q`` (B, H, D): the current token's query rows (PRE-RoPE when
      ``rope_cos``/``rope_sin`` are given — the rotation fuses here);
    - ``k_pages``/``v_pages``: the serving pool ``(L, P, H/G, page,
      W)`` read at ``layer`` (``G`` heads side by side in a lane row of
      ``W = lane_width(D*G)`` lanes: :func:`heads_per_row`), or plain
      ``(P, H, page, D)`` pages with no ``layer``; rows narrower than
      their tiles are padded here, a copy of the array (the serving
      pool is built dense).  f32/bf16, or int8 codes with ``k_scale``/
      ``v_scale`` blockwise f32 scales ``(L, P, 1, page,
      lane_width(H))`` (a token a row, a head a lane) / ``(P, H,
      page)`` — the ``parallel/comm.py`` codec at ``block = D``;
    - ``page_table`` (B, NP) int32; ``lengths`` (B,) int32: live KV
      positions per sequence including the current token;
    - ``kv_heads``: the heads the pool holds when fewer than ``H``
      (grouped-query attention: query head ``i`` reads KV head ``i // (H /
      kv_heads)``; the kernel copies a page in once for all of them).

    Returns (B, H, D) in ``q.dtype``.  Inference-only (no VJP;
    gradients are stopped).  Dispatch: the Pallas in-place page-walk
    kernel on TPU (or when forced), the gather-based jnp composition
    otherwise.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    k_pages, v_pages, k_scale, v_scale, layer = _as_pool(
        k_pages, v_pages, k_scale, v_scale, layer
    )
    args = (q, k_pages, v_pages, page_table, lengths)
    kw = dict(
        scale=scale, k_scale=k_scale, v_scale=v_scale,
        rope_cos=rope_cos, rope_sin=rope_sin,
    )
    if kv_heads is not None and kv_heads != q.shape[1]:
        kw["kv_heads"] = kv_heads
    if _dispatch.use_pallas():
        _dispatch.record_path("paged_decode_attention", "pallas")
        out = paged_decode_fwd(*args, layer, **kw)
    else:
        _dispatch.record_path("paged_decode_attention", "jnp")
        out = paged_decode_attention_reference(*args, layer=layer, **kw)
    return jax.lax.stop_gradient(out)
