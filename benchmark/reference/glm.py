"""GLM-4.7-Flash's decoder stack — plain jax.numpy, float32.

Follows zai-org/GLM-4.7-Flash's ``config.json`` (``model_type``
``glm4_moe_lite``; the keys named below) and the layer equations of ISSUE
42: pre-RMSNorm blocks whose mixer is multi-head latent attention in every
layer, with a low-rank query path, and whose FFN is a dense SwiGLU in the
first ``first_k_dense_replace`` layers and a routed one (sigmoid scores,
top-k on score plus correction bias, no group limit) plus a shared expert
in the rest; a final RMSNorm; an untied head.

No cache, no batching, no kernels, no absorbed form, no imports from the
program: one sequence in, every position's logits out.  MLA is in its
plain, un-absorbed form: every head's keys and values are rebuilt from the
latent.  The queries are taken ``block`` rows at a time (the only
departure from one whole-sequence product), so that the scores held at
once are ``(heads, block, S)`` and a 24k-token sequence fits on one chip;
each block sees every key and the causal mask.  Every expert is applied to
every token and weighted by the routing (zero where not chosen).  The MTP
layer (``num_nextn_predict_layers``) is not part of this forward.

Parameter layout (``p``): ``embed (V,H)``, ``head (H,V)``, ``norm_f (H)``,
``layers``: a list of dicts, one a layer, with ``norm1``, ``norm2`` (H) and

  MLA:  ``wqa (H, q_lora)``, ``qnorm (q_lora)``, ``wqb (q_lora,
        n*(dn+dr))``, ``wa (H, r+dr)``, ``anorm (r)``, ``wb (r,
        n*(dn+dv))``, ``wo (n*dv, H)``
  dense FFN:  ``w_gate w_up (H,I)``, ``w_down (I,H)``
  routed FFN: ``router (H,E)``, ``bias (E)``, ``e_gate e_up (E,H,Im)``,
        ``e_down (E,Im,H)``, ``s_gate s_up (H,Is)``, ``s_down (Is,H)``
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.precision import einsum

#: query rows a block of the attention
BLOCK = 512


def layer_kinds(cfg):
    """[(mixer, ffn)] per layer: MLA everywhere; the first
    ``first_k_dense_replace`` layers keep a dense FFN."""
    return [("mla", "dense" if i < cfg["first_k_dense_replace"] else "moe")
            for i in range(cfg["num_hidden_layers"])]


def rms_norm(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def swiglu(x, w_gate, w_up, w_down, prec):
    h = jax.nn.silu(einsum("sh,hi->si", x, w_gate, prec)) * einsum(
        "sh,hi->si", x, w_up, prec)
    return einsum("si,ih->sh", h, w_down, prec)


def rope_rotate(x, pos, theta):
    """Rotary embedding over the last axis of ``x`` (S, ..., R) at
    positions ``pos`` (S,).  assumed: rotate-half pairing (lane i with lane
    i + R/2), the convention of the family's DeepSeek-V3 lineage as the
    repository reads it; ``partial_rotary_factor`` 1: all R lanes."""
    r = x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (r,)
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    return x * cos.reshape(shape) + jnp.concatenate(
        [-x2, x1], -1) * sin.reshape(shape)


def mla(lp, y, cfg, prec, block=BLOCK):
    """Multi-head latent attention over one sequence ``y`` (S,H):
    c_q = RMSNorm(y W_qa), q = c_q W_qb; [c | k_r] = y W_kva, c normed;
    [k_n | v] = c W_kvb per head; the rotary key shared by all heads;
    scores (q_n.k_n + q_r.k_r) / sqrt(dn + dr) under a causal softmax; no
    output gate."""
    n = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    s = y.shape[0]
    pos = jnp.arange(s)
    c_q = rms_norm(einsum("sh,hk->sk", y, lp["wqa"], prec), lp["qnorm"], eps)
    q = einsum("sk,kq->sq", c_q, lp["wqb"], prec).reshape(s, n, dn + dr)
    a = einsum("sh,hk->sk", y, lp["wa"], prec)
    c = rms_norm(a[:, :r], lp["anorm"], eps)
    kv = einsum("sr,rk->sk", c, lp["wb"], prec).reshape(s, n, dn + dv)
    q_n = q[..., :dn]
    q_r = rope_rotate(q[..., dn:], pos, cfg["rope_theta"])
    k_r = rope_rotate(a[:, r:], pos, cfg["rope_theta"])
    b = min(block, s)
    pad = -s % b

    def attend(i):
        rows = i * b + jnp.arange(b)
        qn = jax.lax.dynamic_slice_in_dim(q_n_p, i * b, b, 0)
        qr = jax.lax.dynamic_slice_in_dim(q_r_p, i * b, b, 0)
        sc = einsum("qnd,knd->nqk", qn, kv[..., :dn], prec) + einsum(
            "qnd,kd->nqk", qr, k_r, prec)
        sc = sc * (dn + dr) ** -0.5 + jnp.where(
            rows[:, None] >= pos[None, :], 0.0, -1e9)
        return einsum("nqk,knd->qnd", jax.nn.softmax(sc, -1), kv[..., dn:],
                      prec)

    q_n_p = jnp.pad(q_n, ((0, pad), (0, 0), (0, 0)))
    q_r_p = jnp.pad(q_r, ((0, pad), (0, 0), (0, 0)))
    o = jax.lax.map(attend, jnp.arange((s + pad) // b))
    o = o.reshape(s + pad, n * dv)[:s]
    return einsum("sk,kh->sh", o, lp["wo"], prec)


def route(lp, y, cfg):
    """(S,E) routing weights, zero where not chosen (``topk_method``
    ``noaux_tc``, ``n_group`` 1): sigmoid scores in f32; the top
    ``num_experts_per_tok`` by score + correction bias; weights are the
    chosen scores (without bias), normalised to sum 1 (``norm_topk_prob``),
    times ``routed_scaling_factor``."""
    sc = jax.nn.sigmoid(jnp.einsum(
        "sh,he->se", y.astype(jnp.float32), lp["router"],
        precision=jax.lax.Precision.HIGHEST))
    idx = jax.lax.top_k(sc + lp["bias"], cfg["num_experts_per_tok"])[1]
    chosen = jnp.zeros(sc.shape, bool).at[
        jnp.arange(sc.shape[0])[:, None], idx].set(True)
    w = jnp.where(chosen, sc, 0.0)
    return w / jnp.sum(w, -1, keepdims=True) * cfg["routed_scaling_factor"]


def moe(lp, y, cfg, prec):
    """Every expert applied to every token, weighted by the routing, plus
    the shared expert (``n_shared_experts`` 1)."""
    w = route(lp, y, cfg)

    def expert(acc, xs):
        g, u, d, we = xs
        return acc + we[:, None] * swiglu(y, g, u, d, prec), None

    out, _ = jax.lax.scan(
        expert, swiglu(y, lp["s_gate"], lp["s_up"], lp["s_down"], prec),
        (lp["e_gate"], lp["e_up"], lp["e_down"], w.T))
    return out


def block(lp, x, kind, cfg, prec):
    _, ffn = kind
    eps = cfg["rms_norm_eps"]
    x = x + mla(lp, rms_norm(x, lp["norm1"], eps), cfg, prec)
    y = rms_norm(x, lp["norm2"], eps)
    if ffn == "dense":
        return x + swiglu(y, lp["w_gate"], lp["w_up"], lp["w_down"], prec)
    return x + moe(lp, y, cfg, prec)


def logits(p, ids, cfg, prec="f32"):
    """``ids`` (S,) int32 -> (S, V) float32 logits; position i sees tokens
    0..i only, so padding after a sequence's end changes nothing before it.
    ``p``'s leaves may be stored in any float type: each layer is upcast
    when it is used, never the whole tree."""
    up = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a.astype(jnp.float32), t)
    x = up(p["embed"][ids])
    for lp, kind in zip(p["layers"], layer_kinds(cfg)):
        x = block(up(lp), x, kind, cfg, prec)
    x = rms_norm(x, up(p["norm_f"]), cfg["rms_norm_eps"])
    # tie_word_embeddings false: the head is its own matrix
    return einsum("sh,hv->sv", x, up(p["head"]), prec)


__all__ = ["layer_kinds", "logits", "block", "moe", "route", "mla",
           "rms_norm", "swiglu", "BLOCK"]
