"""Ling-3.0-flash's language stack as ONE chip of an expert-parallel group
sees it — plain jax.numpy, float32.

Follows inclusionAI/Ling-3.0-flash-VL's ``config.json`` (the keys named
below) and the layer equations of ISSUE 35: pre-RMSNorm blocks whose mixer
is Kimi Delta Attention (KDA: a gated delta rule with one decay per key
channel, a 4-tap causal depthwise convolution and SiLU on q, k, v) or
multi-head latent attention (MLA, here in its plain, un-absorbed form),
and whose FFN is a dense SwiGLU or a routed one (sigmoid scores,
group-limited top-k) plus a shared expert; final RMSNorm; an untied head.

No cache, no batching, no kernels, no chunked form, no imports from the
program: one sequence in, every position's logits out.  KDA is its
per-token recurrence under ``lax.scan``; MLA materialises every head's
keys and values; every HELD expert is applied to every token and masked
by the routing weights.

**The share.**  ``held`` is the list of expert ids this chip holds and
``p["head"]`` the vocabulary columns it holds.  The router scores all
``num_experts`` experts; only the held experts' terms (and the shared
expert) are added, what the other chips of the group would add is left
out, and that partial result goes on to the next layer — the program does
the same.  ``held = range(num_experts)`` is the uncut layer.

Parameter layout (``p``): ``embed (V,H)``, ``head (H,V)``, ``norm_f (H)``,
``layers``: a list of dicts, one a layer, with ``norm1``, ``norm2`` (H) and

  KDA:  ``wq wk wv wg (H, n*d)``, ``bg (n*d)``, ``conv_q conv_k conv_v
        (taps, n*d)``, ``wbeta (H,n)``, ``wgate (H,n)``, ``onorm (d)``,
        ``wo (n*d, H)``
  MLA:  ``wq (H, n*(dn+dr))``, ``wa (H, r+dr)``, ``anorm (r)``,
        ``wb (r, n*(dn+dv))``, ``wgate (H,n)``, ``wo (n*dv, H)``
  dense FFN:  ``w_gate w_up (H,I)``, ``w_down (I,H)``
  routed FFN: ``router (H,E)``, ``bias (E)``, ``e_gate e_up (Eh,H,Im)``,
        ``e_down (Eh,Im,H)`` (row j is expert ``held[j]``), ``s_gate s_up
        (H,Is)``, ``s_down (Is,H)``
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.precision import einsum


def layer_kinds(cfg):
    """[(mixer, ffn)] per layer.  assumed: layer i is MLA when (i + 1) is a
    multiple of ``layer_group_size`` (the family's convention: the last
    layer of each group is the full-attention one), else KDA; the first
    ``first_k_dense_replace`` layers keep a dense FFN."""
    return [
        ("mla" if (i + 1) % cfg["layer_group_size"] == 0 else "kda",
         "dense" if i < cfg["first_k_dense_replace"] else "moe")
        for i in range(cfg["num_hidden_layers"])
    ]


def rms_norm(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def swiglu(x, w_gate, w_up, w_down, prec):
    h = jax.nn.silu(einsum("sh,hi->si", x, w_gate, prec)) * einsum(
        "sh,hi->si", x, w_up, prec)
    return einsum("si,ih->sh", h, w_down, prec)


def causal_conv(x, taps):
    """Depthwise causal convolution: y_t = sum_i taps[i] * x_{t-(K-1)+i},
    zeros before the sequence's start.  ``x`` (S,C), ``taps`` (K,C)."""
    k = taps.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x], 0)
    return sum(taps[i] * xp[i:i + x.shape[0]] for i in range(k))


def kda(lp, y, cfg, prec):
    """Kimi Delta Attention over one sequence ``y`` (S,H): the per-token
    recurrence, state in f32, zero at the sequence's start."""
    n, d = cfg["num_attention_heads"], cfg["head_dim"]
    s = y.shape[0]

    def proj(w, conv):
        # linear_silu: SiLU after the short convolution
        x = jax.nn.silu(causal_conv(einsum("sh,hk->sk", y, w, prec), conv))
        return x.reshape(s, n, d)

    q, k, v = (proj(lp["w" + c], lp["conv_" + c]) for c in "qkv")
    # use_qk_norm: q and k L2-normalised per head; q carries d^-1/2
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * d ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    # assumed (kda_safe_gate, kda_lower_bound): the log-decay of each key
    # channel is kda_lower_bound * sigmoid(W_g y + b_g), in (-5, 0);
    # W_g is a full matrix (no_kda_lora)
    log_a = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        einsum("sh,hk->sk", y, lp["wg"], prec) + lp["bg"]
    ).reshape(s, n, d)
    beta = jax.nn.sigmoid(einsum("sh,hn->sn", y, lp["wbeta"], prec))

    def step(state, xs):
        # state (n, d_k, d_v):  S <- (I - b k k^T) Diag(a) S + b k v^T
        # (products and sums written out: exact f32, no matmul unit)
        q_t, k_t, v_t, a_t, b_t = xs
        state = state * jnp.exp(a_t)[:, :, None]
        kS = jnp.sum(k_t[:, :, None] * state, axis=1)
        state = state + k_t[:, :, None] * (
            b_t[:, None] * (v_t - kS))[:, None, :]
        o_t = jnp.sum(q_t[:, :, None] * state, axis=1)
        return state, o_t

    _, o = jax.lax.scan(
        step, jnp.zeros((n, d, d), jnp.float32), (q, k, v, log_a, beta))
    # group_norm_size 1: one RMSNorm a head; head_wise: one gate a head
    gate = jax.nn.sigmoid(einsum("sh,hn->sn", y, lp["wgate"], prec))
    o = rms_norm(o, lp["onorm"], cfg["rms_norm_eps"]) * gate[:, :, None]
    return einsum("sk,kh->sh", o.reshape(s, n * d), lp["wo"], prec)


def rope_rotate(x, pos, theta):
    """Rotary embedding (rotate-half pairing) over the last axis of ``x``
    (S, ..., R) at positions ``pos`` (S,)."""
    r = x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (r,)
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    return x * cos.reshape(shape) + jnp.concatenate(
        [-x2, x1], -1) * sin.reshape(shape)


def mla(lp, y, cfg, prec):
    """Multi-head latent attention, un-absorbed: every head's keys and
    values are rebuilt from the latent; the rotary key is shared by all
    heads.  q_lora_rank is null: q is one full projection."""
    n = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    s = y.shape[0]
    pos = jnp.arange(s)
    q = einsum("sh,hk->sk", y, lp["wq"], prec).reshape(s, n, dn + dr)
    a = einsum("sh,hk->sk", y, lp["wa"], prec)
    c = rms_norm(a[:, :r], lp["anorm"], cfg["rms_norm_eps"])
    kv = einsum("sr,rk->sk", c, lp["wb"], prec).reshape(s, n, dn + dv)
    q_r = rope_rotate(q[..., dn:], pos, cfg["rope_theta"])
    k_r = rope_rotate(a[:, r:], pos, cfg["rope_theta"])
    sc = einsum("qnd,knd->nqk", q[..., :dn], kv[..., :dn], prec) + einsum(
        "qnd,kd->nqk", q_r, k_r, prec)
    sc = sc * (dn + dr) ** -0.5 + jnp.where(
        pos[:, None] >= pos[None, :], 0.0, -1e9)
    o = einsum("nqk,knd->qnd", jax.nn.softmax(sc, -1), kv[..., dn:], prec)
    # assumed: the head-wise output gate holds for MLA layers as for KDA
    gate = jax.nn.sigmoid(einsum("sh,hn->sn", y, lp["wgate"], prec))
    return einsum("sk,kh->sh", (o * gate[:, :, None]).reshape(s, n * dv),
                  lp["wo"], prec)


def route(lp, y, cfg):
    """(S,E) routing weights over ALL experts, zero where not chosen:
    sigmoid scores in f32; selection on score + bias; a group's score is
    the sum of its two best; the best ``topk_group`` groups stay; the top
    ``num_experts_per_tok`` experts within them; weights are the scores
    (without bias) of the chosen, normalised to sum 1 (norm_topk_prob),
    times ``routed_scaling_factor``."""
    e, g = cfg["num_experts"], cfg["n_group"]
    s = jax.nn.sigmoid(jnp.einsum(
        "sh,he->se", y.astype(jnp.float32), lp["router"],
        precision=jax.lax.Precision.HIGHEST))
    sel = s + lp["bias"]
    grp = jnp.sum(jax.lax.top_k(sel.reshape(-1, g, e // g), 2)[0], -1)
    keep = jax.lax.top_k(grp, cfg["topk_group"])[1]
    allowed = jnp.zeros(grp.shape, bool).at[
        jnp.arange(grp.shape[0])[:, None], keep].set(True)
    sel = jnp.where(jnp.repeat(allowed, e // g, axis=1), sel, -jnp.inf)
    idx = jax.lax.top_k(sel, cfg["num_experts_per_tok"])[1]
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], idx].set(True)
    w = jnp.where(chosen, s, 0.0)
    return w / jnp.sum(w, -1, keepdims=True) * cfg["routed_scaling_factor"]


def moe(lp, y, cfg, held, prec, shared=True):
    """The held experts' terms of the routed layer, each applied to every
    token and weighted (zero where the token was not routed to it), plus
    the shared expert.  expert_swiglu_limit / share_expert_swiglu_limit
    are 0 (no clamp) for every layer kept."""
    w = route(lp, y, cfg)
    out = jnp.zeros_like(y, jnp.float32)
    for j, e in enumerate(held):
        out = out + w[:, e:e + 1] * swiglu(
            y, lp["e_gate"][j], lp["e_up"][j], lp["e_down"][j], prec)
    if shared:
        out = out + swiglu(y, lp["s_gate"], lp["s_up"], lp["s_down"], prec)
    return out


def block(lp, x, kind, cfg, held, prec):
    mixer, ffn = kind
    eps = cfg["rms_norm_eps"]
    y = rms_norm(x, lp["norm1"], eps)
    x = x + (kda if mixer == "kda" else mla)(lp, y, cfg, prec)
    y = rms_norm(x, lp["norm2"], eps)
    if ffn == "dense":
        return x + swiglu(y, lp["w_gate"], lp["w_up"], lp["w_down"], prec)
    return x + moe(lp, y, cfg, held, prec)


def logits(p, ids, cfg, held, prec="f32"):
    """``ids`` (S,) int32 -> (S, V_held) float32 logits; position i sees
    tokens 0..i only, so padding after a sequence's end changes nothing
    before it.  ``p``'s leaves may be stored in any float type: each layer
    is upcast when it is used, never the whole tree."""
    up = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a.astype(jnp.float32), t)
    x = up(p["embed"][ids])
    for lp, kind in zip(p["layers"], layer_kinds(cfg)):
        x = block(up(lp), x, kind, cfg, held, prec)
    x = rms_norm(x, up(p["norm_f"]), cfg["rms_norm_eps"])
    # assumed: the head is untied (tie_word_embeddings is not in the
    # published keys; the family's models untie it)
    return einsum("sh,hv->sv", x, up(p["head"]), prec)


__all__ = ["layer_kinds", "logits", "block", "moe", "route", "kda", "mla",
           "rms_norm", "swiglu"]
