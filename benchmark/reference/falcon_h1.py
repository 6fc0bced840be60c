"""Falcon-H1's decoder — a Mamba-2 state-space mixer and grouped-query
attention side by side in every block — in plain jax.numpy, float32.

Follows tiiuae/Falcon-H1-34B-Instruct's ``config.json`` (the keys named
below) and the layer equations of ISSUE 37, read from the family's public
modelling code; each reading that the published keys do not settle is
marked *assumed* here and listed in the configuration file.  Per block,
with ``u = RMSNorm(h)``:

    h <- h + ssm(u) + attention(u);   h <- h + mlp(RMSNorm(h))

- **ssm**: ``p = (W_in (u * ssm_in_multiplier)) * mu`` split ``z | x B C |
  dt`` (assumed order), ``mu`` = ``ssm_multipliers`` over z, x, B, C, dt
  (assumed); ``x B C`` through a 4-tap causal depthwise convolution with
  bias, then SiLU; per head (32 of 128 channels; the first half of the heads
  on group 0's ``B, C``, the second half on group 1's) the recurrence
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``
  with ``dt = softplus(dt + dt_bias)`` (no clamp: ``time_step_limit`` (0,
  inf), assumed) and ``A = -exp(A_log)``; ``y * SiLU(z)``, RMSNorm inside
  each group's channels, one scale a channel (``mamba_rms_norm``,
  ``mamba_norm_before_gate`` false); ``W_out``, times
  ``ssm_out_multiplier``.
- **attention**: q from ``u * attention_in_multiplier``, k times
  ``key_multiplier``, rotate-half RoPE over the whole head on q and k,
  causal softmax, query head ``i`` on KV head ``i // (heads / kv_heads)``,
  ``W_o``, times ``attention_out_multiplier``.  No biases.
- **mlp**: ``W_down(SiLU(W_gate v * mlp_multipliers[0]) * W_up v) *
  mlp_multipliers[1]``.
- ``h_0 = E[token] * embedding_multiplier``; ``logits = W_head
  RMSNorm(h_L) * lm_head_multiplier``; untied head.

No cache, no batching, no kernels, no chunked form, no imports from the
program: one sequence in, every position's logits out.  The recurrence is
its per-token form under ``lax.scan``, written as products and sums (exact
f32, no matmul unit); the matrix products go through
``benchmark/reference/precision.py`` at the stated precision.

Parameter layout (``p``): ``embed (V,H)``, ``head (H,V)``, ``norm_f (H)``,
``layers``: a list of dicts, one a layer, with ``norm1``, ``norm2`` (H),
``w_in (H, d_ssm + C + n_h)``, ``conv_w (taps, C)``, ``conv_b (C)``,
``dt_bias a_log d (n_h)``, ``ssm_norm (d_ssm)``, ``w_out (d_ssm, H)``,
``wq (H, n*d)``, ``wk wv (H, kv*d)``, ``wo (n*d, H)``, ``w_gate w_up (H,
I)``, ``w_down (I, H)`` — ``C = d_ssm + 2 * n_groups * d_state``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.precision import einsum, rounded

#: the block's branches; ``branches`` below leaves one out for the tests
BRANCHES = ("ssm", "attention")


def rms_norm(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def causal_conv(x, taps, bias):
    """Depthwise causal convolution with bias: y_t = b + sum_i taps[i] *
    x_{t-(K-1)+i}, zeros before the sequence's start.  ``x`` (S,C)."""
    k = taps.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x], 0)
    return bias + sum(taps[i] * xp[i:i + x.shape[0]] for i in range(k))


def rope_rotate(x, pos, theta):
    """Rotary embedding (rotate-half pairing) over the whole last axis of
    ``x`` (S, heads, D) at positions ``pos`` (S,)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def ssm(lp, u, cfg, prec, state_prec="f32"):
    """The Mamba-2 branch over one sequence ``u`` (S,H): the per-token
    recurrence, state in f32, zero at the sequence's start.  ``state_prec``
    is a control: the state rounded to that precision after every token
    (what a cache that held it in bf16 would do)."""
    ds, nh, hd = cfg["mamba_d_ssm"], cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    s = u.shape[0]
    mz, mx, mb, mc, mdt = cfg["ssm_multipliers"]
    p = einsum("sh,hk->sk", u * cfg["ssm_in_multiplier"], lp["w_in"], prec)
    # assumed: in_proj's columns are z | x | B | C | dt
    z = p[:, :ds] * mz
    xbc = jnp.concatenate([
        p[:, ds:2 * ds] * mx, p[:, 2 * ds:2 * ds + g * n] * mb,
        p[:, 2 * ds + g * n:2 * ds + 2 * g * n] * mc], axis=-1)
    dt = p[:, 2 * ds + 2 * g * n:] * mdt
    xbc = jax.nn.silu(causal_conv(xbc, lp["conv_w"], lp["conv_b"]))
    x = xbc[:, :ds].reshape(s, nh, hd)
    # the heads split over the groups in order
    b = jnp.repeat(xbc[:, ds:ds + g * n].reshape(s, g, n), nh // g, axis=1)
    c = jnp.repeat(xbc[:, ds + g * n:].reshape(s, g, n), nh // g, axis=1)
    dt = jax.nn.softplus(dt + lp["dt_bias"])                # (S, n_h)
    a = -jnp.exp(lp["a_log"])

    def step(state, xs):
        # state (n_h, P, N):  S <- exp(dt A) S + (dt x) B^T;  y = S C + D x
        x_t, b_t, c_t, dt_t = xs
        state = state * jnp.exp(dt_t * a)[:, None, None] + (
            (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        state = rounded(state, state_prec)
        y_t = jnp.sum(state * c_t[:, None, :], axis=-1)
        return state, y_t + lp["d"][:, None] * x_t

    _, y = jax.lax.scan(
        step, jnp.zeros((nh, hd, n), jnp.float32), (x, b, c, dt))
    y = y.reshape(s, ds) * jax.nn.silu(z)
    # RMSNorm inside each group's channels, then one scale a channel
    yg = y.reshape(s, g, ds // g)
    yg = yg * jax.lax.rsqrt(
        jnp.mean(yg * yg, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    y = yg.reshape(s, ds) * lp["ssm_norm"]
    return einsum("sk,kh->sh", y, lp["w_out"], prec) * cfg[
        "ssm_out_multiplier"]


def attention(lp, u, cfg, prec):
    """Grouped-query causal attention over one sequence ``u`` (S,H)."""
    n, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    s = u.shape[0]
    pos = jnp.arange(s)
    ua = u * cfg["attention_in_multiplier"]
    q = einsum("sh,hk->sk", ua, lp["wq"], prec).reshape(s, n, d)
    k = (einsum("sh,hk->sk", ua, lp["wk"], prec)
         * cfg["key_multiplier"]).reshape(s, kv, d)
    v = einsum("sh,hk->sk", ua, lp["wv"], prec).reshape(s, kv, d)
    q = rope_rotate(q, pos, float(cfg["rope_theta"]))
    k = rope_rotate(k, pos, float(cfg["rope_theta"]))
    k, v = (jnp.repeat(t, n // kv, axis=1) for t in (k, v))
    sc = einsum("qnd,knd->nqk", q, k, prec) * d ** -0.5 + jnp.where(
        pos[:, None] >= pos[None, :], 0.0, -1e9)
    o = einsum("nqk,knd->qnd", jax.nn.softmax(sc, -1), v, prec)
    return einsum("sk,kh->sh", o.reshape(s, n * d), lp["wo"], prec) * cfg[
        "attention_out_multiplier"]


def mlp(lp, v, cfg, prec):
    m_gate, m_out = cfg["mlp_multipliers"]
    h = jax.nn.silu(einsum("sh,hi->si", v, lp["w_gate"], prec) * m_gate) * \
        einsum("sh,hi->si", v, lp["w_up"], prec)
    return einsum("si,ih->sh", h, lp["w_down"], prec) * m_out


def block(lp, x, cfg, prec, branches=BRANCHES, state_prec="f32"):
    eps = cfg["rms_norm_eps"]
    u = rms_norm(x, lp["norm1"], eps)
    mixed = 0.0
    if "ssm" in branches:
        mixed = mixed + ssm(lp, u, cfg, prec, state_prec)
    if "attention" in branches:
        mixed = mixed + attention(lp, u, cfg, prec)
    x = x + mixed
    return x + mlp(lp, rms_norm(x, lp["norm2"], eps), cfg, prec)


def embed(table, ids, cfg):
    return table[ids].astype(jnp.float32) * cfg["embedding_multiplier"]


def head(norm_f, w, x, cfg, prec):
    """Logits over the columns of ``w`` (a block of the vocabulary or all of
    it)."""
    return einsum("sh,hv->sv", rms_norm(x, norm_f, cfg["rms_norm_eps"]),
                  w, prec) * cfg["lm_head_multiplier"]


def logits(p, ids, cfg, prec="f32", branches=BRANCHES):
    """``ids`` (S,) int32 -> (S, V) float32 logits; position i sees tokens
    0..i only, so padding after a sequence's end changes nothing before it.
    ``p``'s leaves may be stored in any float type: each layer is upcast
    when it is used, never the whole tree."""
    up = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a.astype(jnp.float32), t)
    x = embed(p["embed"], ids, cfg)
    for lp in p["layers"]:
        x = block(up(lp), x, cfg, prec, branches)
    return head(up(p["norm_f"]), up(p["head"]), x, cfg, prec)


__all__ = ["logits", "block", "ssm", "attention", "mlp", "embed", "head",
           "rms_norm", "BRANCHES"]
