"""GPT-2 forward pass, plain jax.numpy, float32.

Follows Radford et al. 2019 and openai-community/gpt2-large's
``config.json``: learned positions, pre-LN blocks (LN, causal multi-head
attention, residual; LN, MLP with the tanh GELU ``gelu_new``, residual),
final LN, logits through the tied token embedding.  No cache, no
batching, no kernels, no imports from the program: one sequence in, every
position's logits out.

Parameter layout (flat dict; stacked leaves lead with the layer axis):
  wte (V,H)  wpe (P,H)  lnf_g/b (H)
  ln1_g/b (L,H)  qkv_w (L,H,3H) qkv_b (L,3H)   columns ordered
  (head, {q,k,v}, head_dim)   out_w (L,H,H) out_b (L,H)
  ln2_g/b (L,H)  fc1_w (L,H,I) fc1_b (L,I)  fc2_w (L,I,H) fc2_b (L,H)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.bert import gelu_tanh, layer_norm
from benchmark.reference.precision import einsum

LAYER_KEYS = (
    "ln1_g", "ln1_b", "qkv_w", "qkv_b", "out_w", "out_b",
    "ln2_g", "ln2_b", "fc1_w", "fc1_b", "fc2_w", "fc2_b",
)


def logits(p, ids, cfg, prec="f32"):
    """``ids`` (S,) int32 -> (S, V) float32 logits; position i sees tokens
    0..i only, so padding after a sequence's end changes nothing before."""
    heads, eps = cfg["n_head"], cfg["layer_norm_epsilon"]
    s = ids.shape[0]
    x = p["wte"][ids] + p["wpe"][:s]
    h = x.shape[-1]
    d = h // heads
    causal = jnp.where(
        jnp.arange(s)[:, None] >= jnp.arange(s)[None, :], 0.0, -1e9
    )

    def body(x, lp):
        y = layer_norm(x, lp["ln1_g"], lp["ln1_b"], eps)
        qkv = einsum("sh,hk->sk", y, lp["qkv_w"], prec) + lp["qkv_b"]
        qkv = qkv.reshape(s, heads, 3, d)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        sc = einsum("qnd,knd->nqk", q, k, prec) * (d ** -0.5) + causal
        ctx = einsum("nqk,knd->qnd", jax.nn.softmax(sc, axis=-1), v, prec)
        x = x + einsum("sh,hk->sk", ctx.reshape(s, h), lp["out_w"], prec) \
            + lp["out_b"]
        y = layer_norm(x, lp["ln2_g"], lp["ln2_b"], eps)
        y = gelu_tanh(einsum("sh,hi->si", y, lp["fc1_w"], prec) + lp["fc1_b"])
        return x + einsum("si,ih->sh", y, lp["fc2_w"], prec) + lp["fc2_b"], None

    x, _ = jax.lax.scan(body, x, {k: p[k] for k in LAYER_KEYS})
    x = layer_norm(x, p["lnf_g"], p["lnf_b"], eps)
    return einsum("sh,vh->sv", x, p["wte"], prec)
