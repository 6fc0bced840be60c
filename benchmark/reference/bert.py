"""BERT pre-training (MLM on K gathered positions + NSP), plain jax.numpy.

Follows Devlin et al. 2018 and google-bert/bert-large-uncased's
``config.json``: post-LN encoder, learned positions and token types, MLM
transform (dense, GELU, LN) with the decoder tied to the word embeddings,
pooler (dense, tanh on [CLS]) and a 2-way NSP head.  Departures, both what
the configuration file states the recipe runs: tanh-approximated GELU,
dropout off.  float32 throughout; no kernels, no sharding, no imports from
the program.  Layers are scanned and rematerialised so that a micro-batch
of full-width f32 activations fits beside the weights.

Parameter layout (a flat dict; stacked leaves lead with the layer axis):
  word (V,H)  pos (P,H)  type (T,H)  emb_ln_g/b (H)
  qkv_w (L,H,3H) qkv_b (L,3H)   columns ordered (head, {q,k,v}, head_dim)
  out_w (L,H,H) out_b (L,H)  ln1_g/b (L,H)
  fc1_w (L,H,I) fc1_b (L,I)  fc2_w (L,I,H) fc2_b (L,H)  ln2_g/b (L,H)
  mlm_w (H,H) mlm_b (H)  mlm_ln_g/b (H)  mlm_bias (V)
  pool_w (H,H) pool_b (H)  nsp_w (H,2) nsp_b (2)
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.precision import einsum

LAYER_KEYS = (
    "qkv_w", "qkv_b", "out_w", "out_b", "ln1_g", "ln1_b",
    "fc1_w", "fc1_b", "fc2_w", "fc2_b", "ln2_g", "ln2_b",
)


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)
    ))


def _layer(x, lp, bias, heads, eps, prec):
    b, s, h = x.shape
    d = h // heads
    qkv = einsum("bsh,hk->bsk", x, lp["qkv_w"], prec) + lp["qkv_b"]
    qkv = qkv.reshape(b, s, heads, 3, d)
    q, k, v = qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]
    sc = einsum("bqnd,bknd->bnqk", q, k, prec) * (d ** -0.5) + bias
    p = jax.nn.softmax(sc, axis=-1)
    ctx = einsum("bnqk,bknd->bqnd", p, v, prec).reshape(b, s, h)
    attn = einsum("bsh,hk->bsk", ctx, lp["out_w"], prec) + lp["out_b"]
    x = layer_norm(x + attn, lp["ln1_g"], lp["ln1_b"], eps)
    y = gelu_tanh(einsum("bsh,hi->bsi", x, lp["fc1_w"], prec) + lp["fc1_b"])
    y = einsum("bsi,ih->bsh", y, lp["fc2_w"], prec) + lp["fc2_b"]
    return layer_norm(x + y, lp["ln2_g"], lp["ln2_b"], eps)


def loss_sums(p, batch, cfg, prec="f32"):
    """(sum of weighted MLM losses, sum of NSP losses) over the rows of
    ``batch`` — sums, so that micro-batches add up to the whole batch.

    ``batch`` is batch-first: input_ids, token_type_ids (B,S);
    attention_mask (B,S); mlm_positions, mlm_label_ids, mlm_weights (B,K);
    nsp_labels (B,)."""
    eps, heads = cfg["layer_norm_eps"], cfg["num_attention_heads"]
    ids = batch["input_ids"]
    s = ids.shape[1]
    x = p["word"][ids] + p["pos"][:s][None] + p["type"][batch["token_type_ids"]]
    x = layer_norm(x, p["emb_ln_g"], p["emb_ln_b"], eps)
    bias = jnp.where(batch["attention_mask"] > 0, 0.0, -1e9)[:, None, None, :]

    @jax.checkpoint
    def body(x, lp):
        return _layer(x, lp, bias, heads, eps, prec), None

    x, _ = jax.lax.scan(body, x, {k: p[k] for k in LAYER_KEYS})

    pooled = jnp.tanh(
        einsum("bh,hk->bk", x[:, 0], p["pool_w"], prec) + p["pool_b"]
    )
    nsp_logits = einsum("bh,hk->bk", pooled, p["nsp_w"], prec) + p["nsp_b"]
    nsp_lp = jax.nn.log_softmax(nsp_logits, axis=-1)
    nsp = -jnp.take_along_axis(nsp_lp, batch["nsp_labels"][:, None], axis=-1)

    rows = jnp.take_along_axis(x, batch["mlm_positions"][:, :, None], axis=1)
    t = gelu_tanh(einsum("bkh,hj->bkj", rows, p["mlm_w"], prec) + p["mlm_b"])
    t = layer_norm(t, p["mlm_ln_g"], p["mlm_ln_b"], eps)
    logits = einsum("bkh,vh->bkv", t, p["word"], prec) + p["mlm_bias"]
    lp = jax.nn.log_softmax(logits, axis=-1)
    tok = -jnp.take_along_axis(
        lp, batch["mlm_label_ids"][:, :, None], axis=-1
    )[..., 0]
    return jnp.sum(tok * batch["mlm_weights"]), jnp.sum(nsp)


def loss_and_grad(p, batch, cfg, *, micro: int, prec="f32"):
    """Mean MLM loss over the batch's weighted predictions + mean NSP loss,
    and its gradient, accumulated over micro-batches of ``micro`` rows."""
    n = batch["input_ids"].shape[0]
    if n % micro:
        raise ValueError(f"micro-batch {micro} does not divide batch {n}")
    count = jnp.maximum(jnp.sum(batch["mlm_weights"]), 1.0)

    def part(p, mb):
        mlm, nsp = loss_sums(p, mb, cfg, prec)
        return mlm / count + nsp / n

    split = jax.tree_util.tree_map(
        lambda a: a.reshape((n // micro, micro) + a.shape[1:]), batch
    )

    def step(carry, mb):
        loss, grad = carry
        l, g = jax.value_and_grad(part)(p, mb)
        return (loss + l, jax.tree_util.tree_map(jnp.add, grad, g)), None

    zero = jax.tree_util.tree_map(jnp.zeros_like, p)
    (loss, grad), _ = jax.lax.scan(step, (jnp.float32(0.0), zero), split)
    return loss, grad
