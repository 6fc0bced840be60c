"""Operations and bytes of GLM-4.7-Flash's decoder stack as the
``glm-4.7-flash-5l`` configuration runs it, from shapes alone.

``cfg`` is the configuration file's dict (the published keys).  Nothing
reads the program.  A multiply-add is two operations.  Every expert of a
layer is held here (64 of 64); a routed layer's experts are counted at
``num_experts_per_tok`` a token (top-k routing sends every token to that
many, whatever the bias).

Attention is counted in the un-absorbed form, whatever form the program
runs: scores over ``qk_nope + qk_rope`` lanes and values over
``v_head_dim``, each query against every key before it and itself, and the
latent's up-projection (``kv_b``) once a token — so that a later change of
form cannot read a kernel's share above 100 %.  Bytes are as stored: a
cached latent row is 640 lanes of bf16 (576 of them used).
"""

from __future__ import annotations

BF16, F32 = 2, 4
LANES = 128


def layer_kinds(cfg):
    return [("mla", "dense" if i < cfg["first_k_dense_replace"] else "moe")
            for i in range(cfg["num_hidden_layers"])]


def _count(cfg, what):
    return sum(1 for k in layer_kinds(cfg) if what in k)


def latent_row_lanes(cfg) -> int:
    used = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return -(-used // LANES) * LANES


def _heads(cfg):
    return (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"])


# -- parameters (elements) ---------------------------------------------------

def mla_params(cfg) -> int:
    h, q, r = cfg["hidden_size"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    n, dn, dr, dv = _heads(cfg)
    return (h * q + q + q * n * (dn + dr) + h * (r + dr) + r
            + r * n * (dn + dv) + n * dv * h)


def expert_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def weight_bytes(cfg) -> float:
    """Every weight held (the embedding table too): bf16, but for the
    routers, their biases and the norms (f32)."""
    h, e = cfg["hidden_size"], cfg["n_routed_experts"]
    n_moe = _count(cfg, "moe")
    bf16 = (
        cfg["num_hidden_layers"] * mla_params(cfg)
        + _count(cfg, "dense") * 3 * h * cfg["intermediate_size"]
        + n_moe * (e + cfg["n_shared_experts"]) * expert_params(cfg)
        + 2 * h * cfg["vocab_size"]
    )
    f32 = n_moe * (h * e + e) + (2 * cfg["num_hidden_layers"] + 1) * h
    return BF16 * bf16 + F32 * f32


def latent_bytes_per_token(cfg) -> float:
    return _count(cfg, "mla") * BF16 * latent_row_lanes(cfg)


# -- operations ---------------------------------------------------------------

def _attn_per_key(cfg) -> float:
    """Scores and values of one query against one key, all heads."""
    n, dn, dr, dv = _heads(cfg)
    return 2.0 * n * (dn + dr + dv)


def flat_token_flops(cfg) -> float:
    """Forward operations of one token outside attention's scores and
    values (no logits)."""
    h, q, r = cfg["hidden_size"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    n, dn, dr, dv = _heads(cfg)
    mla = 2.0 * (h * q + q * n * (dn + dr) + h * (r + dr)
                 + r * n * (dn + dv) + n * dv * h)
    moe = (2.0 * h * cfg["n_routed_experts"]
           + 2.0 * expert_params(cfg)
           * (cfg["num_experts_per_tok"] + cfg["n_shared_experts"]))
    return (cfg["num_hidden_layers"] * mla
            + _count(cfg, "dense") * 6.0 * h * cfg["intermediate_size"]
            + _count(cfg, "moe") * moe)


def logits_flops(cfg) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def token_flops(cfg, ctx: int, logits: bool) -> float:
    """One token whose attention reads ``ctx`` keys (itself included)."""
    return (flat_token_flops(cfg)
            + _count(cfg, "mla") * _attn_per_key(cfg) * ctx
            + (logits_flops(cfg) if logits else 0.0))


def chunk_attention_flops(cfg, n: int, offset: int) -> float:
    """A prefill chunk's scores and values, all layers: its ``n`` real
    queries at positions ``offset ...``, query ``t`` against ``offset + t +
    1`` keys."""
    keys = n * offset + n * (n + 1) / 2.0
    return _count(cfg, "mla") * _attn_per_key(cfg) * keys


def chunk_flops(cfg, n: int, offset: int) -> float:
    """A prefill chunk of ``n`` real tokens at ``offset``, one logits row."""
    return (n * flat_token_flops(cfg) + chunk_attention_flops(cfg, n, offset)
            + logits_flops(cfg))


def prefill_flops(cfg, n_prompt: int) -> float:
    """A whole prompt as one chunk at offset 0."""
    return chunk_flops(cfg, n_prompt, 0)


# -- bytes a kernel has to move ------------------------------------------------

def mla_decode_bytes(cfg, ctx_sum: float) -> float:
    """The latent decode kernel, all layers of one step: one cached row for
    every live context position."""
    return latent_bytes_per_token(cfg) * ctx_sum
