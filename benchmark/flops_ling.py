"""Operations and bytes of Ling-3.0-flash's language stack as one chip of
its expert-parallel group runs it, from shapes alone.

``cfg`` is the configuration file's dict (the published keys; ``num_experts``
there is the number HELD, ``num_experts_published`` what the router
scores).  Nothing reads the program.  A multiply-add is two operations.

What a chip does not do is not counted: the experts it does not hold, the
vocabulary columns it does not hold.  The routed experts' share depends on
where the router sends the tokens, so every function that counts it takes
that as an argument — ``pairs``, the (token, expert) pairs that landed on
held experts, or ``touched``, the distinct held experts a step streamed —
and the harness hands over what the program's counters measured
(``serve/moe/routed_local_tokens``, ``serve/moe/experts_touched``): a share
of a roofline built on the expected value instead could read over 100 %
in a step that happened to touch fewer.

Attention is counted in the form with the fewest operations (MLA
un-absorbed: scores over ``qk_nope + qk_rope`` lanes, values over
``v_head_dim``), bytes as stored (a cached latent row is 640 lanes of
bf16, 576 of them used; the recurrent state f32).
"""

from __future__ import annotations

BF16, F32 = 2, 4
LANES = 128


def layer_kinds(cfg):
    return [
        ("mla" if (i + 1) % cfg["layer_group_size"] == 0 else "kda",
         "dense" if i < cfg["first_k_dense_replace"] else "moe")
        for i in range(cfg["num_hidden_layers"])
    ]


def _count(cfg, what):
    return sum(1 for k in layer_kinds(cfg) if what in k)


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"])


def latent_row_lanes(cfg) -> int:
    used = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return -(-used // LANES) * LANES


# -- parameters (elements) ---------------------------------------------------

def kda_params(cfg) -> int:
    h, n, d = _dims(cfg)
    taps = cfg["short_conv_kernel_size"]
    return (h * 4 * n * d + n * d + taps * 3 * n * d + 2 * h * n + d
            + n * d * h)


def mla_params(cfg) -> int:
    h, n, _ = _dims(cfg)
    dn, dr, dv, r = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"], cfg["kv_lora_rank"])
    return (h * n * (dn + dr) + h * (r + dr) + r + r * n * (dn + dv) + h * n
            + n * dv * h)


def expert_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_weight_bytes(cfg) -> float:
    """Bytes of everything a decode step reads whatever the routing: the
    mixers, the dense FFNs, the shared experts, the routers (f32), the norms
    (f32) and the held head — not the routed experts, not the embedding
    table (a step reads one row a rider)."""
    h = cfg["hidden_size"]
    n_moe = _count(cfg, "moe")
    bf16 = (
        _count(cfg, "kda") * kda_params(cfg)
        + _count(cfg, "mla") * mla_params(cfg)
        + _count(cfg, "dense") * 3 * h * cfg["intermediate_size"]
        + n_moe * 3 * h * cfg["moe_shared_expert_intermediate_size"]
        + h * cfg["vocab_size"]
    )
    f32 = n_moe * (h * cfg["num_experts_published"]
                   + cfg["num_experts_published"]) \
        + (2 * cfg["num_hidden_layers"] + 1) * h
    return BF16 * bf16 + F32 * f32


def held_weight_bytes(cfg) -> float:
    """Every weight the chip holds (the embedding table too)."""
    return dense_weight_bytes(cfg) + BF16 * (
        cfg["hidden_size"] * cfg["vocab_size"]
        + _count(cfg, "moe") * cfg["num_experts"] * expert_params(cfg)
    )


# -- per-slot and per-token state (bytes) ------------------------------------

def state_bytes_per_slot(cfg) -> float:
    """One sequence's recurrent state over all KDA layers: the f32 matrix
    of every head, and the convolution's last inputs (bf16)."""
    _, n, d = _dims(cfg)
    taps = cfg["short_conv_kernel_size"]
    return _count(cfg, "kda") * (
        F32 * n * d * d + BF16 * (taps - 1) * 3 * n * d)


def latent_bytes_per_token(cfg) -> float:
    return _count(cfg, "mla") * BF16 * latent_row_lanes(cfg)


# -- operations ---------------------------------------------------------------

def token_flops(cfg, ctx: int, logits: bool, pairs_per_layer: float) -> float:
    """Forward operations of one token whose latent attention reads ``ctx``
    keys (itself included) and of whose routed (token, expert) pairs
    ``pairs_per_layer`` landed on held experts in each routed layer."""
    h, n, d = _dims(cfg)
    dn, dr, dv, r = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"], cfg["kv_lora_rank"])
    taps = cfg["short_conv_kernel_size"]
    kda = (2 * h * 4 * n * d + 4 * h * n + 2 * taps * 3 * n * d
           + 7 * n * d * d + 2 * n * d * h)
    mla = (2 * h * n * (dn + dr) + 2 * h * (r + dr) + 2 * r * n * (dn + dv)
           + 2 * n * (dn + dr) * ctx + 2 * n * dv * ctx + 2 * h * n
           + 2 * n * dv * h)
    moe = (2 * h * cfg["num_experts_published"]
           + 6 * h * cfg["moe_shared_expert_intermediate_size"]
           + 6 * h * cfg["moe_intermediate_size"] * pairs_per_layer)
    f = (_count(cfg, "kda") * kda + _count(cfg, "mla") * mla
         + _count(cfg, "dense") * 6 * h * cfg["intermediate_size"]
         + _count(cfg, "moe") * moe)
    return f + (2.0 * h * cfg["vocab_size"] if logits else 0.0)


def prefill_flops(cfg, n_prompt: int, pairs_per_layer: float) -> float:
    """A causal prompt: token p attends p + 1 keys; one logits row."""
    flat = n_prompt * token_flops(cfg, 0, False, pairs_per_layer)
    n = cfg["num_attention_heads"]
    attn = _count(cfg, "mla") * 2.0 * n * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    ) * (n_prompt * (n_prompt + 1) / 2.0)
    return flat + attn + 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def expected_pairs_per_layer(cfg) -> float:
    """(token, expert) pairs of one token that land on held experts if the
    router spreads evenly: top-k times the share of experts held."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] / \
        cfg["num_experts_published"]


# -- bytes a step or a kernel has to move --------------------------------------

def expert_stream_bytes(cfg, touched: float) -> float:
    """``touched`` distinct (layer, held expert) streamed once each."""
    return BF16 * expert_params(cfg) * touched


def kda_step_bytes(cfg, riders: float) -> float:
    """The KDA decode kernel, all layers of one step: every live slot's f32
    state read and written once."""
    _, n, d = _dims(cfg)
    return _count(cfg, "kda") * riders * 2.0 * F32 * n * d * d


def mla_decode_bytes(cfg, ctx_sum: float) -> float:
    """The latent decode kernel, all layers of one step: one cached row for
    every live context position."""
    return latent_bytes_per_token(cfg) * ctx_sum


def moe_grouped_bytes(cfg, touched: float, pairs: float) -> float:
    """The grouped expert matmul: the touched experts' matrices once, the
    routed rows in and out (bf16)."""
    return expert_stream_bytes(cfg, touched) + \
        2.0 * BF16 * cfg["hidden_size"] * pairs


def decode_step_bytes(cfg, riders: float, ctx_sum: float,
                      touched: float, iterations: int = 1) -> float:
    """What one decode program has to move over its ``iterations`` (1, but
    for a decode block): the unrouted weights once an iteration, the
    experts touched, every rider-iteration's recurrent state in and out,
    the live latent rows (``riders``, ``ctx_sum`` and ``touched`` summed
    over the iterations)."""
    return (iterations * dense_weight_bytes(cfg)
            + expert_stream_bytes(cfg, touched)
            + 2.0 * state_bytes_per_slot(cfg) * riders
            + mla_decode_bytes(cfg, ctx_sum))
