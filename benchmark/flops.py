"""Operations and bytes the *algorithm* needs, from shapes alone.

Nothing here reads the program: every count is what the published
architecture has to compute for the given shapes, whatever implements it.
Recomputation (activation checkpointing) is never counted.  A multiply-add
is two operations.
"""

from __future__ import annotations


def encoder_layer_flops_per_token(hidden: int, inter: int, ctx: int) -> float:
    """Forward FLOPs of one transformer layer for one token attending over
    ``ctx`` keys: QKV (2·H·3H), out-proj (2·H·H), MLP (2·2·H·I), QKᵀ and
    PV (2·ctx·H each)."""
    return 8.0 * hidden * hidden + 4.0 * hidden * inter + 4.0 * ctx * hidden


def bert_forward_flops_per_seq(cfg: dict, seq_len: int, k_pred: int) -> float:
    """Forward FLOPs of BERT pre-training on one sequence: the encoder on
    every position, the MLM transform + tied decoder on the K predicted
    positions only, the pooler and the NSP head on [CLS]."""
    h, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    enc = seq_len * cfg["num_hidden_layers"] * encoder_layer_flops_per_token(
        h, i, seq_len
    )
    heads = k_pred * (2.0 * h * h + 2.0 * h * v) + 2.0 * h * h + 4.0 * h
    return enc + heads


def bert_train_flops_per_token(cfg: dict, seq_len: int, k_pred: int) -> float:
    """Forward + backward (2x forward) per input token."""
    return 3.0 * bert_forward_flops_per_seq(cfg, seq_len, k_pred) / seq_len


def gpt_token_flops(cfg: dict, ctx: int, logits: bool) -> float:
    """Forward FLOPs of one token of a GPT-2 shaped decoder whose
    attention reads ``ctx`` keys (itself included); ``logits`` adds the
    tied vocabulary projection (needed for the last prompt position and
    every generated token only)."""
    h, i = cfg["n_embd"], cfg["n_inner"] or 4 * cfg["n_embd"]
    f = cfg["n_layer"] * encoder_layer_flops_per_token(h, i, ctx)
    return f + (2.0 * h * cfg["vocab_size"] if logits else 0.0)


def gpt_prefill_flops(cfg: dict, n_prompt: int) -> float:
    """A causal prompt of n tokens: token p attends p+1 keys; one logits
    row (the last position)."""
    h, i = cfg["n_embd"], cfg["n_inner"] or 4 * cfg["n_embd"]
    dense = n_prompt * cfg["n_layer"] * (8.0 * h * h + 4.0 * h * i)
    attn = cfg["n_layer"] * 4.0 * h * (n_prompt * (n_prompt + 1) / 2.0)
    return dense + attn + 2.0 * h * cfg["vocab_size"]


def gpt_weight_bytes(cfg: dict, bytes_per_param: int) -> float:
    """Bytes of the weights one decode step has to stream: every layer's
    matrices and biases, the final LN and the tied embedding (read once as
    the logits projection; the lookup rows and positions are negligible)."""
    h, i = cfg["n_embd"], cfg["n_inner"] or 4 * cfg["n_embd"]
    per_layer = 3 * h * h + 3 * h + h * h + h + 2 * h * i + i + h + 4 * h
    return bytes_per_param * (
        cfg["n_layer"] * per_layer + 2 * h + cfg["vocab_size"] * h
    )


def gpt_kv_bytes_per_token(cfg: dict, bytes_per_el: int) -> float:
    """K and V rows of one cached position, all layers."""
    return 2.0 * cfg["n_layer"] * cfg["n_embd"] * bytes_per_el


def layer_norm_bytes(rows: int, width: int, in_bytes: int, out_bytes: int,
                     backward: bool) -> float:
    """Forward: read x, write y.  Backward: read x and dy, write dx (the
    affine gradients are ``width``-sized and negligible)."""
    if backward:
        return rows * width * (2.0 * in_bytes + out_bytes)
    return rows * width * float(in_bytes + out_bytes)


def flash_fwd_flops(n_prompt: int, heads: int, head_dim: int) -> float:
    """Causal attention forward over an n-token prompt: QKᵀ and PV on the
    lower triangle (diagonal included)."""
    return 4.0 * heads * head_dim * (n_prompt * (n_prompt + 1) / 2.0)


def paged_decode_bytes(ctx_lens, heads: int, head_dim: int,
                       bytes_per_el: int) -> float:
    """One decode-attention call of one layer: K and V of every live
    context position are read once."""
    return 2.0 * heads * head_dim * bytes_per_el * float(sum(ctx_lens))
