"""Operations and bytes of Falcon-H1's decoder (a Mamba-2 state-space mixer
and grouped-query attention side by side in every block, SwiGLU, an untied
head) as the serving programs run it, from shapes alone.

``cfg`` is the configuration file's dict (the published keys).  Nothing
reads the program.  A multiply-add is two operations.  Operations are
counted in the form with the fewest: the state-space mixer as its per-token
recurrence (decay, rank-one update, read-out: 5 a state element), whatever
the chunked prompt form spends.  Bytes are counted at the dtype the
program STREAMS: bf16 weights and K/V rows, the f32 recurrent state and the
f32 small leaves (norm scales, conv taps, per-head scalars).
"""

from __future__ import annotations

BF16, F32 = 2, 4


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"])


def _ssm(cfg):
    """``(d_ssm, conv channels, heads, head width, state size)``."""
    ds, g, n = cfg["mamba_d_ssm"], cfg["mamba_n_groups"], cfg["mamba_d_state"]
    return ds, ds + 2 * g * n, cfg["mamba_n_heads"], cfg["mamba_d_head"], n


# -- parameters (elements) ---------------------------------------------------

def matrix_params(cfg) -> int:
    """One layer's matrices (stored bf16): in_proj, out_proj, q k v o,
    gate up down."""
    h, nq, kv, d = _dims(cfg)
    ds, c, nh, _, _ = _ssm(cfg)
    return (h * (ds + c + nh) + ds * h + h * (nq + 2 * kv) * d + nq * d * h
            + 3 * h * cfg["intermediate_size"])


def small_params(cfg) -> int:
    """One layer's f32 leaves: conv taps and bias, dt_bias, A_log, D, the
    gated norm's scale, the block's two RMSNorm scales."""
    ds, c, nh, _, _ = _ssm(cfg)
    return cfg["mamba_d_conv"] * c + c + 3 * nh + ds + 2 * cfg["hidden_size"]


def weight_bytes(cfg) -> float:
    """Bytes of every weight a decode iteration reads: the layers, the
    final norm and the head — not the embedding table (one row a rider)."""
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    return (BF16 * (layers * matrix_params(cfg) + h * cfg["vocab_size"])
            + F32 * (layers * small_params(cfg) + h))


def held_weight_bytes(cfg) -> float:
    """Every weight the chip holds (the embedding table too)."""
    return weight_bytes(cfg) + BF16 * cfg["hidden_size"] * cfg["vocab_size"]


# -- per-slot and per-token state (bytes) ------------------------------------

def ssm_state_bytes_per_slot(cfg) -> float:
    """One sequence's f32 state-space state over all layers."""
    _, _, nh, hd, n = _ssm(cfg)
    return cfg["num_hidden_layers"] * F32 * nh * hd * n


def state_bytes_per_slot(cfg) -> float:
    """The state and the convolution's last inputs (bf16)."""
    _, c, _, _, _ = _ssm(cfg)
    return ssm_state_bytes_per_slot(cfg) + cfg["num_hidden_layers"] * BF16 * (
        cfg["mamba_d_conv"] - 1) * c


def kv_bytes_per_token(cfg) -> float:
    """K and V rows of one position over all layers, at the KV heads."""
    _, _, kv, d = _dims(cfg)
    return cfg["num_hidden_layers"] * 2 * BF16 * kv * d


# -- operations ---------------------------------------------------------------

def token_flops(cfg, ctx: int, logits: bool) -> float:
    """Forward operations of one token whose attention reads ``ctx`` keys
    (itself included)."""
    h, nq, kv, d = _dims(cfg)
    ds, c, nh, hd, n = _ssm(cfg)
    ssm = (2 * h * (ds + c + nh) + 2 * cfg["mamba_d_conv"] * c
           + 5 * nh * hd * n + 2 * ds * h)
    attn = 2 * h * (nq + 2 * kv) * d + 4 * nq * d * ctx + 2 * nq * d * h
    f = cfg["num_hidden_layers"] * (
        ssm + attn + 6 * h * cfg["intermediate_size"])
    return f + (2.0 * h * cfg["vocab_size"] if logits else 0.0)


def prefill_flops(cfg, n_prompt: int) -> float:
    """A causal prompt: token p attends p + 1 keys; one logits row."""
    _, nq, _, d = _dims(cfg)
    flat = n_prompt * token_flops(cfg, 0, False)
    attn = cfg["num_hidden_layers"] * 4.0 * nq * d * (
        n_prompt * (n_prompt + 1) / 2.0)
    return flat + attn + 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


# -- bytes a step or a kernel has to move --------------------------------------

def ssm_step_bytes(cfg, riders: float) -> float:
    """The state-space decode kernel (`ssm_step_fwd`), all layers of one
    iteration: every rider's f32 state read and written once."""
    return 2.0 * ssm_state_bytes_per_slot(cfg) * riders


def gqa_decode_bytes(cfg, ctx_sum: float) -> float:
    """The paged decode kernel, all layers of one iteration: the K and V
    rows of every live context position read once a KV head (not once a
    query head)."""
    return kv_bytes_per_token(cfg) * ctx_sum


def ssd_chunk_flops(cfg, n_prompt: int) -> float:
    """The prompt recurrence kernel (`ssd_chunk_fwd`), all layers of one
    prompt of ``n_prompt`` real tokens: per head and chunk the carried-in
    state's read-out ``(C e^g) S^T`` (2 C N P) and the state's update
    ``S e^{g_C} + S_own`` (2 P N).  Counted as OPERATIONS, not bytes: the
    kernel's operands are a prompt's intermediates (4 to 33 MB each), which
    XLA:TPU may hand over in VMEM, so no byte of them has to cross HBM
    (PERF.md section 6, PR 37: counted as bytes the share read 103 %)."""
    _, _, nh, hd, n = _ssm(cfg)
    ch = cfg["mamba_chunk_size"]
    chunks = -(-n_prompt // ch)
    return cfg["num_hidden_layers"] * nh * chunks * (
        2.0 * ch * n * hd + 2.0 * hd * n)


def decode_step_bytes(cfg, riders: float, ctx_sum: float,
                      iterations: int = 1) -> float:
    """What one decode program has to move over its ``iterations`` (1, but
    for a decode block): the weights once an iteration, every
    rider-iteration's recurrent state in and out, the live K/V rows
    (``riders`` and ``ctx_sum`` summed over the iterations)."""
    return (iterations * weight_bytes(cfg)
            + 2.0 * state_bytes_per_slot(cfg) * riders
            + gqa_decode_bytes(cfg, ctx_sum))
