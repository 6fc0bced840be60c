"""GPT — the tensor-parallel decoder block benchmark (BASELINE config #5).

≙ ``apex/transformer/testing/standalone_gpt.py`` (the reference's GPT
fixture) — a Megatron-style pre-LN causal decoder built from the same
apex_tpu parts as BERT: Column/Row parallel projections, Pallas flash
attention (causal), fused RoPE, fused LayerNorm, vocab-parallel CE.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from apex_tpu import parallel_state as ps
from apex_tpu.models.bert import _LayerNorm
from apex_tpu.ops.attention import flash_attention
from apex_tpu.ops.rope import fused_apply_rotary_pos_emb_cached
from apex_tpu.transformer.tensor_parallel import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
    vocab_parallel_cross_entropy,
)
from apex_tpu.transformer.tensor_parallel.layers import _tp_world
from apex_tpu.transformer.tensor_parallel.mappings import (
    copy_to_tensor_model_parallel_region,
    gather_from_sequence_parallel_region,
)
from apex_tpu.transformer.tensor_parallel.utils import divide

__all__ = ["GptConfig", "GptBlock", "GptModel", "gpt_lm_loss",
           "gpt_lm_loss_cp"]

_TP = ps.TENSOR_PARALLEL_AXIS
_CP = ps.CONTEXT_PARALLEL_AXIS


def _cp_world(cfg) -> int:
    """Bound cp-axis size when context parallelism is configured, else 1."""
    if cfg.context_parallel and ps.axis_is_bound(_CP):
        return jax.lax.axis_size(_CP)
    return 1


def _cp_shard_rows(table, cfg, s_local):
    """This cp rank's ``s_local`` rows of a GLOBAL per-position table
    (RoPE cos/sin, learned position embeddings).  Contiguous layout:
    rows [rank·s_local, ...).  Zigzag ("ring_zigzag"): the concatenation
    of global chunks ``rank`` and ``2cp−1−rank`` (chunk = s_local/2
    rows), matching :func:`context_parallel.zigzag_split`."""
    rank = jax.lax.axis_index(_CP)
    if cfg.context_parallel == "ring_zigzag":
        from apex_tpu.transformer.context_parallel import zigzag_shard

        cp = jax.lax.axis_size(_CP)
        # chunk math runs on the GLOBAL SEQUENCE (cp·s_local rows), not
        # the full table — a learned-position table longer than the
        # sequence (max_seq_len > S) must be trimmed first
        return zigzag_shard(table[: cp * s_local], rank, cp, axis=0)
    return jax.lax.dynamic_slice_in_dim(table, rank * s_local, s_local, 0)


def _rope_cos_sin(seq_len: int, dim: int, base: float = 10000.0):
    """Cached cos/sin tables (S, D) in the rotate_half (GPT-NeoX) layout
    the fused RoPE kernel expects."""
    inv = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    freqs = jnp.outer(jnp.arange(seq_len, dtype=jnp.float32), inv)
    emb = jnp.concatenate((freqs, freqs), axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


@dataclasses.dataclass(frozen=True)
class GptConfig:
    vocab_size: int = 50304
    hidden_size: int = 1024
    num_layers: int = 12
    num_heads: int = 16
    intermediate_size: int = 4096
    max_seq_len: int = 2048
    layer_norm_eps: float = 1e-5
    rotary: bool = True
    dtype: Any = jnp.bfloat16
    sequence_parallel: bool = False
    # Context parallelism (long-context attention over the cp mesh axis,
    # apex_tpu.transformer.context_parallel): None, "ring" (ppermute'd KV
    # blocks, O(S_local) memory), "ring_zigzag" (same ring with the
    # causal-load-balanced zigzag layout: this rank's S/cp rows are
    # global chunks [rank; 2cp-1-rank] — shard inputs with
    # context_parallel.zigzag_split) or "ulysses" (head<->sequence
    # all-to-all).  The model's sequence inputs are then the cp rank's
    # S/cp shard; RoPE/positions index GLOBAL positions in either layout.
    # Mutually exclusive with sequence_parallel (the sequence dim is
    # already sharded).  Gradients: treat cp like a data axis — pmean
    # over cp alongside dp (every param's grad covers only local tokens'
    # paths); use gpt_lm_loss_cp for the shifted next-token loss across
    # shard boundaries.
    context_parallel: Optional[str] = None
    remat: bool = False
    # Per-layer checkpoint policy when remat=True — same taxonomy as
    # BertConfig: "full" recomputes everything, "dots" saves no-batch-dim
    # matmul outputs, "sums" saves only the gpt_{qkv,fc1,sum_attn,
    # sum_mlp} named tags (epilogue-fusion friendly: every raw matmul
    # output stays single-consumer).
    remat_policy: str = "full"
    # MoE: num_experts > 0 replaces the dense MLP with a SwitchMoe block
    # (experts sharded over the dp/ep axis, apex_tpu.transformer.moe); the
    # per-layer aux losses are sown into the "losses" collection and folded
    # into gpt_lm_loss with moe_aux_coef.
    num_experts: int = 0
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_aux_coef: float = 0.01

    def __post_init__(self):
        if self.context_parallel not in (None, "ring", "ring_zigzag",
                                         "ulysses"):
            raise ValueError(
                f"context_parallel must be None, 'ring', 'ring_zigzag' "
                f"or 'ulysses', got {self.context_parallel!r}"
            )
        if self.context_parallel and self.sequence_parallel:
            raise ValueError(
                "context_parallel and sequence_parallel are mutually "
                "exclusive: both shard the sequence dimension"
            )
        if self.remat_policy not in ("full", "dots", "sums"):
            raise ValueError(
                f"unknown remat_policy {self.remat_policy!r} "
                "(expected 'full', 'dots' or 'sums')"
            )


class GptBlock(nn.Module):
    """Pre-LN decoder block: x + attn(LN(x)); x + mlp(LN(x))."""

    cfg: GptConfig

    @nn.compact
    def __call__(self, x, *, deterministic=True):
        cfg = self.cfg
        h = cfg.hidden_size
        world = _tp_world(_TP)
        heads_local = divide(cfg.num_heads, world)
        head_dim = divide(h, cfg.num_heads)

        y = _LayerNorm(
            h, cfg.layer_norm_eps,
            sequence_parallel=cfg.sequence_parallel, name="ln_attn",
        )(x)
        qkv = ColumnParallelLinear(
            h, 3 * h, gather_output=False,
            sequence_parallel_enabled=cfg.sequence_parallel,
            dtype=cfg.dtype, name="qkv",
        )(y)
        # inert unless remat_policy="sums" selects it by name (the same
        # epilogue-fusion-friendly save set as the BERT blocks)
        qkv = checkpoint_name(qkv, "gpt_qkv")
        s, b = qkv.shape[0], qkv.shape[1]
        # per-head-interleaved (heads, 3, head_dim) column layout — see
        # BertSelfAttention: required for tp-invariant column sharding
        qkv = qkv.reshape(s, b, heads_local, 3, head_dim)
        q, k, v = (
            jnp.transpose(qkv[:, :, :, i], (1, 2, 0, 3)) for i in range(3)
        )
        cp = _cp_world(cfg)
        if cfg.rotary:
            # under cp, s is the LOCAL shard: RoPE must use the global
            # positions of this rank's shard (contiguous [rank·s, ...),
            # or the two zigzag chunks)
            cos, sin = _rope_cos_sin(s * cp, head_dim)
            if cp > 1:
                cos, sin = (
                    _cp_shard_rows(t, cfg, s) for t in (cos, sin)
                )
            q = fused_apply_rotary_pos_emb_cached(q, cos, sin)
            k = fused_apply_rotary_pos_emb_cached(k, cos, sin)
        if cp > 1:
            from apex_tpu.transformer.context_parallel import (
                ring_attention,
                ulysses_attention,
            )

            if cfg.context_parallel == "ulysses":
                ctx = ulysses_attention(
                    q, k, v, causal=True, scale=head_dim**-0.5
                )
            else:
                ctx = ring_attention(
                    q, k, v, causal=True, scale=head_dim**-0.5,
                    layout=(
                        "zigzag"
                        if cfg.context_parallel == "ring_zigzag"
                        else "contiguous"
                    ),
                )
        else:
            ctx = flash_attention(q, k, v, causal=True, scale=head_dim**-0.5)
        ctx = jnp.transpose(ctx, (2, 0, 1, 3)).reshape(s, b, heads_local * head_dim)
        attn = RowParallelLinear(
            h, h, input_is_parallel=True,
            sequence_parallel_enabled=cfg.sequence_parallel,
            dtype=cfg.dtype, name="out",
        )(ctx)
        x = checkpoint_name(x + attn, "gpt_sum_attn")

        y = _LayerNorm(
            h, cfg.layer_norm_eps,
            sequence_parallel=cfg.sequence_parallel, name="ln_mlp",
        )(x)
        if cfg.num_experts:
            from apex_tpu.transformer.moe import MoeConfig, SwitchMoe

            # Routing happens on this rank's local (possibly SP-sharded)
            # tokens; expert weights shard over dp/ep and are replicated
            # across tp.  Without SP at tp > 1 the full sequence is routed
            # identically on every tp rank (correct, redundant) — enable
            # sequence_parallel to split that work.
            # NOTE: the aux coefficient has ONE owner — gpt_lm_loss
            # applies cfg.moe_aux_coef; SwitchMoe returns the raw aux.
            y, aux = SwitchMoe(
                MoeConfig(
                    hidden_size=h,
                    ffn_hidden_size=cfg.intermediate_size,
                    num_experts=cfg.num_experts,
                    top_k=cfg.moe_top_k,
                    capacity_factor=cfg.moe_capacity_factor,
                    dtype=cfg.dtype,
                    sequence_parallel=cfg.sequence_parallel,
                    context_parallel=bool(cfg.context_parallel),
                ),
                name="moe",
            )(y)
            self.sow("losses", "moe_aux", aux)
        else:
            y = ColumnParallelLinear(
                h, cfg.intermediate_size, gather_output=False,
                sequence_parallel_enabled=cfg.sequence_parallel,
                dtype=cfg.dtype, name="fc1",
            )(y)
            y = checkpoint_name(y, "gpt_fc1")
            y = jax.nn.gelu(y, approximate=True)
            y = RowParallelLinear(
                cfg.intermediate_size, h, input_is_parallel=True,
                sequence_parallel_enabled=cfg.sequence_parallel,
                dtype=cfg.dtype, name="fc2",
            )(y)
        return checkpoint_name(x + y, "gpt_sum_mlp")


class _GptStep(nn.Module):
    cfg: GptConfig
    deterministic: bool

    @nn.compact
    def __call__(self, x):
        return GptBlock(self.cfg, name="block")(
            x, deterministic=self.deterministic
        ), None


class GptModel(nn.Module):
    """Embedding + scanned decoder stack + final LN.  Seq-first (S, B)."""

    cfg: GptConfig

    @nn.compact
    def __call__(self, input_ids, *, deterministic=True):
        cfg = self.cfg
        x = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size,
            sequence_parallel_enabled=cfg.sequence_parallel,
            dtype=cfg.dtype, name="word_embeddings",
        )(input_ids)
        if not cfg.rotary:
            pos = self.param(
                "position_embeddings",
                nn.initializers.normal(stddev=0.02),
                (cfg.max_seq_len, cfg.hidden_size),
            )
            start = 0
            rows = None
            if cfg.sequence_parallel and _tp_world(_TP) > 1:
                # x is the SP seq shard [rank·S/tp, (rank+1)·S/tp): slice
                # the matching positions, and mark the table tp-partial.
                # Guard the table size: dynamic_slice CLAMPS out-of-range
                # starts, which would silently reuse rows on high ranks.
                tp = _tp_world(_TP)
                if tp * x.shape[0] > cfg.max_seq_len:
                    raise ValueError(
                        f"global sequence tp*S_local = {tp}*{x.shape[0]} "
                        f"exceeds max_seq_len ({cfg.max_seq_len})"
                    )
                start = jax.lax.axis_index(_TP) * x.shape[0]
                ps.register_sequence_parallel_param(
                    self.path + ("position_embeddings",)
                )
            elif _cp_world(cfg) > 1:
                # cp shard: global positions of this rank's shard
                # (contiguous or zigzag); grads need no marking — cp is
                # synced like a data axis (pmean).  The global length
                # must fit the table: dynamic_slice CLAMPS out-of-range
                # starts, which would silently reuse the last rows on
                # high ranks instead of failing.
                cp = _cp_world(cfg)
                if cp * x.shape[0] > cfg.max_seq_len:
                    raise ValueError(
                        f"global sequence cp*S_local = {cp}*{x.shape[0]} "
                        f"exceeds max_seq_len ({cfg.max_seq_len})"
                    )
                rows = _cp_shard_rows(pos, cfg, x.shape[0])
            if rows is None:
                rows = jax.lax.dynamic_slice_in_dim(
                    pos, start, x.shape[0], 0
                )
            x = x + rows[:, None, :].astype(cfg.dtype)
        step = _GptStep
        if cfg.remat:
            from apex_tpu.transformer.pipeline_parallel.schedules import (
                resolve_remat_policy,
            )

            step = nn.remat(
                step, prevent_cse=False,
                policy=resolve_remat_policy(cfg.remat_policy),
            )
        scanned = nn.scan(
            step,
            variable_axes={"params": 0, "losses": 0},
            split_rngs={"params": True, "dropout": True},
            length=cfg.num_layers,
            metadata_params={nn.PARTITION_NAME: "layers"},
        )
        x, _ = scanned(cfg, deterministic, name="layers")(x)
        x = _LayerNorm(
            cfg.hidden_size, cfg.layer_norm_eps,
            sequence_parallel=cfg.sequence_parallel, name="ln_f",
        )(x)
        if cfg.sequence_parallel and _tp_world(_TP) > 1:
            x = gather_from_sequence_parallel_region(x)
        return x


def _apply_with_moe_aux(params, model: GptModel, input_ids, deterministic):
    """Model forward returning ``(h, aux_total)``.

    For MoE configs this strips any "losses" collection that leaked into
    the variables (flax init returns sown collections): apply would
    APPEND fresh aux to the stale init-time values — double-counting —
    and the stale leaves would receive gradients/optimizer updates as if
    they were parameters.  The per-layer sown aux values are averaged and
    scaled by ``cfg.moe_aux_coef``.
    """
    if not model.cfg.num_experts:
        return (
            model.apply(params, input_ids, deterministic=deterministic),
            0.0,
        )
    variables = {k: v for k, v in params.items() if k != "losses"}
    h, sown = model.apply(
        variables, input_ids, deterministic=deterministic,
        mutable=["losses"],
    )
    aux = jax.tree_util.tree_leaves(sown.get("losses", {}))
    aux_total = (
        model.cfg.moe_aux_coef * sum(jnp.mean(a) for a in aux)
        if aux
        else 0.0
    )
    return h, aux_total


def _tied_vocab_logits(params, model: GptModel, h, *, sp_gathered: bool):
    """Vocab-parallel logits through the tied embedding decoder.

    ``sp_gathered``: True when ``h`` arrived through a sequence-dim
    gather whose reduce-scatter backward already sums the vocab-partial
    cotangent — otherwise the Megatron ``copy_to`` boundary (identity
    fwd / psum bwd) is inserted here so upstream params get full grads
    at tp > 1.
    """
    if not sp_gathered and ps.axis_is_bound(_TP):
        h = copy_to_tensor_model_parallel_region(h)
    embed = params["params"]["word_embeddings"]["weight"]
    return jnp.matmul(
        h.astype(model.cfg.dtype),
        jnp.transpose(embed).astype(model.cfg.dtype),
        preferred_element_type=jnp.float32,
    )


def gpt_lm_loss(params, model: GptModel, input_ids, *, deterministic=True):
    """Next-token CE with the decoder tied to the embedding (vocab-parallel
    logits — no gather, ≙ vocab_parallel_cross_entropy usage in Megatron).

    With ``cfg.num_experts > 0`` the per-layer MoE aux losses (sown into
    the "losses" collection) are averaged and added with
    ``cfg.moe_aux_coef``."""
    if _cp_world(model.cfg) > 1:
        raise ValueError(
            "the sequence is context-parallel sharded: use gpt_lm_loss_cp "
            "(the next-token shift crosses cp shard boundaries)"
        )
    h, aux_total = _apply_with_moe_aux(params, model, input_ids, deterministic)
    logits = _tied_vocab_logits(
        params, model, h, sp_gathered=model.cfg.sequence_parallel
    )
    # shift: predict token t+1 from position t
    losses = vocab_parallel_cross_entropy(
        logits[:-1].astype(jnp.float32), input_ids[1:]
    )
    return jnp.mean(losses) + aux_total


def gpt_lm_loss_cp(
    params,
    model: GptModel,
    input_ids_local,
    *,
    axis_name: str = _CP,
    deterministic: bool = True,
):
    """Next-token CE for a context-parallel-sharded sequence.

    ``input_ids_local``: ``(S_local, B)`` — this cp rank's shard of the
    global sequence in the model's configured layout: contiguous (rank r
    holds rows [r·S_local, ...)) for ``context_parallel="ring"`` /
    ``"ulysses"``, or the zigzag pair (global chunks ``r`` and
    ``2cp−1−r``, see ``context_parallel.zigzag_split``) for
    ``"ring_zigzag"``.  The next-token shift crosses shard boundaries
    with ``ppermute`` fetches; the global last position has no target
    and is masked (on the last rank for contiguous, rank 0's hi half for
    zigzag).  Returns the global-token-mean loss, replicated over cp
    (summed with psum, so it equals the unsharded :func:`gpt_lm_loss`
    value).  Gradient sync: treat cp like a data axis — ``pmean``
    gradients over cp (alongside dp) before the optimizer step.
    """
    # aux values are cp-replicated (SwitchMoe pmeans its stats over cp)
    h, aux_total = _apply_with_moe_aux(
        params, model, input_ids_local, deterministic
    )
    # no SP under cp, so the copy_to boundary always applies at tp > 1
    logits = _tied_vocab_logits(params, model, h, sp_gathered=False)
    world = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    valid = jnp.ones(
        (input_ids_local.shape[0], input_ids_local.shape[1]), jnp.float32
    )
    if model.cfg.context_parallel == "ring_zigzag":
        # local rows = [chunk rank; chunk 2cp−1−rank].  Boundary targets:
        # chunk r's last row predicts chunk r+1's first token — that is
        # rank r+1's lo-first, EXCEPT chunk cp−1 whose successor (chunk
        # cp) is this same rank's OWN hi-first.  Chunk 2cp−1−r's last row
        # predicts chunk 2cp−r's first token = rank r−1's hi-first; for
        # rank 0 the hi chunk is the global end (masked).
        sc = input_ids_local.shape[0] // 2
        lo, hi = input_ids_local[:sc], input_ids_local[sc:]
        lo_first_next = jax.lax.ppermute(
            lo[:1], axis_name,
            [((i + 1) % world, i) for i in range(world)],
        )
        lo_boundary = jnp.where(
            jnp.equal(rank, world - 1), hi[:1], lo_first_next
        )
        hi_boundary = jax.lax.ppermute(
            hi[:1], axis_name,
            [(i, (i + 1) % world) for i in range(world)],
        )
        targets = jnp.concatenate(
            [lo[1:], lo_boundary, hi[1:], hi_boundary], axis=0
        )
        # global final position = chunk 2cp−1's last row = rank 0's last
        rank0 = jnp.equal(rank, 0).astype(valid.dtype)
        valid = valid.at[-1].set(1.0 - rank0)
    else:
        # target for local position i is local token i+1; for the last
        # local position it is the next rank's FIRST token (one ring hop
        # backwards)
        first_next = jax.lax.ppermute(
            input_ids_local[:1],
            axis_name,
            [((i + 1) % world, i) for i in range(world)],
        )
        targets = jnp.concatenate(
            [input_ids_local[1:], first_next], axis=0
        )
        # the global final position (last rank's last row): no successor
        last_rank = jnp.equal(rank, world - 1).astype(valid.dtype)
        valid = valid.at[-1].set(1.0 - last_rank)
    losses = vocab_parallel_cross_entropy(
        logits.astype(jnp.float32), targets
    )  # (S_local, B)
    local_sum = jnp.sum(losses * valid)
    local_count = jnp.sum(valid)
    ce = jax.lax.psum(local_sum, axis_name) / jax.lax.psum(
        local_count, axis_name
    )
    return ce + aux_total
