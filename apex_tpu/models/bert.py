"""BERT — the north-star benchmark model (BASELINE config #3).

A Megatron-style BERT encoder built entirely from apex_tpu components, so
the benchmark exercises the framework end to end:

- embeddings: :class:`~apex_tpu.transformer.tensor_parallel.VocabParallelEmbedding`
  (vocab row-sharded over tp) + learned position/type embeddings,
- attention: Column/RowParallelLinear QKV/out projections around the Pallas
  flash-attention kernel (heads sharded over tp),
- MLP: the canonical Column(4H, gather=False) → GELU → Row(H) pair,
- norms: fused LayerNorm (Pallas), post-LN like original BERT,
- loss: vocab-parallel softmax cross-entropy (no logits gather).

Reference analogs: ``apex/transformer/testing/standalone_bert.py`` (the
reference's in-repo BERT fixture) and the Megatron BERT recipe its tensor/
pipeline layers were built for (SURVEY §2.3, §6).

Layout is Megatron's seq-first ``(S, B, H)`` so Megatron sequence
parallelism (activations sharded along S between TP regions) composes: with
``sequence_parallel=True`` every hidden tensor entering/leaving a layer is
the local ``(S/tp, B, H)`` shard and the Column/Row layers all-gather /
reduce-scatter at the boundaries (SURVEY §3.4).

Weight tying: the MLM decoder reuses the word-embedding matrix.  Modules
stay functional — tying happens in :func:`bert_pretrain_loss`, which reads
the embedding shard out of the param tree (≙ Megatron sharing
``word_embeddings.weight`` with the output layer through the embedding
group).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from apex_tpu import parallel_state as ps
from apex_tpu.ops.attention import flash_attention
from apex_tpu.ops.layer_norm import fused_layer_norm_affine
from apex_tpu.transformer.tensor_parallel import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
    vocab_parallel_cross_entropy,
)
from apex_tpu.transformer.tensor_parallel.layers import _tp_world, sharded_init
from apex_tpu.transformer.tensor_parallel.mappings import (
    copy_to_tensor_model_parallel_region,
    gather_from_sequence_parallel_region,
)
from apex_tpu.transformer.tensor_parallel.utils import divide

__all__ = [
    "BertConfig",
    "BertLayer",
    "BertEncoderCore",
    "BertModel",
    "BertForPreTraining",
    "bert_pretrain_loss",
    "bert_large_config",
]

_TP = ps.TENSOR_PARALLEL_AXIS

# one list with pipeline_parallel's "sums" remat wrapper (defined there —
# infra does not import the model layer); re-exported for convenience
from apex_tpu.transformer.pipeline_parallel.schedules import (  # noqa: E402
    SUMS_SAVE_NAMES,
)


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 1024
    num_layers: int = 24
    num_heads: int = 16
    intermediate_size: int = 4096
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    # compute dtype (params stay f32 — the grad-accum-fusion analog: wgrad
    # cotangents land in f32 because params are f32; see tensor_parallel
    # module docs)
    dtype: Any = jnp.bfloat16
    sequence_parallel: bool = False
    remat: bool = False  # jax.checkpoint each layer (activation ckpt analog)
    # "full" recomputes the whole layer in backward (min memory, ~1.33x
    # compute); "dots" saves every dense (no-batch-dim) matmul output and
    # recomputes only attention internals + elementwise (softmax/GELU).
    # "sums" saves the same BYTES as "dots" but picks the tensors backward
    # actually consumes: qkv, fc1 (wgrad/recompute inputs) and the two
    # post-residual sums (LayerNorm-backward inputs) instead of the raw
    # out-proj/fc2 matmul outputs, which leaves every raw matmul output
    # with one consumer, so its bias + residual epilogue fuses into the
    # matmul.  Extra recompute vs "dots": gelu + 2 LN forwards per layer
    # (elementwise).  Measured on a v5e through examples/bert/
    # pretrain_bert.py's step (BERT-Large, 128 x 128 tokens, K = 20; PERF.md
    # section 6, PR 38): "full" 383.6 ms a step; with scan_layers=False
    # "sums" 326.5, "dots" 339.0; under the scan "dots" 348.6 ("sums" there
    # was read with remat_attention only: 362.7 against "dots" 357.1).
    remat_policy: str = "full"
    # Always recompute the attention core (scores/softmax/PV) in backward,
    # regardless of remat_policy: an inner nothing_saveable checkpoint.
    # Under "dots" and "sums" it changes nothing that is saved (the batched
    # QK^T / PV products are not "dots without a batch dimension", and the
    # named policy saves its names only) and costs time: "dots" 357.1 ms
    # with it against 348.6 under the scan, 350.0 against 339.0 unrolled
    # (same runs).  It matters under policies that would save the scores.
    remat_attention: bool = False
    # jax.checkpoint's prevent_cse for the per-layer remat.  None = auto:
    # False under scan_layers (documented safe there) and True unrolled
    # (where CSE could merge the recompute with the forward and keep the
    # saves alive).  Setting False explicitly on the unrolled path is a
    # *performance* choice, not a correctness one — values are identical;
    # XLA may then keep forward activations instead of recomputing when
    # HBM allows, at the cost of the checkpoint's memory guarantee.
    remat_prevent_cse: Optional[bool] = None
    # Who runs the layer loop; the parameters are stacked (L, ...) under
    # layers/layer either way.  True: nn.scan over layers (XLA sees a rolled
    # loop, compile time flat in depth) — required for the pipeline-stage
    # use.  False: a Python loop over slices of the stacked leaves — XLA
    # schedules each layer separately, so what a checkpoint saves stays an
    # ordinary buffer, freed as the backward pass passes it, and gradients
    # are allocated as they are made: at BERT-Large b128 "dots"/"sums" fit a
    # v5e with 2 GB to spare by XLA's count (13.8 GB) where the scan's (L,
    # ...) buffers need 17.1 GB, and the step is 10-36 ms shorter.  The
    # price is the program: L copies of a layer's code (293 MB against 26
    # MB), 47-53 s to compile cold against 6-14 s, ~3 s to load from the
    # compile cache.
    scan_layers: bool = True
    # The mesh axis whose ranks hold the rows of one global batch, for a
    # data-parallel step that averages its gradients over that axis:
    # :func:`bert_pretrain_loss` then sums the MLM loss's numerator and its
    # count of masked positions over the axis before the divide (the global
    # mean, whatever each rank's share of masked positions) and scales the
    # rank's term by the axis size, so that the averaged gradient is the
    # global loss's.  None: the mean over this rank's rows.
    mlm_mean_axis: Optional[str] = None

    def __post_init__(self):
        if self.remat_policy not in ("full", "dots", "sums"):
            raise ValueError(
                f"unknown remat_policy {self.remat_policy!r} "
                "(options are 'full', 'dots', 'sums')"
            )


def bert_large_config(**overrides) -> BertConfig:
    """BERT-Large (≈336M params), the BASELINE.json north-star shape."""
    return BertConfig(**overrides)


def _per_rank_dropout_rng(module: nn.Module, rank_local: bool):
    """Dropout key, folded with the tp rank when the tensor is RANK-LOCAL
    (SP sequence shard, or tp-sharded attention heads) — ≙ Megatron's
    model-parallel RNG stream, which seeds dropout differently per tp rank
    inside sharded regions.  For REPLICATED tensors the key must stay
    identical across ranks (folding would desynchronize the replicated
    activations), so ``rank_local=False`` returns the shared key.
    """
    from apex_tpu.transformer.tensor_parallel.random import to_per_rank_key

    rng = module.make_rng("dropout")
    if rank_local and _tp_world(_TP) > 1:
        rng = to_per_rank_key(rng)
    return rng


class _LayerNorm(nn.Module):
    size: int
    eps: float
    # True when this LN runs inside the sequence-parallel region: its
    # params are tp-replicated but see only an S/tp shard per rank, so
    # their grads need the tp psum (allreduce_sequence_parallel_gradients)
    sequence_parallel: bool = False

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", nn.initializers.ones, (self.size,))
        b = self.param("bias", nn.initializers.zeros, (self.size,))
        if self.sequence_parallel:
            ps.register_sequence_parallel_param(self.path + ("scale",))
            ps.register_sequence_parallel_param(self.path + ("bias",))
        return fused_layer_norm_affine(x, w, b, (self.size,), eps=self.eps)


class BertSelfAttention(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x, attention_bias=None, *, deterministic=True):
        with jax.named_scope("bert_self_attention"):
            return self._attend(x, attention_bias, deterministic)

    def _attend(self, x, attention_bias, deterministic):
        cfg = self.cfg
        h = cfg.hidden_size
        world = _tp_world(_TP)
        heads_local = divide(cfg.num_heads, world)
        head_dim = divide(h, cfg.num_heads)

        qkv = ColumnParallelLinear(
            h, 3 * h, gather_output=False,
            sequence_parallel_enabled=cfg.sequence_parallel,
            dtype=cfg.dtype, name="qkv",
        )(x)
        # inert unless remat_policy="sums" selects it by name
        qkv = checkpoint_name(qkv, "bert_qkv")
        s = qkv.shape[0]  # full sequence after the SP gather inside Column
        b = qkv.shape[1]
        # Global QKV column layout is (heads, 3, head_dim) — per-head
        # interleaved, the Megatron convention — so column-sharding the
        # output dim over tp hands each rank whole (q, k, v) triples for
        # its heads and the math is tp-invariant.  (A (3, heads, d) layout
        # would shard into "rank 0 owns q of all heads", breaking tp>1.)
        qkv = qkv.reshape(s, b, heads_local, 3, head_dim)
        q, k, v = (
            jnp.transpose(qkv[:, :, :, i], (1, 2, 0, 3)) for i in range(3)
        )
        p = 0.0 if deterministic else cfg.attention_dropout
        # q/k/v are head-SHARDED over tp: each rank's heads need their own
        # dropout mask, so the key is rank-local whenever tp > 1
        rng = _per_rank_dropout_rng(self, True) if p > 0.0 else None

        def core(q, k, v, bias):
            return flash_attention(
                q, k, v, bias, scale=head_dim**-0.5,
                dropout_p=p, dropout_rng=rng,
            )

        if cfg.remat_attention:
            core = jax.checkpoint(
                core, policy=jax.checkpoint_policies.nothing_saveable
            )
        ctx = core(q, k, v, attention_bias)
        ctx = jnp.transpose(ctx, (2, 0, 1, 3)).reshape(s, b, heads_local * head_dim)
        return RowParallelLinear(
            h, h, input_is_parallel=True,
            sequence_parallel_enabled=cfg.sequence_parallel,
            dtype=cfg.dtype, name="out",
        )(ctx)


class BertMlp(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x):
        with jax.named_scope("bert_mlp"):
            return self._mlp(x)

    def _mlp(self, x):
        cfg = self.cfg
        y = ColumnParallelLinear(
            cfg.hidden_size, cfg.intermediate_size, gather_output=False,
            sequence_parallel_enabled=cfg.sequence_parallel,
            dtype=cfg.dtype, name="fc1",
        )(x)
        y = checkpoint_name(y, "bert_fc1")
        y = jax.nn.gelu(y, approximate=True)
        return RowParallelLinear(
            cfg.intermediate_size, cfg.hidden_size, input_is_parallel=True,
            sequence_parallel_enabled=cfg.sequence_parallel,
            dtype=cfg.dtype, name="fc2",
        )(y)


class BertLayer(nn.Module):
    """Post-LN transformer block (original BERT residual order)."""

    cfg: BertConfig

    @nn.compact
    def __call__(self, x, attention_bias=None, *, deterministic=True):
        cfg = self.cfg
        attn = BertSelfAttention(cfg, name="attention")(
            x, attention_bias, deterministic=deterministic
        )
        if not deterministic and cfg.hidden_dropout > 0.0:
            # under SP the activations are sequence shards (rank-local
            # masks); otherwise they are replicated (shared mask required)
            attn = nn.Dropout(cfg.hidden_dropout)(
                attn, deterministic=False,
                rng=_per_rank_dropout_rng(self, cfg.sequence_parallel),
            )
        x = _LayerNorm(
            cfg.hidden_size, cfg.layer_norm_eps,
            sequence_parallel=cfg.sequence_parallel, name="ln_attn",
        )(checkpoint_name(x + attn, "bert_sum_attn"))
        mlp = BertMlp(cfg, name="mlp")(x)
        if not deterministic and cfg.hidden_dropout > 0.0:
            mlp = nn.Dropout(cfg.hidden_dropout)(
                mlp, deterministic=False,
                rng=_per_rank_dropout_rng(self, cfg.sequence_parallel),
            )
        return _LayerNorm(
            cfg.hidden_size, cfg.layer_norm_eps,
            sequence_parallel=cfg.sequence_parallel, name="ln_mlp",
        )(checkpoint_name(x + mlp, "bert_sum_mlp"))


class _BlockStep(nn.Module):
    """One scan step: carry = hidden states; bias broadcast to all steps."""

    cfg: BertConfig
    deterministic: bool

    @nn.compact
    def __call__(self, x, attention_bias):
        y = BertLayer(self.cfg, name="layer")(
            x, attention_bias, deterministic=self.deterministic
        )
        return y, None


@jax.custom_vjp
def _unstack(stacked):
    """The ``(L, ...)`` leaf as L per-layer arrays."""
    return tuple(stacked[i] for i in range(stacked.shape[0]))


def _unstack_fwd(stacked):
    return _unstack(stacked), None


def _unstack_bwd(_, grads):
    # each layer's gradient is finished before the stack is built: left to
    # itself XLA fuses every weight-gradient matmul into an in-place update
    # of the (L, ...) buffer, chains the L updates behind the whole backward
    # pass, and keeps each matmul's inputs alive until its turn (2.2 GB more
    # at BERT-Large, compiled for v5e)
    return (jnp.stack(jax.lax.optimization_barrier(grads)),)


_unstack.defvjp(_unstack_fwd, _unstack_bwd)


class BertEncoderCore(nn.Module):
    """A homogeneous stack of ``num_layers`` BertLayers.

    The parameters are stacked ``(L, ...)`` under ``layers/layer`` whatever
    runs the loop.  ``scan_layers=True``: ``nn.scan`` over the layer dim, so
    24 layers trace once and XLA sees a rolled loop, compile time flat in
    depth.  ``scan_layers=False``: a Python loop over slices of the same
    stacked leaves, one checkpointed function called L times (traced once).
    Also the pipeline-stage module: a pp stage is a BertEncoderCore with
    ``num_layers = L/pp`` (homogeneous stages, the Megatron layout).
    """

    cfg: BertConfig
    num_layers: int

    @nn.compact
    def __call__(self, x, attention_bias=None, *, deterministic=True):
        cfg = self.cfg
        policy = prevent_cse = None
        if cfg.remat:
            # activation checkpointing per layer ≙ tensor_parallel.random
            # .checkpoint (recompute-in-backward; PRNG replay is automatic
            # in JAX — keys are values, not stateful generators).  "sums":
            # same bytes as "dots", chosen so every raw matmul output is
            # single-consumer (epilogues fuse); see BertConfig.
            from apex_tpu.transformer.pipeline_parallel.schedules import (
                resolve_remat_policy,
            )

            policy = resolve_remat_policy(cfg.remat_policy)
            # prevent_cse=False is documented safe only under scan/pmap
            # differentiation; on the unrolled path the layer is
            # differentiated directly under jit, where CSE could merge the
            # backward recompute with the forward and silently defeat the
            # checkpoint, so auto mode keeps it True there (see
            # BertConfig.remat_prevent_cse for the explicit override).
            prevent_cse = cfg.remat_prevent_cse
            if prevent_cse is None:
                prevent_cse = not cfg.scan_layers
        if cfg.scan_layers or self.is_initializing():
            # (init always goes this way: it is what lays the tree out)
            step = _BlockStep
            if cfg.remat:
                step = nn.remat(step, prevent_cse=prevent_cse, policy=policy)
            scanned = nn.scan(
                step,
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True},
                length=self.num_layers,
                in_axes=nn.broadcast,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )
            y, _ = scanned(cfg, deterministic, name="layers")(
                x, attention_bias
            )
            return y
        return self._unrolled(x, attention_bias, deterministic, policy,
                              prevent_cse)

    def _unrolled(self, x, attention_bias, deterministic, policy, prevent_cse):
        """The layers one after another over the stacked leaves.  XLA
        schedules each layer on its own: what a checkpoint saves stays an
        ordinary buffer, freed as the backward pass passes it, and the
        gradients are allocated as they are made — at BERT-Large on a v5e
        3 GB less than the scanned loop holds for the same policy."""
        block = _BlockStep(self.cfg, deterministic)
        here = self.path + ("layers",)

        def layer(params, x, bias, key):
            rngs = None if key is None else {"dropout": key}
            # applied on its own, ``block`` counts paths from itself
            with ps.sequence_parallel_param_prefix(here):
                y, _ = block.apply({"params": params}, x, bias, rngs=rngs)
            return y

        # one function object for every layer: traced once, and under
        # jit its derivative and its lowering are shared by the L calls too
        # (tracing and lowering BERT-Large's LAMB step on a v5e's host:
        # 3.3-3.9 s against the scan's 2.2-3.0; 4.8-5.4 s without the jit;
        # 11.4 s for L separately named layers)
        layer = jax.jit(layer)
        if self.cfg.remat:
            layer = jax.checkpoint(
                layer, policy=policy, prevent_cse=prevent_cse
            )
        keys = (
            [None] * self.num_layers if deterministic
            else jax.random.split(self.make_rng("dropout"), self.num_layers)
        )
        leaves, treedef = jax.tree_util.tree_flatten(
            self.variables["params"]["layers"]
        )
        per_layer = zip(*(_unstack(leaf) for leaf in leaves))
        for params, key in zip(per_layer, keys):
            x = layer(treedef.unflatten(params), x, attention_bias, key)
        return x


class BertEmbeddings(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, *, deterministic=True):
        cfg = self.cfg
        s, b = input_ids.shape  # seq-first (S, B)
        sp = cfg.sequence_parallel and _tp_world(_TP) > 1
        # Megatron's SP embedding order: the vocab-parallel lookup
        # reduce-SCATTERS its psum along the sequence dim, so the SP
        # regime starts here and pos/type/LN run on the S/tp shard.  (A
        # full-seq embedding block followed by a slice would be WRONG, not
        # just slower: the slice's backward zeroes other shards' cotangent
        # rows, so cross-(seq-shard, vocab-shard) embedding-gradient
        # contributions would be silently dropped — each rank's lookup
        # only covers its own vocab rows.)
        word = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size,
            sequence_parallel_enabled=cfg.sequence_parallel,
            dtype=cfg.dtype, name="word_embeddings",
        )(input_ids)
        local_s = word.shape[0]  # S/tp under SP, S otherwise
        start = 0
        if sp:
            # dynamic_slice CLAMPS an out-of-range start — guard the table
            # size so a too-long sequence fails loudly instead of silently
            # reusing the last position rows on high ranks
            tp = _tp_world(_TP)
            if tp * local_s > cfg.max_position_embeddings:
                raise ValueError(
                    f"global sequence tp*S_local = {tp}*{local_s} exceeds "
                    f"max_position_embeddings "
                    f"({cfg.max_position_embeddings})"
                )
            start = jax.lax.axis_index(_TP) * local_s
            ps.register_sequence_parallel_param(
                self.path + ("position_embeddings",)
            )
        pos_tab = self.param(
            "position_embeddings",
            nn.initializers.normal(stddev=0.02),
            (cfg.max_position_embeddings, cfg.hidden_size),
        )
        rows = jax.lax.dynamic_slice_in_dim(pos_tab, start, local_s, 0)
        word = word + rows[:, None, :].astype(cfg.dtype)
        if cfg.type_vocab_size:
            tt = (
                jnp.zeros_like(input_ids)
                if token_type_ids is None
                else token_type_ids
            )
            if sp:
                tt = jax.lax.dynamic_slice_in_dim(tt, start, local_s, 0)
                ps.register_sequence_parallel_param(
                    self.path + ("token_type_embeddings",)
                )
            type_tab = self.param(
                "token_type_embeddings",
                nn.initializers.normal(stddev=0.02),
                (cfg.type_vocab_size, cfg.hidden_size),
            )
            word = word + jnp.take(type_tab, tt, axis=0).astype(cfg.dtype)
        out = _LayerNorm(
            cfg.hidden_size, cfg.layer_norm_eps,
            sequence_parallel=cfg.sequence_parallel, name="ln",
        )(word)
        if not deterministic and cfg.hidden_dropout > 0.0:
            out = nn.Dropout(cfg.hidden_dropout)(
                out, deterministic=False,
                rng=_per_rank_dropout_rng(self, sp),
            )
        return out


class BertModel(nn.Module):
    """Embeddings + encoder.  Returns (S[, /tp], B, H) sequence output."""

    cfg: BertConfig

    @nn.compact
    def __call__(
        self, input_ids, token_type_ids=None, attention_mask=None,
        *, deterministic=True,
    ):
        cfg = self.cfg
        bias = None
        if attention_mask is not None:
            # (B, S) with 1 = keep (BERT convention) → additive (B,1,1,S)
            bias = jnp.where(
                attention_mask.astype(bool), 0.0, -1e9
            )[:, None, None, :].astype(jnp.float32)
        x = BertEmbeddings(cfg, name="embeddings")(
            input_ids, token_type_ids, deterministic=deterministic
        )
        return BertEncoderCore(cfg, cfg.num_layers, name="encoder")(
            x, bias, deterministic=deterministic
        )


class BertForPreTraining(nn.Module):
    """BERT + MLM transform + NSP pooler (heads' logits are computed in
    :func:`bert_pretrain_loss` so the MLM decoder can tie to the embedding).
    Returns ``(mlm_hidden, nsp_logits)``.
    """

    cfg: BertConfig

    @nn.compact
    def __call__(
        self, input_ids, token_type_ids=None, attention_mask=None,
        *, deterministic=True,
    ):
        cfg = self.cfg
        seq = BertModel(cfg, name="bert")(
            input_ids, token_type_ids, attention_mask,
            deterministic=deterministic,
        )
        sp = cfg.sequence_parallel and _tp_world(_TP) > 1
        # NSP pooler on [CLS] (position 0).  Under SP the pooler is
        # REPLICATED computation on the gathered sequence, so its gather
        # must split (not reduce-scatter) the cotangent — the Megatron
        # ``tensor_parallel_output_grad=False`` case; a reduce-scatter
        # here would feed the encoder tp× the NSP gradient.
        seq_full = (
            gather_from_sequence_parallel_region(
                seq, tensor_parallel_output_grad=False
            )
            if sp
            else seq
        )
        pooled = jnp.tanh(
            nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="pooler")(
                seq_full[0]
            )
        )
        nsp_logits = nn.Dense(2, dtype=cfg.dtype, name="nsp_head")(pooled)
        # MLM transform: dense + GELU + LN (the BERT "cls/predictions"
        # transform).  Runs in the SP (sequence-sharded) layout — per-token
        # math, so each rank transforms only its S/tp shard (Megatron's
        # order) — then gathers for the vocab-sharded decoder matmul.  The
        # gather's reduce-scatter backward sums the decoder's vocab-partial
        # cotangents into the true per-shard cotangent; the transform's
        # params sit between gather and matmul in the partial-cotangent
        # region, hence the sequence-parallel grad marking.
        h = nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="mlm_dense")(seq)
        h = jax.nn.gelu(h, approximate=True)
        h = _LayerNorm(
            cfg.hidden_size, cfg.layer_norm_eps,
            sequence_parallel=sp, name="mlm_ln",
        )(h)
        if sp:
            ps.register_sequence_parallel_param(
                self.path + ("mlm_dense", "kernel")
            )
            ps.register_sequence_parallel_param(
                self.path + ("mlm_dense", "bias")
            )
            h = gather_from_sequence_parallel_region(h)
        # vocab-sharded decoder bias (the tied decoder weight is read from
        # the embedding table in bert_pretrain_loss)
        per = divide(cfg.vocab_size, _tp_world(_TP))
        mlm_bias = self.param(
            "mlm_bias",
            sharded_init(nn.initializers.zeros, (cfg.vocab_size,), 0),
            (per,),
        )
        return (h, mlm_bias), nsp_logits


def bert_pretrain_loss(
    params,
    model: BertForPreTraining,
    batch,
    *,
    deterministic: bool = True,
    rngs: Optional[dict] = None,
    mlm_loss_chunks: Optional[int] = None,
):
    """MLM + NSP loss (the phase-1 pretraining objective).

    ``batch``: dict with ``input_ids``/``token_type_ids``/``attention_mask``
    (S-first ids (S, B) / mask (B, S)), ``mlm_labels`` (S, B; -1 = unmasked,
    ignored), ``nsp_labels`` (B,).  MLM decoder weight is tied to
    ``bert/embeddings/word_embeddings/weight`` (vocab-sharded ⇒ logits are
    vocab-parallel and feed vocab_parallel_cross_entropy directly — no
    logits gather, ≙ _VocabParallelCrossEntropy).

    **Masked-position gather (the reference recipe's input format).**  When
    the batch carries the fixed-K triple ``mlm_positions`` (K, B) /
    ``mlm_label_ids`` (K, B) / ``mlm_weights`` (K, B; 1.0 = real
    prediction, 0.0 = pad), the MLM head runs only on the K gathered rows
    per sequence — the BERT ``max_predictions_per_seq`` recipe
    (masked_lm_positions/masked_lm_ids/masked_lm_weights in the reference's
    BERT pretraining input), which at phase-1 shapes (S=128, K=20) removes
    ~84% of the decoder-matmul + cross-entropy work.  The dense
    ``mlm_labels`` path remains for full-sequence scoring;
    :func:`apex_tpu.data.pack_mlm_predictions` converts dense labels to the
    triple.

    ``mlm_loss_chunks``: split the (S·B, V) logits matmul + cross entropy
    into this many row chunks, each rematerialized in backward — the full
    f32 logits tensor (2 GB at batch 128 / BERT-Large vocab) never exists;
    peak is 1/chunks of it, for one extra decoder-matmul pass (~3% of
    step FLOPs).  None/1 = unchunked.
    """
    (h, mlm_bias), nsp_logits = model.apply(
        params,
        batch["input_ids"],
        batch.get("token_type_ids"),
        batch.get("attention_mask"),
        deterministic=deterministic,
        rngs=rngs,
    )
    embed = params["params"]["bert"]["embeddings"]["word_embeddings"]["weight"]
    positions = batch.get("mlm_positions")
    if positions is not None:
        # (S, B, H) -> (K, B, H); backward is a scatter-add into dh.  h is
        # full-S in both layouts (the SP path gathered inside the model),
        # so the gather is rank-local and the tp grad boundaries below are
        # unchanged.
        h = jnp.take_along_axis(h, positions[:, :, None], axis=0)
        # pack_mlm_predictions pads label ids with 0, but a hand-built
        # triple may use the dense path's -1 ignore convention; an
        # out-of-range id would NaN the xent gather and survive the
        # weight-0 multiply, so clamp exactly as the dense path does.
        labels = jnp.maximum(batch["mlm_label_ids"], 0)
        weights = batch["mlm_weights"].astype(jnp.float32)
    else:
        labels = batch["mlm_labels"]
        weights = (labels >= 0).astype(jnp.float32)
        labels = jnp.maximum(labels, 0)
    if not model.cfg.sequence_parallel and ps.axis_is_bound(_TP):
        # ≙ Megatron's copy_to_tensor_model_parallel_region before the
        # vocab-sharded logits matmul: identity forward, psum backward.
        # The decoder cotangent d h = d logits_r @ W_r is PARTIAL per tp
        # rank (each rank's vocab shard); without this psum every param
        # between the loss and the next collective boundary (mlm
        # transform, final layer norms, last-layer weights) silently gets
        # partial/mixed gradients at tp > 1.  (Under SP the MLM gather's
        # reduce-scatter backward performs this sum instead.)
        h = copy_to_tensor_model_parallel_region(h)
    with jax.named_scope("mlm_logits_xent"):
        dec = jnp.transpose(embed).astype(model.cfg.dtype)

        def rows_loss(h_rows, l_rows, w_rows):
            logits = (
                jnp.matmul(
                    h_rows.astype(model.cfg.dtype), dec,
                    preferred_element_type=jnp.float32,
                )
                + mlm_bias
            )
            losses = vocab_parallel_cross_entropy(
                logits.astype(jnp.float32), l_rows
            )
            return jnp.sum(losses * w_rows), jnp.sum(w_rows)

        nc = mlm_loss_chunks or 1
        if nc > 1:
            rows = labels.size
            if rows % nc:
                raise ValueError(
                    f"mlm_loss_chunks={nc} must divide the number of "
                    f"MLM prediction rows ({rows})"
                )
            hc = h.reshape(nc, rows // nc, h.shape[-1])
            lc = labels.reshape(nc, rows // nc)
            wc = weights.reshape(nc, rows // nc)
            # Statically unrolled (not lax.map/scan): scan's backward stacks
            # the per-chunk dh cotangents into an (nc, rows/nc, H) buffer
            # through dynamic-update-slice — an extra full pass over dh that
            # the unrolled form doesn't pay (measured ~2% of the BERT-Large
            # bench step).  nc is small and static, so HLO growth is trivial.
            chunk_fn = jax.checkpoint(rows_loss)
            total = jnp.float32(0.0)
            count = jnp.float32(0.0)
            for i in range(nc):
                s, c = chunk_fn(hc[i], lc[i], wc[i])
                total = total + s
                count = count + c
        else:
            total, count = rows_loss(
                h.reshape(-1, h.shape[-1]), labels.reshape(-1),
                weights.reshape(-1),
            )
        axis = model.cfg.mlm_mean_axis
        if axis is not None:
            # the global mean: every rank's masked positions counted; the
            # rank's term times the axis size, which the caller's gradient
            # average divides out again
            count = jax.lax.psum(count, axis)
            total = total * jax.lax.axis_size(axis)
        mlm_loss = total / jnp.maximum(count, 1.0)

    nsp_labels = batch.get("nsp_labels")
    nsp_loss = 0.0
    if nsp_labels is not None:
        logp = jax.nn.log_softmax(nsp_logits.astype(jnp.float32), axis=-1)
        nsp_loss = -jnp.mean(
            jnp.take_along_axis(logp, nsp_labels[:, None], axis=-1)
        )
    return mlm_loss + nsp_loss
