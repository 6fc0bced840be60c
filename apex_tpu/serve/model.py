"""Functional forward for serving — prefill and decode bodies.

The training stack's :class:`apex_tpu.models.gpt.GptModel` is a flax
module built for ``value_and_grad`` over a full sequence; serving needs
the same weights driven through three attention dataflows — a one-shot
**prefill** that also emits every position's K/V for the cache, a
**chunked prefill** that reads the earlier positions back from it, and
a single-token **decode** that appends to and reads from the paged
cache — over ONE head, block, layer loop and tail (``_embed_at``,
``_block``, ``_layers``, ``_final_logits`` / ``_sample_tail``).
This module is the functional re-expression of ``GptBlock`` /
``GptModel`` over the ``GptModel.init`` parameter tree (the scanned
stack's leaves carry a leading ``num_layers`` axis, which maps directly
onto ``lax.scan`` here), kept numerically in lockstep with the training
forward:

- same compute-dtype discipline as ``ColumnParallelLinear`` /
  ``RowParallelLinear`` at tp=1 (matmul in ``cfg.dtype`` with
  ``preferred_element_type=f32``, cast back, bias in compute dtype);
- same fused LayerNorm, same f32 RoPE rotation
  (``ops.rope._apply``'s math), same causal flash attention for
  prefill, same tied-embedding f32 logits as ``gpt._tied_vocab_logits``
  — ``tests/test_serve.py`` pins prefill/decode logits against
  ``GptModel.apply`` itself.

**Layer kinds.**  A block is a *mixer* and an *FFN*, each of a kind
(:data:`_MIXERS`, :data:`_FFNS`): the GPT stack above is ``("mha",
"gelu")`` throughout and keeps its ``lax.scan``; a
:class:`~apex_tpu.models.hybrid.HybridConfig` names a kind per layer —
``"kda"`` (linear attention with a per-slot recurrent state), ``"mla"``
(latent attention over latent pages) or ``"ssm_gqa"`` (a Mamba-2
state-space branch with a per-slot state and a grouped-query attention
branch over K/V pages, side by side), ``"dense"`` (SwiGLU) or ``"moe"``
(a dropless routed layer told which experts it holds, plus a shared
expert) — over RMSNorm and an untied head, and runs as a Python loop over
its layers through the same :func:`_block`.  Its two dataflows are the
prompt (:class:`_Flow` with ``prompt=True``) and the decode step; the
cache set they carry is :func:`apex_tpu.serve.cache.init_hybrid_cache`'s
(docs/serving.md "Layer kinds and the cache set").

Serving scope: a single model shard (no SP/CP).  What a kind cannot run is
refused by name (:func:`validate_config`, :func:`validate_features`): a
model with recurrent layers has no prefix cache, copy-on-write fork,
chunked prefill or speculative program (each needs a snapshot of the
per-slot state); the routed FFN runs one chip's share only; training runs
none of these kinds.  **Weight wires**: :func:`quantize_params` /
:func:`dequantize_params` put the large parameter leaves on the
blockwise int8 code of ``parallel/comm.py`` (small leaves — biases, LN
affines — stay exact, mirroring ``sync_gradients``'s ``min_size``
rule); the engine dequantizes inside the compiled step, so the param
HBM footprint is the wire footprint.  **The step tree**
(:func:`step_params`) is what the engine hands the bodies: the block's
matmul weights and biases in the compute dtype, cast once when a tree
is installed.  The bodies take either tree, bit for bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.models.gpt import GptConfig, _rope_cos_sin
from apex_tpu.ops import kda as kda_ops
from apex_tpu.ops import ssm as ssm_ops
from apex_tpu.ops.attention import flash_attention
from apex_tpu.ops.layer_norm import fused_layer_norm_affine
from apex_tpu.ops.mla import latent_row_width, mla_decode_attention
from apex_tpu.ops.paged_attention import (
    gather_history,
    paged_decode_attention,
)
from apex_tpu.ops.rope import fused_apply_rotary_pos_emb_cached, rotate_half
from apex_tpu.parallel import comm
from apex_tpu.serve import cache as cache_lib
from apex_tpu.transformer.moe import dropless_moe, route_group_limited

__all__ = [
    "validate_config",
    "validate_features",
    "layer_kinds",
    "is_stateful",
    "is_routed",
    "rope_tables",
    "PackedWeight",
    "quantize_params",
    "dequantize_params",
    "step_params",
    "stream_keys",
    "slot_keys",
    "sample_tokens",
    "prefill_body",
    "chunk_prefill_body",
    "decode_body",
]

#: leaves smaller than this stay f32 under weight_wire="int8" (biases,
#: LN affines — the same noise-sensitivity rule as comm.sync_gradients)
WEIGHT_WIRE_MIN_SIZE = 1024


def layer_kinds(cfg):
    """``((mixer, ffn), ...)`` of a stack whose layers differ in kind, None
    for the homogeneous GPT stack (``("mha", "gelu")`` throughout)."""
    return getattr(cfg, "kinds", None)


def is_stateful(cfg) -> bool:
    """Some layer keeps a per-slot recurrent state."""
    return bool(getattr(cfg, "stateful", False))


def is_routed(cfg) -> bool:
    """Some layer is a routed FFN (its programs return the MoE counts)."""
    return bool(getattr(cfg, "routed", False))


#: what serving refuses, for which kind of model, and why
_REFUSED = {
    "prefix_cache": (
        "prefix cache (and its copy-on-write fork): a cached page run "
        "holds no recurrent state, and a borrower's slot would start from "
        "zeros — needs per-prefix state snapshots"
    ),
    "chunked_prefill": (
        "chunked prefill: a chunk would have to resume the slot's "
        "recurrent state and convolution tail mid-prompt"
    ),
    "spec": (
        "speculative programs (draft / verify / rollback): a rejected "
        "position cannot be rolled back out of a recurrent state"
    ),
}


def validate_config(cfg):
    """What serving runs, by kind of model; what it refuses, by name:

    - any model: sequence or context parallelism (the engine owns the
      whole sequence on one shard);
    - the GPT stack (:class:`GptConfig`): its flax ``SwitchMoe`` FFN — the
      capacity-dropping path is a training path; a routed FFN is served
      through :class:`~apex_tpu.models.hybrid.HybridConfig`'s dropless
      ``"moe"`` kind;
    - a model with recurrent layers: :func:`validate_features`."""
    if cfg.sequence_parallel or cfg.context_parallel:
        raise ValueError(
            "serving requires sequence_parallel=False and "
            "context_parallel=None (the engine owns the whole sequence)"
        )
    if layer_kinds(cfg) is None and cfg.num_experts:
        raise ValueError(
            "serving does not run GptConfig's capacity-dropping SwitchMoe "
            "FFN (num_experts > 0); describe the model as a HybridConfig, "
            "whose routed FFN is the dropless held-experts layer"
        )
    return cfg


def validate_features(cfg, **asked) -> None:
    """Refuse, by name, each serving mechanism in ``asked``
    (``prefix_cache``, ``chunked_prefill``, ``spec``: truthy = wanted)
    that a model with recurrent layers cannot run."""
    if not is_stateful(cfg):
        return
    for name, wanted in asked.items():
        if wanted:
            raise ValueError(
                f"a model with recurrent (KDA or state-space) layers does "
                f"not run the "
                f"{_REFUSED[name]} (ROADMAP M5)"
            )


def _head_dim(cfg: GptConfig) -> int:
    return cfg.hidden_size // cfg.num_heads


def rope_tables(cfg: GptConfig):
    """Cached f32 cos/sin ``(max_seq_len, head_dim)`` in the model's
    rotate_half layout (None for non-rotary configs)."""
    if not cfg.rotary:
        return None, None
    return _rope_cos_sin(cfg.max_seq_len, _head_dim(cfg))


# ---------------------------------------------------------------------------
# parameter access + weight wires
# ---------------------------------------------------------------------------


def _tree(params):
    return params["params"]


@jax.tree_util.register_pytree_node_class
class PackedWeight:
    """A parameter leaf on the blockwise int8 wire: the codes and f32
    scales are the traced arrays; shape/size/block/dtype ride the
    treedef as static metadata (so a jitted step sees them as
    structure, not operands)."""

    def __init__(self, codes, scale, shape, n, block, dtype):
        self.codes = codes
        self.scale = scale
        self.shape = tuple(shape)
        self.n = int(n)
        self.block = int(block)
        self.dtype = dtype

    def unpack(self):
        flat = comm.dequantize_blocks(
            self.codes, self.scale, self.block, self.n
        )
        return flat.reshape(self.shape).astype(self.dtype)

    def tree_flatten(self):
        return (self.codes, self.scale), (
            self.shape, self.n, self.block, self.dtype,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def _is_packed(leaf) -> bool:
    return isinstance(leaf, PackedWeight)


def quantize_params(params, *, block: int = comm.DEFAULT_BLOCK,
                    min_size: int = WEIGHT_WIRE_MIN_SIZE):
    """Pack every parameter leaf of >= ``min_size`` elements onto the
    blockwise int8 wire (flattened, ``comm.quantize_blocks``); smaller
    leaves pass through exact.  Inverse: :func:`dequantize_params`."""

    def pack(leaf):
        if leaf.size < min_size:
            return leaf
        flat = jnp.ravel(leaf).astype(jnp.float32)
        codes, scale = comm.quantize_blocks(flat, block=block)
        return PackedWeight(
            codes, scale, leaf.shape, flat.shape[0], block, leaf.dtype
        )

    return jax.tree_util.tree_map(pack, params)


def dequantize_params(params):
    """Unpack a :func:`quantize_params` tree back to dense leaves —
    called INSIDE the compiled step, so the resident format stays
    int8."""
    return jax.tree_util.tree_map(
        lambda leaf: leaf.unpack() if _is_packed(leaf) else leaf,
        params, is_leaf=_is_packed,
    )


#: where the leaves sit that the step tree holds in the compute dtype:
#: the block's four matrices and their biases, which ``_linear`` reads
#: through ``.astype(cfg.dtype)`` and no other way.  XLA hoists those
#: casts out of the layer loop as passes of their own, so casting ahead
#: leaves the rest of the program as it was, bit for bit.  NOT the
#: token table and the learned positions, though ``_embed`` / ``_logits``
#: / ``_embed_at`` read them the same way: XLA:TPU fuses their casts
#: into the consumers (no pass, no temporary) and may keep the excess
#: precision, so a table rounded ahead of the program is another
#: computation there — on the chip it moved every GPT-2 Large logit, by
#: up to 0.05 (PERF.md section 6, PR 34).  Nor LayerNorm's scales and
#: biases: the fused LayerNorm reads them in f32.
_COMPUTE_DTYPE_KEYS = frozenset(("qkv", "out", "fc1", "fc2"))


@functools.partial(jax.jit, static_argnames="dtype")
def _cast_leaves(leaves, dtype):
    return [leaf.astype(dtype) for leaf in leaves]


def step_params(cfg: GptConfig, params):
    """The tree the step programs take — the *step tree* of ``params``:
    the block's matmul weights and biases already in ``cfg.dtype``,
    cast ONCE here (one compiled launch for all of them) where the
    programs would cast the whole stack again at the head of every call
    (docs/serving.md "Weights").  The conversion is the bodies' own
    hoisted pass, so every logit is bit-identical on either tree.

    What is not cast is the caller's array itself, not a copy: the
    embedding tables and LayerNorm leaves (:data:`_COMPUTE_DTYPE_KEYS`
    says why), ``PackedWeight`` leaves (the int8 wire stays packed; the
    step dequantizes it), and any leaf already in ``cfg.dtype`` — an
    f32-compute config gets its own tree back."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=_is_packed
    )
    cast = [
        i for i, (path, leaf) in enumerate(leaves)
        if not _is_packed(leaf) and leaf.dtype != cfg.dtype
        and _COMPUTE_DTYPE_KEYS.intersection(
            getattr(key, "key", None) for key in path
        )
    ]
    if not cast:
        return params
    out = [leaf for _, leaf in leaves]
    for i, leaf in zip(cast, _cast_leaves([out[i] for i in cast], cfg.dtype)):
        out[i] = leaf
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# functional layers (numerics of the flax stack at tp=1)
# ---------------------------------------------------------------------------


def _layer_norm(x, p, eps):
    return fused_layer_norm_affine(
        x, p["scale"], p["bias"], (x.shape[-1],), eps=eps
    )


def _linear(x, p, dtype):
    """tp=1 Column/RowParallelLinear numerics: compute-dtype matmul
    with f32 accumulation, cast back, bias in compute dtype."""
    y = jnp.matmul(
        x.astype(dtype), p["weight"].astype(dtype),
        preferred_element_type=jnp.float32,
    ).astype(dtype)
    return y + p["bias"].astype(dtype)


def _embed(p, ids, dtype):
    return jnp.take(p["weight"], ids, axis=0).astype(dtype)


def _logits(tree, h, dtype):
    """Tied-embedding vocab logits (``gpt._tied_vocab_logits`` at
    tp=1): f32 output."""
    embed = tree["word_embeddings"]["weight"]
    return jnp.matmul(
        h.astype(dtype), jnp.transpose(embed).astype(dtype),
        preferred_element_type=jnp.float32,
    )


def _rope_rows(x, cos, sin):
    """f32 rotate_half rotation with PER-SEQUENCE cos/sin rows
    ``(B, D)`` broadcast over heads — ``ops.rope._apply``'s math for
    the decode step, where every sequence sits at its own position."""
    with jax.named_scope("rope_f32"):
        xf = x.astype(jnp.float32)
    out = xf * cos[:, None, :] + rotate_half(xf) * sin[:, None, :]
    return out.astype(x.dtype)


def _mlp(x, bp, cfg):
    y = _layer_norm(x, bp["ln_mlp"], cfg.layer_norm_eps)
    y = _linear(y, bp["fc1"], cfg.dtype)
    y = jax.nn.gelu(y, approximate=True)
    y = _linear(y, bp["fc2"], cfg.dtype)
    return x + y


# ---------------------------------------------------------------------------
# fused sampling: greedy / temperature / top-k inside the compiled step
# ---------------------------------------------------------------------------


def _is_key_batch(rng, logits) -> bool:
    """True when ``rng`` is a PER-SLOT key batch aligned with the
    leading (batch) dim of ``logits`` — ``(B, 2)`` raw uint32 keys, or
    ``(B,)`` typed keys — rather than one key for the whole call."""
    if logits.ndim < 2:
        return False
    if jnp.issubdtype(rng.dtype, jax.dtypes.prng_key):
        return rng.ndim == 1 and rng.shape[0] == logits.shape[0]
    return rng.ndim == 2 and rng.shape[0] == logits.shape[0]


#: Threefry-2x32 (Salmon et al., "Parallel random numbers: as easy as
#: 1, 2, 3"): rotation schedule and key-schedule parity constant
_THREEFRY_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
_THREEFRY_PARITY = 0x1BD11BDA


def fold_in(keys, data):
    """``jax.random.fold_in`` for raw threefry keys, inside a compiled
    step: ``keys`` ``(..., 2)`` uint32, ``data`` ``(...)`` any 32-bit
    integer, bit-for-bit the key ``jax.random.fold_in(keys[i],
    data[i])`` gives (``tests/test_serve_hostloop.py``).

    Written out in ``lax`` primitives because every step program folds
    its own sampling keys (the host folds none: docs/serving.md "Host
    phases") and ``jax.random.fold_in`` is expensive to LOWER: XLA:TPU
    gets the hash unrolled, and the rule that unrolls it re-traces
    ~250 jit-wrapped ``jnp`` operations for every module it appears in
    — 0.55 s of set-up a program on the chip's host, against 0.2 s for
    the rest of a 36-layer program (PERF.md section 6).  These ~130
    primitive binds lower in milliseconds."""
    lax = jax.lax
    data = lax.convert_element_type(data, jnp.uint32)
    keys = jnp.broadcast_to(keys, data.shape + (2,))
    k0, k1 = keys[..., 0], keys[..., 1]
    ks = (k0, k1, lax.bitwise_xor(lax.bitwise_xor(k0, k1),
                                  np.uint32(_THREEFRY_PARITY)))
    # fold_in hashes the count words (0, data) under the key
    x0, x1 = k0, lax.add(data, k1)
    for group in range(5):
        for rot in _THREEFRY_ROTATIONS[group % 2]:
            x0 = lax.add(x0, x1)
            x1 = lax.bitwise_or(
                lax.shift_left(x1, np.uint32(rot)),
                lax.shift_right_logical(x1, np.uint32(32 - rot)),
            )
            x1 = lax.bitwise_xor(x0, x1)
        x0 = lax.add(x0, ks[(group + 1) % 3])
        x1 = lax.add(
            lax.add(x1, ks[(group + 2) % 3]), np.uint32(group + 1)
        )
    return jnp.stack([x0, x1], axis=-1)


def stream_keys(base_key, streams):
    """Per-slot stream keys ``fold_in(engine base key, stream seed)`` —
    a function of request IDENTITY, never of call counters, so a
    speculative rollback replays the same draws and a ``k = 0`` spec
    stream equals the plain one (``serve/spec.py`` "RNG discipline").
    Traced INSIDE the step programs: the host hands over the integer
    seeds ``(B,)`` and folds nothing."""
    return fold_in(base_key, streams)


def slot_keys(base_key, streams, gens):
    """The plain decode program's per-slot sampling keys:
    ``fold_in(fold_in(base, streams[b]), gens[b])`` — the RAW emission
    key a ``k = 0`` speculative round consumes."""
    return fold_in(stream_keys(base_key, streams), gens)


def sample_tokens(logits, temps, rng, *, top_k: int = 0):
    """Sample next tokens INSIDE the compiled step — the host never
    round-trips the logits ("LLM Inference Acceleration via Efficient
    Operation Fusion", PAPERS.md: keep the sampling tail fused).

    ``logits`` is ``(..., V)`` f32, ``temps`` broadcasts against the
    leading dims: a slot with ``temp <= 0`` decodes greedily (argmax —
    bit-identical to the pre-sampling engine), a positive temperature
    draws via the Gumbel-argmax trick over ``logits / temp`` after the
    static ``top_k`` mask (0 = full vocab).  ``rng`` is either one key
    for the whole call (legacy) or a per-slot key batch ``(B, 2)``
    aligned with ``logits``'s batch dim — the engine's per-request
    stream keys, a function of request identity and stream position
    rather than any global call counter, so a replayed or rolled-back
    stream re-draws bit-identically."""
    temps = jnp.asarray(temps, jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    vocab = logits.shape[-1]
    masked = logits
    if 0 < top_k < vocab:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        masked = jnp.where(logits < kth, -jnp.inf, logits)
    scaled = masked / jnp.maximum(temps, 1e-6)[..., None]
    if _is_key_batch(rng, logits):
        gumbel = jax.vmap(
            lambda kk: jax.random.gumbel(
                kk, logits.shape[1:], dtype=jnp.float32
            )
        )(rng)
    else:
        gumbel = jax.random.gumbel(rng, logits.shape, dtype=jnp.float32)
    sampled = jnp.argmax(scaled + gumbel, axis=-1).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


# ---------------------------------------------------------------------------
# the model, written once: head, block, layer loop, tail
# ---------------------------------------------------------------------------
#
# Prefill, chunked prefill and decode are three ATTENTION DATAFLOWS over
# one model (docs/serving.md "One block, three dataflows").  A dataflow
# supplies ``attend(kv, layer, q, k, v) -> (ctx, kv)``: ``q, k, v`` are
# the block's projections ``(*rows, H, D)`` (``rows`` is ``(S, 1)`` for
# the prompt dataflows, ``(B,)`` for decode); ``ctx`` comes back one
# context row per input row, in row order, heads still apart (the block
# flattens it to ``x``'s shape); ``kv`` is the pool with this layer's
# K/V written.


def _rows_at(table, positions):
    """Rows of a per-position ``table`` at ``positions``: a gather for
    an int32 vector, a static slice (no gather in the program) for a
    ``range`` — rows that sit at their own index, a prompt from its
    start."""
    if isinstance(positions, range):
        return table[positions.start:positions.stop]
    return jnp.take(table, positions, axis=0)


def _embed_at(cfg: GptConfig, tree, tokens, positions):
    """The model's head: token embeddings at absolute ``positions``,
    one per row of ``tokens`` (an int32 vector, or a ``range``:
    :func:`_rows_at`).  Learned positions are added here; rotary
    configs get their f32 ``(cos, sin)`` rows back instead, for the
    dataflow's ``attend`` to rotate with.  Returns ``(x, rope)``,
    ``rope`` None without RoPE."""
    x = _embed(tree["word_embeddings"], tokens, cfg.dtype)
    if cfg.rotary:
        # a range needs no row past its end
        rows = (
            positions.stop if isinstance(positions, range)
            else cfg.max_seq_len
        )
        cos, sin = _rope_cos_sin(rows, _head_dim(cfg))
        return x, (_rows_at(cos, positions), _rows_at(sin, positions))
    rows = _rows_at(tree["position_embeddings"], positions)
    # (N, hidden) over x's unit axes: (S, 1, hidden) for a prompt
    rows = jnp.expand_dims(rows, range(1, x.ndim - 1))
    return x + rows.astype(cfg.dtype), None


def _mha_mixer(cfg, lp, x, kv, layer, attend):
    """The GPT mixer: LayerNorm, fused QKV, the dataflow's ``attend``,
    the output projection."""
    y = _layer_norm(x, lp["ln_attn"], cfg.layer_norm_eps)
    qkv = _linear(y, lp["qkv"], cfg.dtype).reshape(
        *x.shape[:-1], cfg.num_heads, 3, _head_dim(cfg)
    )
    ctx, kv = attend(kv, layer, *(qkv[..., i, :] for i in range(3)))
    return x + _linear(ctx.reshape(x.shape), lp["out"], cfg.dtype), kv


#: a mixer ``(cfg, lp, x, kv, layer, flow) -> (x + mixed, kv)`` and an FFN
#: ``(cfg, lp, x, flow) -> x + ffn`` per kind; ``flow`` is the dataflow:
#: the ``attend`` closure for ``"mha"``, a :class:`_Flow` for the rest
_MIXERS = {"mha": _mha_mixer}
_FFNS = {"gelu": lambda cfg, lp, x, flow: _mlp(x, lp, cfg)}


def _block(cfg, lp, x, kv, layer, attend):
    """One pre-norm decoder block over ``x`` ``(*rows, hidden)`` — THE
    block: every step body applies a layer through this function and no
    other way (``tests/test_serve.py`` pins it), whatever the layer's
    kinds.  Returns the new hidden and the cache set the mixer handed
    back."""
    kinds = layer_kinds(cfg)
    mixer, ffn = kinds[layer] if kinds else ("mha", "gelu")
    x, kv = _MIXERS[mixer](cfg, lp, x, kv, layer, attend)
    return _FFNS[ffn](cfg, lp, x, attend), kv


def _layers(cfg, tree, x, kv_pages, attend):
    """The layer loop.  The pool is the loop's CARRY (indexed by
    layer), never its xs/ys: a scanned-over pool is sliced and
    restacked every layer (docs/serving.md "The KV pool").  A stack
    whose layers differ in kind is a Python loop over its layers."""
    if layer_kinds(cfg) is not None:
        kv_pages = dict(kv_pages)
        for l, lp in enumerate(tree["layers"]):
            x, kv_pages = _block(cfg, lp, x, kv_pages, l, attend)
        return x, kv_pages

    def layer(carry, xs):
        lp, l = xs
        return _block(cfg, lp, *carry, l, attend), None

    (x, kv_pages), _ = jax.lax.scan(
        layer, (x, dict(kv_pages)),
        (tree["layers"]["block"], jnp.arange(cfg.num_layers)),
    )
    return x, kv_pages


def _final_logits(cfg: GptConfig, tree, h):
    """Final LayerNorm and the tied-embedding logits of rows ``h``."""
    h = _layer_norm(h, tree["ln_f"], cfg.layer_norm_eps)
    return _logits(tree, h, cfg.dtype)


def _sample_tail(logits, temps, rng, top_k):
    """The fused tail of every step program: the next token (argmax
    without a key) and the in-step non-finite screen over each row's
    logits.  Returns ``(logits, next_tokens, finite)``."""
    if rng is None:
        next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        next_tokens = sample_tokens(logits, temps, rng, top_k=top_k)
    return logits, next_tokens, jnp.isfinite(logits).all(axis=-1)


def _prompt_tail(cfg: GptConfig, tree, x, length, temp, rng, top_k):
    """The prompt dataflows' tail: the LAST LIVE row of ``x``
    ``(S, 1, hidden)`` only."""
    h_last = jax.lax.dynamic_slice_in_dim(
        x[:, 0], jnp.maximum(length - 1, 0), 1, 0
    )  # (1, hidden)
    logits = _final_logits(cfg, tree, h_last)[0]  # (V,) f32
    return _sample_tail(logits, temp, rng, top_k)


def _prompt_heads(q, k, v, rope):
    """A prompt dataflow's ``q, k, v`` ``(S, 1, H, D)`` as ``(1, H, S,
    D)``, ``q`` and ``k`` rotated at the rows' positions."""
    q, k, v = (jnp.transpose(t, (1, 2, 0, 3)) for t in (q, k, v))
    if rope is not None:
        q = fused_apply_rotary_pos_emb_cached(q, *rope)
        k = fused_apply_rotary_pos_emb_cached(k, *rope)
    return q, k, v


def _write_prompt(kv, layer, page_ids, k, v):
    """``k, v`` ``(1, H, S, D)`` as per-position rows ``(S, H, D)``
    into this layer's pages."""
    return cache_lib.write_prompt_kv(
        kv, layer, page_ids,
        jnp.transpose(k[0], (1, 0, 2)), jnp.transpose(v[0], (1, 0, 2)),
    )


# ---------------------------------------------------------------------------
# the kinds of a hybrid stack: KDA and MLA mixers, SwiGLU and routed FFNs
# ---------------------------------------------------------------------------
#
# A hybrid step works on rows ``x (T, hidden)``: the ``T`` positions of one
# prompt (``flow.prompt``) or one token of each of ``T`` decode slots.
# Weights are read in the compute dtype with f32 accumulation; RMSNorm
# statistics, the router, every gate and the whole KDA recurrence are f32.


class _Flow:
    """The dataflow of one hybrid step — what a mixer needs beyond its
    rows.  Prompt: ``length`` live rows from position 0, ``page_ids`` the
    prompt's pages, ``slot`` the decode slot whose state it will leave.
    Decode: ``lengths`` per slot (0 = idle), ``page_tables``.  ``live``
    ``(T,)`` marks the real rows: padding and idle rows leave every state
    as it was and reach no expert.  ``stats`` collects each routed layer's
    counts (the layer loop is a Python loop)."""

    def __init__(self, *, prompt, live, positions, page_size, length=None,
                 page_ids=None, slot=None, lengths=None, page_tables=None):
        self.prompt, self.live, self.positions = prompt, live, positions
        self.length, self.page_ids, self.slot = length, page_ids, slot
        self.lengths, self.page_tables = lengths, page_tables
        self.page_size = page_size
        self.stats = []


def _rms_norm(x, p, eps):
    """RMSNorm with f32 statistics; f32 out (callers cast)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return xf * jax.lax.rsqrt(ms + eps) * p["scale"]


def _matmul(x, w, dtype):
    """``x @ w`` in the compute dtype, f32 out."""
    return jnp.matmul(
        x.astype(dtype), w.astype(dtype), preferred_element_type=jnp.float32
    )


def _scaled(x, m: float):
    """``x * m`` for a configuration's multiplier; ``x`` itself at 1 (a
    model without muP multipliers traces no multiply)."""
    return x if m == 1.0 else x * m


def _swiglu(y, p, dtype, mults=(1.0, 1.0)):
    """``mults``: on the gate's pre-activation, on the output."""
    h = jax.nn.silu(
        _scaled(_matmul(y, p["gate"]["weight"], dtype), mults[0])
    ) * _matmul(y, p["up"]["weight"], dtype)
    return _scaled(_matmul(h, p["down"]["weight"], dtype), mults[1])


def _rope_partial(x, positions, theta):
    """Rotate-half RoPE at ``positions`` ``(T,)`` over the last axis of
    ``x`` ``(T, ..., R)``, f32."""
    r = x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (r,)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1).reshape(shape)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1).reshape(shape)
    xf = x.astype(jnp.float32)
    return xf * cos + rotate_half(xf) * sin


def _short_conv(flow, taps_w, pre, kv, name, li, live):
    """The recurrent mixers' short causal depthwise convolution over ``pre``
    ``(T, C)`` with taps ``taps_w (taps, C)``.  A prompt's rows before its
    start are zeros, and the tail a decode step will need is the last ``taps
    - 1`` inputs AT THE TRUE LENGTH; a decode step reads the slot's tail
    ``kv[name][li]`` and shifts it by one, but for an idle row (``live (T,
    1)`` false: a slot past its steps of a decode block may go on in the
    next one).  Returns ``(mixed f32, the prompt's tail or None, kv)``."""
    taps, t = taps_w.shape[0], pre.shape[0]
    if flow.prompt:
        window = jnp.concatenate(
            [jnp.zeros((taps - 1, pre.shape[1]), pre.dtype), pre], axis=0)
        mixed = sum(taps_w[i] * window[i:i + t] for i in range(taps))
        tail = jax.lax.dynamic_slice_in_dim(window, flow.length, taps - 1, 0)
        return mixed, tail, kv
    window = jnp.concatenate([kv[name][li], pre[:, None]], axis=1)
    mixed = sum(taps_w[i] * window[:, i] for i in range(taps))
    tails = kv[name].at[li].set(
        jnp.where(live[:, :, None], window[:, 1:], window[:, :-1]))
    return mixed, None, dict(kv, **{name: tails})


def _kda_mixer(cfg, lp, x, kv, layer, flow):
    """Kimi Delta Attention: q, k, v through a short causal convolution and
    SiLU, q and k L2-normalised, one decay per key channel, a delta-rule
    state per head (:mod:`apex_tpu.ops.kda`), RMSNorm and a sigmoid gate
    per head on the way out."""
    p, dtype = lp["kda"], cfg.dtype
    n, d = cfg.num_heads, cfg.head_dim
    li = cfg.layers_of("kda").index(layer)
    t = x.shape[0]
    y = _rms_norm(x, lp["norm_mixer"], cfg.rms_eps).astype(dtype)
    proj = _matmul(y, p["qkvg"]["weight"], dtype)
    pre = proj[:, : 3 * n * d].astype(dtype)      # what the conv tail keeps
    live = flow.live[:, None]
    g = jnp.where(live, cfg.kda_lower_bound * jax.nn.sigmoid(
        proj[:, 3 * n * d:] + p["g_bias"]), 0.0).reshape(t, n, d)
    beta = jnp.where(
        live, jax.nn.sigmoid(_matmul(y, p["beta"]["weight"], dtype)), 0.0)
    gate = jax.nn.sigmoid(_matmul(y, p["ogate"]["weight"], dtype))
    mixed, tail, kv = _short_conv(flow, p["conv"], pre, kv, "conv", li, live)
    q, k, v = jnp.split(jax.nn.silu(mixed).reshape(t, 3 * n, d), 3, axis=1)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * d**-0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    if flow.prompt:
        o, state = kda_ops.kda_chunked(q, k, v, g, beta)
        kv = cache_lib.write_slot_state(kv, li, flow.slot, state, tail)
    else:
        o, state = kda_ops.kda_step(kv["state"], li, q, k, v, g, beta)
        kv = dict(kv, state=state)
    o = _rms_norm(o, p["o_norm"], cfg.rms_eps) * gate[:, :, None]
    out = _matmul(o.reshape(t, n * d), p["out"]["weight"], dtype)
    return x + out.astype(dtype), kv


def _mla_mixer(cfg, lp, x, kv, layer, flow):
    """Multi-head latent attention.  The cache holds one row a token, ``[c
    | k_r | 0]``: the normalised latent and the rotated key all heads
    share.  A prompt attends un-absorbed over its own rows; a decode step
    attends in absorbed form over the latent pages
    (:mod:`apex_tpu.ops.mla`)."""
    p, dtype = lp["mla"], cfg.dtype
    n, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    li = cfg.layers_of("mla").index(layer)
    t = x.shape[0]
    scale = (dn + dr) ** -0.5
    y = _rms_norm(x, lp["norm_mixer"], cfg.rms_eps).astype(dtype)
    q = _matmul(y, p["q"]["weight"], dtype).reshape(t, n, dn + dr)
    a = _matmul(y, p["kv_a"]["weight"], dtype)
    c = _rms_norm(a[:, :r], p["kv_norm"], cfg.rms_eps).astype(dtype)
    k_r = _rope_partial(a[:, r:], flow.positions, cfg.rope_theta)
    q_r = _rope_partial(q[..., dn:], flow.positions, cfg.rope_theta)
    gate = jax.nn.sigmoid(_matmul(y, p["ogate"]["weight"], dtype))
    w = latent_row_width(r, dr)
    row = jnp.concatenate(
        [c, k_r.astype(dtype), jnp.zeros((t, w - r - dr), dtype)], axis=-1)
    w_b = p["kv_b"]["weight"].reshape(r, n, dn + dv)
    f32 = dict(preferred_element_type=jnp.float32)
    if flow.prompt:
        kv = cache_lib.write_prompt_latent(kv, li, flow.page_ids, row)
        with jax.named_scope("mla_prefill_attention"):
            kvb = _matmul(c, p["kv_b"]["weight"], dtype).reshape(
                t, n, dn + dv).astype(dtype)
            s = jnp.einsum(
                "qnd,knd->nqk", q[..., :dn].astype(dtype), kvb[..., :dn],
                **f32,
            ) + jnp.einsum(
                "qnd,kd->nqk", q_r.astype(dtype), k_r.astype(dtype), **f32)
            idx = jnp.arange(t)
            s = jnp.where(idx[:, None] >= idx[None, :], s * scale, -jnp.inf)
            o = jnp.einsum(
                "nqk,knd->qnd", jax.nn.softmax(s, axis=-1).astype(dtype),
                kvb[..., dn:], **f32)
    else:
        pos = flow.positions
        # an idle row (a free slot, or one past its steps of a decode
        # block) writes into the null page, whatever its table holds
        pages = jnp.where(
            flow.live, flow.page_tables[jnp.arange(t), pos // flow.page_size],
            cache_lib.NULL_PAGE,
        )
        kv = cache_lib.append_token_latent(
            kv, li, pages, pos % flow.page_size, row)
        q_abs = jnp.einsum(
            "bnd,rnd->bnr", q[..., :dn].astype(dtype),
            w_b[..., :dn].astype(dtype), **f32)
        q_row = jnp.concatenate(
            [q_abs, q_r, jnp.zeros((t, n, w - r - dr), jnp.float32)], -1)
        ctx = mla_decode_attention(
            q_row, kv["latent"], flow.page_tables, flow.lengths,
            layer=li, scale=scale,
        )
        o = jnp.einsum(
            "bnr,rnd->bnd", ctx[..., :r].astype(dtype),
            w_b[..., dn:].astype(dtype), **f32)
    out = _matmul(
        (o * gate[:, :, None]).reshape(t, n * dv), p["out"]["weight"], dtype)
    return x + out.astype(dtype), kv


def _ssm_branch(cfg, p, y, kv, li, flow):
    """The Mamba-2 branch over the block's normed rows ``y`` ``(T, hidden)``
    f32: ``in_proj`` to ``z | x B C | dt``, a short causal convolution with
    bias and SiLU over ``x B C``, the selective state-space recurrence
    (:mod:`apex_tpu.ops.ssm`; state per slot, f32), the skip ``D x``, the
    gate ``SiLU(z)`` and an RMSNorm inside each group's channels."""
    dtype = cfg.dtype
    nh, hd, g, n = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                    cfg.ssm_state)
    ds, c = cfg.ssm_width, cfg.ssm_conv_width
    t = y.shape[0]
    proj = _matmul(
        _scaled(y, cfg.ssm_in_multiplier), p["in_proj"]["weight"], dtype)
    if any(m != 1.0 for m in cfg.ssm_multipliers):
        mz, mx, mb, mc, mdt = cfg.ssm_multipliers
        proj = proj * np.repeat(
            np.asarray([mz, mx, mb, mc, mdt], np.float32),
            [ds, ds, g * n, g * n, nh])
    z = proj[:, :ds].reshape(t, nh, hd)
    pre = proj[:, ds:ds + c].astype(dtype)        # what the conv tail keeps
    live = flow.live[:, None]
    # a padding or idle row takes no step: its state stays as it was
    dt = jnp.where(
        live, jax.nn.softplus(proj[:, ds + c:] + p["dt_bias"]), 0.0)
    mixed, tail, kv = _short_conv(
        flow, p["conv"], pre, kv, "ssm_conv", li, live)
    xbc = jax.nn.silu(mixed + p["conv_bias"])
    x = xbc[:, :ds].reshape(t, nh, hd)
    b = xbc[:, ds:ds + g * n].reshape(t, g, n)
    c_ = xbc[:, ds + g * n:].reshape(t, g, n)
    a = -jnp.exp(p["a_log"])
    if flow.prompt:
        o, state = ssm_ops.ssd_chunked(x, dt, a, b, c_, chunk=cfg.ssm_chunk)
        kv = cache_lib.write_slot_state(
            kv, li, flow.slot, state, tail, names=("ssm", "ssm_conv"))
    else:
        o, state = ssm_ops.ssm_step(kv["ssm"], li, x, dt, a, b, c_)
        kv = dict(kv, ssm=state)
    o = (o + p["d"][:, None] * x) * jax.nn.silu(z)
    # mamba_rms_norm, norm_before_gate false: RMSNorm after the gate,
    # inside each group's channels, then one scale a channel
    og = o.reshape(t, g, ds // g)
    og = og * jax.lax.rsqrt(
        jnp.mean(og * og, axis=-1, keepdims=True) + cfg.rms_eps)
    out = _matmul(
        og.reshape(t, ds) * p["norm"]["scale"], p["out_proj"]["weight"],
        dtype)
    return _scaled(out, cfg.ssm_out_multiplier), kv


def _gqa_branch(cfg, p, y, kv, li, flow):
    """The grouped-query attention branch over the same rows: full-width
    rotate-half RoPE on q and k, ``num_kv_heads`` K/V heads in the paged
    pool (query head ``i`` on KV head ``i // (H / kv)``).  A prompt attends
    over its own rows through the flash kernel, K/V repeated per query head
    for the prompt only; a decode step walks the pages, each copied in once
    for all the query heads of its KV heads
    (:func:`~apex_tpu.ops.paged_attention.paged_decode_attention`)."""
    dtype = cfg.dtype
    n, nkv, d = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    t = y.shape[0]
    qkv = _matmul(
        _scaled(y, cfg.attention_in_multiplier), p["wqkv"]["weight"], dtype)
    q = qkv[:, :n * d].reshape(t, n, d)
    k = _scaled(qkv[:, n * d:(n + nkv) * d], cfg.key_multiplier).reshape(
        t, nkv, d)
    v = qkv[:, (n + nkv) * d:].reshape(t, nkv, d).astype(dtype)
    q = _rope_partial(q, flow.positions, cfg.rope_theta).astype(dtype)
    k = _rope_partial(k, flow.positions, cfg.rope_theta).astype(dtype)
    scale = d ** -0.5
    if flow.prompt:
        kv = cache_lib.write_prompt_kv(kv, li, flow.page_ids, k, v)
        heads = lambda x: jnp.transpose(x, (1, 0, 2))[None]  # noqa: E731
        rep = lambda x: jnp.repeat(heads(x), n // nkv, axis=1)  # noqa: E731
        ctx = flash_attention(
            heads(q), rep(k), rep(v), causal=True, scale=scale)
        o = jnp.transpose(ctx[0], (1, 0, 2))
    else:
        pos = flow.positions
        # an idle row writes into the null page, whatever its table holds
        pages = jnp.where(
            flow.live, flow.page_tables[jnp.arange(t), pos // flow.page_size],
            cache_lib.NULL_PAGE,
        )
        kv = cache_lib.append_token_kv(
            kv, li, pages, pos % flow.page_size, k, v)
        o = paged_decode_attention(
            q, kv["k"], kv["v"], flow.page_tables, flow.lengths,
            layer=li, scale=scale, kv_heads=nkv,
        )
    out = _matmul(o.reshape(t, n * d), p["wo"]["weight"], dtype)
    return _scaled(out, cfg.attention_out_multiplier), kv


def _ssm_gqa_mixer(cfg, lp, x, kv, layer, flow):
    """Falcon-H1's parallel block: a Mamba-2 branch and a grouped-query
    attention branch read ONE RMSNorm of the rows and both are added to the
    residual."""
    li = cfg.layers_of("ssm_gqa").index(layer)
    y = _rms_norm(x, lp["norm_mixer"], cfg.rms_eps)
    with jax.named_scope("ssm_branch"):
        m, kv = _ssm_branch(cfg, lp["ssm"], y, kv, li, flow)
    with jax.named_scope("gqa_branch"):
        a, kv = _gqa_branch(cfg, lp["attn"], y, kv, li, flow)
    return x + (m + a).astype(cfg.dtype), kv


def _dense_ffn(cfg, lp, x, flow):
    y = _rms_norm(x, lp["norm_ffn"], cfg.rms_eps).astype(cfg.dtype)
    return x + _swiglu(
        y, lp["mlp"], cfg.dtype, cfg.mlp_multipliers).astype(cfg.dtype)


def _moe_ffn(cfg, lp, x, flow):
    """This chip's share of the routed layer plus the shared expert: route
    over all the layer's experts (f32, on the un-rounded norm output), add
    the held experts' terms only."""
    p = lp["moe"]
    y32 = _rms_norm(x, lp["norm_ffn"], cfg.rms_eps)
    idx, weights = route_group_limited(
        y32, p["router"]["weight"], p["expert_bias"], top_k=cfg.top_k,
        n_group=cfg.n_group, topk_group=cfg.topk_group,
        scale=cfg.routed_scaling_factor,
    )
    y = y32.astype(cfg.dtype)
    out, stats = dropless_moe(
        y, idx, weights, p["experts"], held=cfg.held_experts, live=flow.live)
    flow.stats.append(stats)
    out = out + _swiglu(y, p["shared"], cfg.dtype)
    return x + out.astype(cfg.dtype)


_MIXERS.update(kda=_kda_mixer, mla=_mla_mixer, ssm_gqa=_ssm_gqa_mixer)
_FFNS.update(dense=_dense_ffn, moe=_moe_ffn)


def _hybrid_embed(cfg, tree, tokens):
    x = _embed(tree["word_embeddings"], tokens, cfg.dtype)
    if cfg.embedding_multiplier == 1.0:
        return x
    return (x.astype(jnp.float32) * cfg.embedding_multiplier).astype(
        cfg.dtype)


def _hybrid_logits(cfg, tree, h):
    """Final RMSNorm and the untied head over the vocabulary slice held."""
    h = _scaled(
        _rms_norm(h, tree["norm_f"], cfg.rms_eps), cfg.lm_head_multiplier)
    return _matmul(h, tree["lm_head"]["weight"], cfg.dtype)


def _with_counts(cfg, flow, out):
    """A routed model's step hands its MoE counts (pairs routed to held
    experts, distinct held experts touched, summed over layers) back INSIDE
    the token readback: two more int32 behind the sampled token(s)."""
    logits, tokens, finite = out
    if is_routed(cfg):
        tokens = jnp.concatenate(
            [jnp.reshape(tokens, (-1,)), sum(flow.stats)])
    return logits, tokens, finite


def _hybrid_prefill(cfg, params, cache, tokens, length, page_ids, slot,
                    temp, rng, page_size, top_k):
    tree = _tree(params)
    s = tokens.shape[0]
    x = _hybrid_embed(cfg, tree, tokens[:, 0])
    flow = _Flow(
        prompt=True, live=jnp.arange(s) < length, positions=jnp.arange(s),
        page_size=page_size, length=length, page_ids=page_ids, slot=slot,
    )
    x, cache = _layers(cfg, tree, x, cache, flow)
    h_last = jax.lax.dynamic_slice_in_dim(x, jnp.maximum(length - 1, 0), 1, 0)
    out = _sample_tail(_hybrid_logits(cfg, tree, h_last)[0], temp, rng, top_k)
    return *_with_counts(cfg, flow, out), cache


def _hybrid_decode(cfg, tree, cache, tokens, lengths, page_tables,
                   page_size):
    x = _hybrid_embed(cfg, tree, tokens)
    flow = _Flow(
        prompt=False, live=lengths > 0, positions=jnp.maximum(lengths - 1, 0),
        page_size=page_size, lengths=lengths, page_tables=page_tables,
    )
    x, cache = _layers(cfg, tree, x, cache, flow)
    return _hybrid_logits(cfg, tree, x), cache, flow


def _hybrid_decode_block(cfg, tree, cache, tokens, lengths, page_tables,
                         temps, rng, steps, page_size, top_k, block):
    """``block`` decode iterations in ONE program: iteration ``j`` feeds
    slot ``b`` the token iteration ``j - 1`` sampled for it, at context
    ``lengths[b] + j``, while ``j < steps[b]``; past its ``steps`` a slot is
    idle (no state change, no page written, no expert reached).  The host
    then pays its launch, its transfers and its bookkeeping once a block
    (docs/serving.md "Layer kinds and the cache set").  ``rng`` is ``(block,
    B, 2)`` keys (or None: argmax).  Returns ``(last logits (B, V), tokens
    (block * B,) [+ the MoE counts summed over the block], finite (B,),
    cache)``; ``block = 1`` is the plain single step."""
    counts0 = jnp.zeros((2,), jnp.int32)

    def one(cache, tokens, j, key):
        live = j < steps
        logits, cache, flow = _hybrid_decode(
            cfg, tree, cache, tokens, jnp.where(live, lengths + j, 0),
            page_tables, page_size,
        )
        logits, nxt, finite = _sample_tail(logits, temps, key, top_k)
        counts = sum(flow.stats) if flow.stats else counts0
        return logits, cache, jnp.where(live, nxt, tokens), finite | ~live, \
            counts

    if block == 1:
        logits, cache, out, finite, counts = one(cache, tokens, 0, rng)
    else:
        def body(carry, xs):
            cache, tokens, _, finite, counts = carry
            j, key = xs
            logits, cache, tokens, fin, c = one(cache, tokens, j, key)
            return (cache, tokens, logits, finite & fin, counts + c), tokens

        b = tokens.shape[0]
        (cache, _, logits, finite, counts), out = jax.lax.scan(
            body,
            (cache, tokens, jnp.zeros((b, cfg.vocab_size), jnp.float32),
             jnp.ones((b,), jnp.bool_), counts0),
            (jnp.arange(block), rng),
        )
        out = out.reshape(-1)
    if is_routed(cfg):
        out = jnp.concatenate([out, counts])
    return logits, out, finite, cache


# ---------------------------------------------------------------------------
# prefill: full-sequence forward that also yields per-position K/V
# ---------------------------------------------------------------------------


def prefill_body(
    cfg: GptConfig,
    params,
    kv_pages: dict,
    tokens,          # (S, 1) int32 — one sequence, bucket-padded
    length,          # ()    int32 — live prompt positions
    page_ids,        # (S/page,) int32 — null-page entries pad the tail
    temp=None,       # ()    f32 sampling temperature (None = argmax)
    rng=None,        # PRNG key for the fused sampler
    *,
    page_size: int,
    kv_wire: str = "f32",
    top_k: int = 0,
    slot=None,       # ()    int32 — the decode slot (recurrent state only)
):
    """Full prefill: forward the (padded) prompt, write every layer's
    K/V into the assigned pages, and return the last live position's
    logits.  Causality makes the padding free: a live query row never
    attends a padded (later) key, so the padded tail needs no mask —
    its garbage K/V land in pages the decode ``lengths`` never reads
    (or in the null page).

    Returns ``(logits (V,) f32, next_token () int32, finite () bool,
    kv_pages)`` — ``finite`` is the in-step non-finite screen
    (``isfinite(logits).all()``): the quarantine evidence the scheduler
    reads WITHOUT paying the (V,) device→host logits copy.

    ``page_size`` and ``kv_wire`` restate what the pool's shape and
    planes say (the writes read them there); they stay for callers that
    pass them (``benchmark/rehearse_compile.py``).

    A stack whose layers differ in kind (:func:`layer_kinds`) takes the
    same arguments plus ``slot``: its prompt leaves the sequence's recurrent
    state in that decode slot, padding rows excluded, and a routed model's
    ``next_token`` carries the MoE counts behind it (:func:`_with_counts`).
    """
    if layer_kinds(cfg) is not None:
        return _hybrid_prefill(
            cfg, params, kv_pages, tokens, length, page_ids, slot, temp,
            rng, page_size, top_k,
        )
    del page_size, kv_wire
    tree = _tree(dequantize_params(params))
    x, rope = _embed_at(cfg, tree, tokens, range(tokens.shape[0]))

    def attend(kv, l, q, k, v):
        q, k, v = _prompt_heads(q, k, v, rope)
        ctx = flash_attention(
            q, k, v, causal=True, scale=_head_dim(cfg)**-0.5
        )
        return (
            jnp.transpose(ctx, (2, 0, 1, 3)),
            _write_prompt(kv, l, page_ids, k, v),
        )

    x, kv_pages = _layers(cfg, tree, x, kv_pages, attend)
    return *_prompt_tail(cfg, tree, x, length, temp, rng, top_k), kv_pages


# ---------------------------------------------------------------------------
# chunked prefill: a page-multiple prompt slice with carry-in KV offset
# ---------------------------------------------------------------------------


def chunk_prefill_body(
    cfg: GptConfig,
    params,
    kv_pages: dict,
    tokens,          # (C, 1) int32 — one chunk, bucket-padded
    length,          # ()     int32 — live tokens in THIS chunk
    offset,          # ()     int32 — absolute position of tokens[0]
    chunk_page_ids,  # (C/page,) int32 — null entries skip the write
                     # (cached pages a borrower must never rewrite)
    page_table,      # (NP,)  int32 — the request's full page table
    temp=None,       # ()     f32 sampling temperature (None = argmax)
    rng=None,        # PRNG key for the fused sampler
    *,
    page_size: int,
    top_k: int = 0,
):
    """One page-multiple prefill chunk with **carry-in KV offset**: the
    chunk's queries attend to every position before ``offset`` through
    the paged cache (a dense gather over ``page_table`` — committed
    prefix-cache pages and this request's own earlier chunks read the
    same way) plus the in-chunk keys causally.  Writes the chunk's K/V
    to ``chunk_page_ids``; entries pointing at the null page skip
    pages a borrowed cache run already holds (re-running the final
    chunk of a full-prefix hit recomputes the first token's logits
    WITHOUT touching shared pages).

    The chunk slicing is deterministic, so a cache-hit request that
    re-runs the same final chunk over bit-identical cached pages
    produces bit-identical logits to the cold run — the foundation of
    the serve_bench bit-identity proof.

    Returns ``(logits (V,) f32, next_token () int32, finite () bool,
    kv_pages)`` for the LAST live chunk position (only the final chunk's
    token is consumed; earlier chunks run for their KV writes).
    """
    tree = _tree(dequantize_params(params))
    c = tokens.shape[0]
    heads = cfg.num_heads
    x, rope = _embed_at(
        cfg, tree, tokens, offset + jnp.arange(c, dtype=jnp.int32)
    )
    t_ctx = page_table.shape[0] * page_size
    # carry-in mask: gathered row t is absolute position t of this
    # sequence; only positions before the chunk are valid carry
    carry_valid = jnp.arange(t_ctx) < offset          # (T,)
    causal = (
        jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
    )                                                  # (C, C) in-chunk
    mask = jnp.concatenate(
        [jnp.broadcast_to(carry_valid[None, :], (c, t_ctx)), causal],
        axis=1,
    )[None]                                            # (1, C, T+C)
    scale = _head_dim(cfg)**-0.5
    big_neg = jnp.asarray(jnp.finfo(jnp.float32).min, jnp.float32)

    def attend(kv, l, q, k, v):
        q, k, v = _prompt_heads(q, k, v, rope)         # (1, H, C, D)
        # carry-in K/V: dense gather of the whole page table, read
        # through the cache wire (exactly how decode reads it), as
        # (H, T, D) f32 in absolute position order
        k_ctx = gather_history(
            kv["k"], kv.get("k_scale"), l, page_table[None], heads,
            _head_dim(cfg),
        )[0]
        v_ctx = gather_history(
            kv["v"], kv.get("v_scale"), l, page_table[None], heads,
            _head_dim(cfg),
        )[0]
        # in-chunk keys stay exact (the same in-flight numerics the
        # monolithic prefill uses for every prompt position)
        kf = k[0].astype(jnp.float32)                  # (H, C, D)
        vf = v[0].astype(jnp.float32)
        k_all = jnp.concatenate([k_ctx, kf], axis=1)   # (H, T+C, D)
        v_all = jnp.concatenate([v_ctx, vf], axis=1)
        qf = q[0].astype(jnp.float32)                  # (H, C, D)
        scores = jnp.einsum("hcd,htd->hct", qf, k_all) * scale
        scores = jnp.where(mask, scores, big_neg)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("hct,htd->hcd", probs, v_all)  # (H, C, D)
        # null entries dump cached pages' re-runs into write-only
        # garbage
        return (
            jnp.transpose(ctx, (1, 0, 2)),
            _write_prompt(kv, l, chunk_page_ids, k, v),
        )

    x, kv_pages = _layers(cfg, tree, x, kv_pages, attend)
    return *_prompt_tail(cfg, tree, x, length, temp, rng, top_k), kv_pages


# ---------------------------------------------------------------------------
# decode: one token per running sequence through the paged cache
# ---------------------------------------------------------------------------


def _decode_step(
    cfg: GptConfig,
    tree,         # dequantized ``params["params"]`` tree
    kv_pages: dict,
    tokens,       # (B,) int32 — current token per slot
    lengths,      # (B,) int32 — context length AFTER this token; 0 = idle
    page_tables,  # (B, NP) int32
    *,
    page_size: int,
):
    """The shared decode compute: embed the token column, append each
    layer's K/V at this position's page slot, run the fused paged
    attention, and return the final-LN logits.  This ONE function is
    what both the plain decode program and the speculative verify scan
    (:func:`apex_tpu.serve.spec.verify_body`) execute — same math,
    same shapes, same kernels — which is precisely why a greedy
    speculative stream is bit-identical to the sequential baseline by
    construction.  Returns ``(logits (B, V) f32, kv_pages)``."""
    pos = jnp.maximum(lengths - 1, 0)  # this token's position; idle -> 0
    x, rope = _embed_at(cfg, tree, tokens, pos)
    page_ids = page_tables[jnp.arange(tokens.shape[0]), pos // page_size]
    slots = pos % page_size
    cos_rows, sin_rows = rope or (None, None)

    def attend(kv, l, q, k, v):
        # K is rotated here; the kernel rotates Q (and dequantizes the
        # int8 wire) itself
        if rope is not None:
            k = _rope_rows(k, cos_rows, sin_rows)
        kv = cache_lib.append_token_kv(kv, l, page_ids, slots, k, v)
        ctx = paged_decode_attention(
            q, kv["k"], kv["v"], page_tables, lengths,
            layer=l, scale=_head_dim(cfg)**-0.5,
            k_scale=kv.get("k_scale"), v_scale=kv.get("v_scale"),
            rope_cos=cos_rows, rope_sin=sin_rows,
        )
        return ctx, kv

    x, kv_pages = _layers(cfg, tree, x, kv_pages, attend)
    return _final_logits(cfg, tree, x), kv_pages  # (B, V) f32


def decode_body(
    cfg: GptConfig,
    params,
    kv_pages: dict,
    tokens,       # (B,) int32 — current token per slot
    lengths,      # (B,) int32 — context length AFTER this token; 0 = idle
    page_tables,  # (B, NP) int32
    temps=None,   # (B,) f32 per-slot sampling temperature (None = argmax)
    rng=None,     # PRNG key (or per-slot key batch) for the sampler
    *,
    page_size: int,
    kv_wire: str = "f32",
    top_k: int = 0,
    steps=None,   # (B,) int32 — iterations each slot runs (hybrid blocks)
    block: int = 1,
):
    """One continuous-batching decode iteration over the full slot
    array (:func:`_decode_step` plus the fused sampling tail).  Per
    layer: project the token, rotate K, append K/V to this position's
    page slot, and run the fused single-query paged attention (query
    RoPE + int8 dequant fused in the kernel).  Idle slots
    (``lengths == 0``) write into the null page and read zeros.

    Returns ``(logits (B, V) f32, next_tokens (B,) int32, finite (B,)
    bool, kv_pages)`` — ``finite[b]`` is slot ``b``'s in-step
    non-finite screen over its logits row: a poisoned sequence (NaN in
    its KV pages or a numerically blown state) flags ONLY its own
    slot, so the scheduler's quarantine can evict the offender without
    touching the rest of the batch or reading the (B, V) logits back.
    ``kv_wire`` restates what the pool's planes say and stays for
    callers that pass it (``benchmark/rehearse_compile.py``).
    """
    del kv_wire
    if layer_kinds(cfg) is not None:
        # a stack whose layers differ in kind: ``block`` iterations a
        # program (:func:`_hybrid_decode_block`; 1 = the plain step)
        if steps is None:
            steps = jnp.where(lengths > 0, block, 0)
        return _hybrid_decode_block(
            cfg, _tree(params), kv_pages, tokens, lengths, page_tables,
            temps, rng, steps, page_size, top_k, block,
        )
    logits, kv_pages = _decode_step(
        cfg, _tree(dequantize_params(params)), kv_pages, tokens, lengths,
        page_tables, page_size=page_size,
    )
    return *_sample_tail(logits, temps, rng, top_k), kv_pages
