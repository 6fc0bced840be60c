"""Functional GPT forward for serving — prefill and decode bodies.

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

Serving scope: dense blocks, single model shard (no SP/CP/MoE — the
engine validates).  **Weight wires**: :func:`quantize_params` /
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
from apex_tpu.ops.attention import flash_attention
from apex_tpu.ops.layer_norm import fused_layer_norm_affine
from apex_tpu.ops.paged_attention import (
    gather_history,
    paged_decode_attention,
)
from apex_tpu.ops.rope import fused_apply_rotary_pos_emb_cached, rotate_half
from apex_tpu.parallel import comm
from apex_tpu.serve import cache as cache_lib

__all__ = [
    "validate_config",
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


def validate_config(cfg: GptConfig) -> GptConfig:
    """Serving supports the dense single-shard GPT stack."""
    if cfg.sequence_parallel or cfg.context_parallel:
        raise ValueError(
            "serving requires sequence_parallel=False and "
            "context_parallel=None (the engine owns the whole sequence)"
        )
    if cfg.num_experts:
        raise ValueError("MoE serving is not supported yet")
    return cfg


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


def _block(cfg: GptConfig, lp, x, kv, layer, attend):
    """One pre-LN decoder block over ``x`` ``(*rows, hidden)`` — THE
    block: every step body applies a layer through this function and no
    other way (``tests/test_serve.py`` pins it).  Returns the new
    hidden and the pool ``attend`` handed back."""
    y = _layer_norm(x, lp["ln_attn"], cfg.layer_norm_eps)
    qkv = _linear(y, lp["qkv"], cfg.dtype).reshape(
        *x.shape[:-1], cfg.num_heads, 3, _head_dim(cfg)
    )
    ctx, kv = attend(kv, layer, *(qkv[..., i, :] for i in range(3)))
    x = x + _linear(ctx.reshape(x.shape), lp["out"], cfg.dtype)
    return _mlp(x, lp, cfg), kv


def _layers(cfg: GptConfig, tree, x, kv_pages, attend):
    """The layer loop.  The pool is the loop's CARRY (indexed by
    layer), never its xs/ys: a scanned-over pool is sliced and
    restacked every layer (docs/serving.md "The KV pool")."""

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
    """
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
            kv["k"], kv.get("k_scale"), l, page_table[None], heads
        )[0]
        v_ctx = gather_history(
            kv["v"], kv.get("v_scale"), l, page_table[None], heads
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
    logits, kv_pages = _decode_step(
        cfg, _tree(dequantize_params(params)), kv_pages, tokens, lengths,
        page_tables, page_size=page_size,
    )
    return *_sample_tail(logits, temps, rng, top_k), kv_pages
