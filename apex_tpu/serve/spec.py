"""Speculative decoding — draft proposals, one-step verify, rollback.

A small **draft** model proposes ``k`` tokens; ONE target-model program
verifies all of them by scoring ``k+1`` positions in a single scan, so
each expensive target dispatch emits up to ``k+1`` tokens ("LLM
Inference Acceleration via Efficient Operation Fusion", PAPERS.md: the
verification step replaces ``k`` sequential decode dispatches with one
denser program).  Three device bodies live here, compiled by the
engine exactly like every other step program:

- :func:`draft_body` — a ``k+1``-step scan over the draft model: feed
  the stream's last token, then each proposal, so the draft KV cache
  stays in lockstep with the proposals (the extra step writes the last
  proposal's KV; its logits are discarded).
- :func:`verify_body` — a scan of :func:`apex_tpu.serve.model.
  _decode_step` — the EXACT function the plain decode program runs —
  over the ``k+1`` token columns at successive lengths.  Position
  ``j``'s logits are therefore bit-identical to what ``j`` sequential
  decode iterations would have produced, which is what makes the
  greedy speculative stream bit-identical to the non-speculative
  baseline *by construction*, not by tolerance.
- :func:`rollback_body` — per-slot KV truncation: zero the rows of
  rejected positions through the page table (int8 wire: codes to 0,
  scales to the init value 1.0).  Rejected rows are overwritten before
  any read even without it (the next round's writes start exactly at
  the first stale position), so rollback is hygiene the leak/COW
  drills can assert against, not a correctness crutch — the REAL
  correctness obligation is the scheduler's pre-round COW fork of
  shared tail pages, which keeps both verify writes and this rollback
  off pages a co-reader holds.

**Acceptance** (:func:`speculative_verify`, pure and CPU-testable):

- greedy (``temp <= 0``): proposal ``d_{j+1}`` is accepted iff it
  equals ``argmax`` of the target's position-``j`` logits; the emitted
  run ``tgt_0..tgt_a`` IS the sequential greedy chain.
- temperature: the Leviathan et al. rejection sampler — accept
  ``d_{j+1}`` with probability ``min(1, p_j(d)/q_j(d))``, emit a
  residual sample from ``normalize(max(p_j - q_j, 0))`` on the first
  rejection, a bonus sample from ``p_k`` when everything is accepted.
  The emitted marginal is exactly the target softmax (the chi-square
  test in ``tests/test_serve.py`` proves it empirically), and the
  ``k = 0`` stream is bit-identical to plain decode because the bonus
  sample is literally :func:`~apex_tpu.serve.model.sample_tokens`
  under the same per-slot stream key.

**RNG discipline**: every draw keys off ``fold_in(stream_key,
emission_index)`` — a function of the request's identity and its
position in the stream, never of a global call counter — so a
rollback replays bit-identically and a ``k = 0`` speculative
temperature stream equals the non-speculative one.  Acceptance
uniforms and draft proposals ride distinct ``fold_in`` tags off the
same chain so no draw is ever reused.

Draft KV pages live in the same :class:`~apex_tpu.serve.cache.
PagePool` under the ``"draft"`` page namespace; ``leak_check`` proves
they are neither leaked nor shared into the :class:`~apex_tpu.serve.
cache.PrefixCache`.  See docs/serving.md "Speculative decoding".
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.models.gpt import GptConfig
from apex_tpu.serve import cache as cache_lib
from apex_tpu.serve import model as model_lib

__all__ = [
    "SpecConfig",
    "DRAFT_TAG",
    "ACCEPT_TAG",
    "target_probs",
    "speculative_verify",
    "pin_failed_drafts",
    "draft_body",
    "verify_body",
    "rollback_body",
    "draft_from_params",
]

#: ``fold_in`` sub-stream tags: the emission key at index ``g`` is the
#: RAW ``fold_in(stream_key, g)`` (so ``k = 0`` equals plain decode);
#: draft proposals and acceptance uniforms fold these tags on top.
DRAFT_TAG = 0x0D12AF7
ACCEPT_TAG = 0x0ACCE97


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Speculative-decoding knobs for an
    :class:`~apex_tpu.serve.engine.InferenceEngine`.

    ``mode`` names the intended acceptance regime — ``"greedy"``
    (exact-match, bit-identical output) or ``"temperature"`` (the
    rejection sampler).  The compiled verify program always dispatches
    per slot on the request temperature (``temp <= 0`` slots are
    exact-match either way), so a mixed batch is safe in both modes;
    the field exists so deployments state their contract and the
    scheduler can gate accordingly.
    """

    #: the draft model's parameter tree (``GptModel.init`` layout)
    draft_params: object
    #: proposals per round; each target dispatch emits up to ``k + 1``
    #: tokens.  ``k = 0`` degenerates to plain decode through the
    #: verify program (the rng-discipline regression pin).
    k: int = 4
    mode: str = "greedy"
    #: draft model shape; None = the target config (self-draft — the
    #: "friendly draft" whose greedy acceptance is 100% by definition)
    draft_cfg: Optional[GptConfig] = None
    #: degradation ladder: once the windowed acceptance rate over
    #: ``window`` rounds falls below this floor, the scheduler falls
    #: back to plain decode (``serve/spec_fallbacks``) — a draft that
    #: stopped predicting must not keep taxing every round
    min_accept_rate: float = 0.3
    window: int = 64

    def __post_init__(self):
        if self.k < 0:
            raise ValueError(f"k must be >= 0, got {self.k}")
        if self.mode not in ("greedy", "temperature"):
            raise ValueError(
                f"mode must be greedy|temperature, got {self.mode!r}"
            )
        if not 0.0 <= self.min_accept_rate <= 1.0:
            raise ValueError("min_accept_rate must be within [0, 1]")
        if self.window < 1:
            raise ValueError("window must be >= 1")


def draft_from_params(params, num_layers: int):
    """A draft parameter tree from the FIRST ``num_layers`` blocks of a
    scanned GPT tree (embeddings, final LN and any position table are
    shared with the target) — the ``serve_bench --draft-layers N``
    draft: same checkpoint, truncated depth, no second training run."""
    if num_layers < 1:
        raise ValueError(f"draft needs >= 1 layer, got {num_layers}")
    tree = dict(params["params"])
    block = jax.tree_util.tree_map(
        lambda leaf: leaf[:num_layers], tree["layers"]["block"]
    )
    tree["layers"] = {"block": block}
    return {"params": tree}


# ---------------------------------------------------------------------------
# pure acceptance machinery (CPU-testable, used inside the verify program)
# ---------------------------------------------------------------------------


def target_probs(logits, temps, *, top_k: int = 0):
    """The sampling distribution :func:`~apex_tpu.serve.model.
    sample_tokens` draws from — softmax of the top-k-masked logits
    scaled by the temperature.  ``logits`` is ``(..., V)`` f32,
    ``temps`` broadcasts over the leading dims.  Rows with
    ``temp <= 0`` are greedy point masses in spirit; their rows here
    are computed at the clamped temperature and must not be consumed
    (the greedy acceptance path never reads them)."""
    temps = jnp.asarray(temps, jnp.float32)
    vocab = logits.shape[-1]
    masked = logits
    if 0 < top_k < vocab:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        masked = jnp.where(logits < kth, -jnp.inf, logits)
    scaled = masked / jnp.maximum(temps, 1e-6)[..., None]
    return jax.nn.softmax(scaled, axis=-1)


def pin_failed_drafts(draft_tokens, draft_probs, draft_ok, vocab: int):
    """A faulted / non-finite draft must not smuggle a token into the
    stream: pin the proposals of slots with ``draft_ok`` False to one
    fixed id and claim the matching point-mass draft distribution — the
    rejection sampler preserves the target distribution for ANY
    claimed q consistent with how d was drawn, and greedy only ever
    emits the argmax chain, so a poisoned round degrades to ~zero
    acceptance instead of corruption.  Healthy slots pass through
    bit-for-bit.  Runs at the head of the verify program."""
    bad = jnp.logical_not(draft_ok)
    pin = jnp.full_like(draft_tokens, vocab - 1)
    tokens = jnp.where(bad[:, None], pin, draft_tokens)
    probs = jnp.where(
        bad[None, :, None],
        jax.nn.one_hot(jnp.transpose(pin), vocab, dtype=jnp.float32),
        draft_probs,
    )
    return tokens, probs


def _residual_sample(p, q, keys):
    """Categorical draw from ``normalize(max(p - q, 0))`` per slot via
    Gumbel-argmax (the mathematically-zero all-zero-residual corner
    falls back to token 0 — it is unreachable when ``p != q`` and
    irrelevant when ``p == q``, where rejection never happens)."""
    res = jnp.maximum(p - q, 0.0)
    logr = jnp.where(res > 0, jnp.log(jnp.maximum(res, 1e-38)), -jnp.inf)
    gumbel = jax.vmap(
        lambda kk: jax.random.gumbel(kk, logr.shape[1:], jnp.float32)
    )(keys)
    return jnp.argmax(logr + gumbel, axis=-1).astype(jnp.int32)


def speculative_verify(ver_logits, draft_tokens, draft_probs, temps,
                       stream_keys, gens, *, top_k: int = 0):
    """Device-side acceptance over one speculative round.

    - ``ver_logits`` ``(k+1, B, V)`` f32 — target logits at positions
      ``j = 0..k`` (position ``j`` scored after consuming column ``j``);
    - ``draft_tokens`` ``(B, k)`` — proposals ``d_1..d_k``; proposal
      ``d_{j+1}`` is judged against position ``j``'s logits;
    - ``draft_probs`` ``(k, B, V)`` — the draft distribution each
      proposal was drawn from (temperature slots only);
    - ``stream_keys`` ``(B, 2)`` uint32 per-slot stream keys, ``gens``
      ``(B,)`` int32 tokens generated so far (the emission index base).

    Returns ``(out_tokens (B, k+1), n_accept (B,))``: slot ``s`` emits
    ``out_tokens[s, :n_accept[s] + 1]`` — its accepted proposals plus
    the correction (first rejection) or bonus (full acceptance) token.
    """
    kp1, b, _ = ver_logits.shape
    k = kp1 - 1
    temps = jnp.asarray(temps, jnp.float32)
    tgt = jnp.argmax(ver_logits, axis=-1).astype(jnp.int32)  # (k+1, B)
    greedy_out = jnp.transpose(tgt)                          # (B, k+1)
    if k == 0:
        bonus = model_lib.sample_tokens(
            ver_logits[0], temps,
            model_lib.fold_in(stream_keys, gens), top_k=top_k,
        )
        return bonus[:, None], jnp.zeros((b,), jnp.int32)

    # greedy: d_{j+1} accepted iff it equals the position-j argmax
    g_accept = jnp.transpose(draft_tokens) == tgt[:k]        # (k, B)

    # temperature: u <= p_j(d) / q_j(d), with the same key chain the
    # emitted token at index j would consume (ACCEPT_TAG sub-stream)
    p = target_probs(ver_logits, temps[None, :], top_k=top_k)  # (k+1,B,V)
    d_cols = jnp.transpose(draft_tokens)                     # (k, B)
    rows = jnp.arange(b)
    p_d = jax.vmap(lambda pj, dj: pj[rows, dj])(p[:k], d_cols)
    q_d = jax.vmap(lambda qj, dj: qj[rows, dj])(draft_probs, d_cols)

    def u_at(j):
        keys = model_lib.fold_in(
            model_lib.fold_in(stream_keys, gens + j),
            jnp.full_like(gens, ACCEPT_TAG),
        )
        return jax.vmap(lambda kk: jax.random.uniform(kk, ()))(keys)

    u = jnp.stack([u_at(j) for j in range(k)])               # (k, B)
    t_accept = u * jnp.maximum(q_d, 1e-38) < p_d
    accept = jnp.where(temps[None, :] > 0, t_accept, g_accept)
    # leading-run length: proposals past the first rejection are dead
    n_accept = jnp.sum(
        jnp.cumprod(accept.astype(jnp.int32), axis=0), axis=0
    ).astype(jnp.int32)                                      # (B,)

    # temperature emissions: accepted drafts verbatim, then at index
    # a the residual sample (a < k) or the bonus sample (a == k) —
    # each emission index j consumes the RAW key fold_in(stream, g+j)
    corrections = []
    for j in range(k + 1):
        keys = model_lib.fold_in(stream_keys, gens + j)
        if j < k:
            corrections.append(_residual_sample(p[j], draft_probs[j], keys))
        else:
            corrections.append(
                model_lib.sample_tokens(
                    ver_logits[k], temps, keys, top_k=top_k
                )
            )
    corr = jnp.stack(corrections)                            # (k+1, B)
    idx = jnp.arange(k + 1)[:, None]                         # (k+1, 1)
    drafts_pad = jnp.concatenate(
        [d_cols, jnp.zeros((1, b), jnp.int32)], axis=0
    )                                                        # (k+1, B)
    temp_out = jnp.where(idx < n_accept[None, :], drafts_pad, corr)
    out = jnp.where(temps[None, :] > 0, temp_out, tgt)
    return jnp.transpose(out), n_accept


# ---------------------------------------------------------------------------
# device bodies (compiled by the engine)
# ---------------------------------------------------------------------------


def draft_body(cfg: GptConfig, params, kv_pages: dict, tokens, lengths,
               page_tables, temps, stream_keys, gens, *, k: int,
               page_size: int, top_k: int = 0):
    """``k+1``-step proposal scan over the draft model.  Step ``j``
    feeds the current token at length ``lengths + j`` (writing its
    draft KV) and samples the next proposal from the draft distribution
    (``DRAFT_TAG`` sub-stream; greedy slots argmax).  The last step
    exists only for its KV write, keeping the draft cache in lockstep
    through full-acceptance rounds.  Idle slots (``lengths == 0``)
    stay masked to the null page for every step.

    Returns ``(draft_tokens (B, k), draft_probs (k, B, V), finite
    (B,), kv_pages)``.
    """
    params = model_lib.dequantize_params(params)
    tree = params["params"]

    def step(carry, j):
        cur, kv = carry
        eff = jnp.where(lengths > 0, lengths + j, 0)
        logits, kv = model_lib._decode_step(
            cfg, tree, kv, cur, eff, page_tables,
            page_size=page_size,
        )
        keys = model_lib.fold_in(
            model_lib.fold_in(stream_keys, gens + j),
            jnp.full_like(gens, DRAFT_TAG),
        )
        nxt = model_lib.sample_tokens(logits, temps, keys, top_k=top_k)
        q = target_probs(logits, temps, top_k=top_k)
        fin = jnp.isfinite(logits).all(axis=-1)
        return (nxt, kv), (nxt, q, fin)

    (_, kv_pages), (toks, probs, fins) = jax.lax.scan(
        step, (tokens, kv_pages), jnp.arange(k + 1)
    )
    draft_tokens = jnp.transpose(toks[:k]) if k else jnp.zeros(
        (tokens.shape[0], 0), jnp.int32
    )
    return draft_tokens, probs[:k], fins.all(axis=0), kv_pages


def verify_body(cfg: GptConfig, params, kv_pages: dict, tokens,
                draft_tokens, lengths, page_tables, temps, draft_probs,
                stream_keys, gens, *, page_size: int, top_k: int = 0):
    """ONE target program scoring ``k+1`` positions: a scan of the
    plain decode step (:func:`~apex_tpu.serve.model._decode_step` —
    same function, same shapes, same paged-attention kernel) over the
    columns ``[t_last, d_1..d_k]`` at successive lengths, writing each
    column's KV at its position exactly as ``k+1`` sequential decode
    iterations would.  Acceptance runs on-device
    (:func:`speculative_verify`); only the small token/count arrays
    cross to the host.

    Returns ``(out_tokens (B, k+1), n_accept (B,), finite (B,),
    kv_pages)`` — ``finite[b]`` is slot ``b``'s non-finite screen over
    ALL ``k+1`` of its logits rows.
    """
    params = model_lib.dequantize_params(params)
    tree = params["params"]
    k = draft_tokens.shape[1]
    cols = jnp.concatenate([tokens[:, None], draft_tokens], axis=1)

    def step(kv, j):
        eff = jnp.where(lengths > 0, lengths + j, 0)
        logits, kv = model_lib._decode_step(
            cfg, tree, kv, jnp.take(cols, j, axis=1), eff, page_tables,
            page_size=page_size,
        )
        return kv, logits

    kv_pages, ver_logits = jax.lax.scan(
        step, kv_pages, jnp.arange(k + 1)
    )
    out_tokens, n_accept = speculative_verify(
        ver_logits, draft_tokens, draft_probs, temps, stream_keys,
        gens, top_k=top_k,
    )
    finite = jnp.isfinite(ver_logits).all(axis=(0, 2))
    return out_tokens, n_accept, finite, kv_pages


def rollback_body(kv_pages: dict, starts, counts, page_tables, *,
                  k: int, page_size: int):
    """Per-slot KV-length truncation: zero the rows of positions
    ``[starts[b], starts[b] + counts[b])`` through slot ``b``'s page
    table (codes to 0; int8 scale planes back to the init value 1.0).
    Masked rows (past a slot's count, or slots with ``counts == 0``)
    land on the null page.  The caller guarantees every touched page
    is private (the scheduler COW-forks shared tail pages BEFORE the
    round that might roll back) — that is what makes the truncation
    safe next to a borrowed prefix-cache run."""
    b = starts.shape[0]
    width = page_tables.shape[1]
    layers = kv_pages["k"].shape[0]

    def zero_step(kv, j):
        pos = starts + j
        live = (j < counts) & (starts > 0)
        page_idx = jnp.clip(pos // page_size, 0, width - 1)
        page_ids = jnp.where(
            live, page_tables[jnp.arange(b), page_idx], 0
        )
        slots = pos % page_size

        def zero_layer(l, kv):
            # page-granular like the decode append (cache.append_rows:
            # a per-row scatter relays the whole pool)
            return {
                name: cache_lib.append_rows(
                    arr, l, page_ids, slots,
                    jnp.full(
                        (b, arr.shape[2], arr.shape[4]),
                        1.0 if name.endswith("_scale") else 0, arr.dtype,
                    ),
                )
                for name, arr in kv.items()
            }

        return jax.lax.fori_loop(0, layers, zero_layer, kv), None

    kv_pages, _ = jax.lax.scan(
        zero_step, dict(kv_pages), jnp.arange(max(k, 1))
    )
    return kv_pages
