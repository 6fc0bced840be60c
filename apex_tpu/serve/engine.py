"""AOT-compiled inference engine — prefill/decode executables + cache.

The engine owns the device-side pieces of the serving stack and the
proofs about them:

- **step programs** — one prefill executable per bucket shape and ONE
  decode executable for the full slot array, compiled ahead of time
  (``jit(...).lower(...).compile()``) at :meth:`InferenceEngine.build`.
  Steady-state serving calls compiled executables only: a retrace is
  impossible by construction, and :attr:`compile_counts` +
  a :class:`~apex_tpu.analysis.RetraceSentinel` per program pin it
  observably (``tests/test_serve.py``).
- **verification** — with ``verify=True`` (the default), the
  :mod:`apex_tpu.analysis` passes run over every step program at
  build (``lint_hlo`` on the one AOT-compiled module + ``lint_jaxpr``
  on a re-trace — the split-entry API exists exactly so the lint does
  not pay a second compile): transfer-free (no host round-trip inside
  a latency-critical step), donation-aliased (the pool's output is
  the donated buffer — a dropped donation would double cache memory
  per step), pool-copy-free (``memory-pool-copy``: nothing shaped like
  the pool or a layer of it is materialized between entry and exit —
  docs/serving.md "The KV pool"), plus the standard f64 screens.  Any
  ERROR finding fails the build;
  reports stay on :attr:`reports` and publish to the observability
  board.  ``engine.lint()`` / ``tools/graph_lint.py --target serve``
  re-prove the same through the full :func:`analysis.check` path.
- **cache + wires** — the paged KV pool (:mod:`apex_tpu.serve.cache`),
  optionally on the blockwise int8 KV wire, and optionally int8-packed
  weights (:func:`apex_tpu.serve.model.quantize_params`) dequantized
  inside the compiled step.
- **weights** — :attr:`InferenceEngine.params` is the tree the caller
  installed; the programs take its *step tree*
  (:func:`apex_tpu.serve.model.step_params`: the block's matmul
  weights and biases cast to the compute dtype once per installed tree,
  not at the head of every program — docs/serving.md "Weights").
- **failure surface** — every step program computes an in-step
  non-finite screen over its logits (:attr:`last_prefill_finite` /
  :attr:`last_decode_finite` — the scheduler's poisoned-request
  quarantine evidence, no logits readback), chaos hooks at the
  ``serve.prefill`` / ``serve.decode`` sites make faults injectable
  from one ``APEX_TPU_CHAOS`` spec, and :meth:`rebuild` is the
  supervised recovery: re-run the AOT build (re-verified) while the
  cache arrays and pool are retained so surviving requests resume
  from their pages.  See docs/serving.md "Failure semantics".

Bucketed padding: a prompt compiles against the smallest bucket that
holds it (buckets are page multiples, powers-of-two by default), so the
number of distinct compiled shapes is ``len(prefill_buckets) + 1`` for
the life of the process.

The engine is deliberately scheduler-agnostic: it moves tokens and
pages, :class:`apex_tpu.serve.scheduler.ContinuousBatchingScheduler`
owns admission/shedding/SLOs, and both feed the same
:class:`~apex_tpu.observability.metrics.MetricRegistry`.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math
import time
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.models.gpt import GptConfig
from apex_tpu.observability.metrics import board
from apex_tpu.observability.spans import TRACK_ENGINE, host_recorder
from apex_tpu.resilience import chaos
from apex_tpu.serve import cache as cache_lib
from apex_tpu.serve import model as model_lib
from apex_tpu.serve import spec as spec_lib

__all__ = ["ServeConfig", "InferenceEngine"]


def _default_buckets(page_size: int, max_len: int) -> Tuple[int, ...]:
    """Power-of-two page-multiple buckets covering [page, max_len]."""
    buckets = []
    b = page_size
    while b < max_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_len)
    return tuple(buckets)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine shape/wire knobs (model shape lives in ``GptConfig``)."""

    page_size: int = 16
    #: pool size INCLUDING the reserved null page
    num_pages: int = 128
    #: decode slot count — the continuous batch's capacity
    max_batch: int = 4
    #: page-table width: the longest context is ``max_pages_per_seq *
    #: page_size`` tokens
    max_pages_per_seq: int = 8
    #: prefill bucket lengths (page multiples); () = powers of two up
    #: to the max context
    prefill_buckets: Tuple[int, ...] = ()
    #: "f32" keeps KV in the cache dtype; "int8" stores blockwise codes
    kv_wire: str = "f32"
    #: "f32" keeps weights dense; "int8" packs large leaves on the
    #: comm codec and dequantizes inside the compiled step
    weight_wire: str = "f32"
    #: static top-k cutoff for the fused in-step sampler (0 = full
    #: vocab); per-request temperature rides the call (temp<=0 stays
    #: greedy/argmax, bit-identical to the pre-sampling engine)
    top_k: int = 0
    #: decode iterations ONE decode program runs (a stack whose layers
    #: differ in kind only): each slot emits up to this many tokens a call,
    #: so the host's launch, transfers and bookkeeping are paid once a
    #: block — an offline batch deployment's knob; 1 = a token a call.
    #: Admission happens between blocks.
    decode_block: int = 1
    #: PRNG seed for the fused sampler (one key per engine call,
    #: folded with the call index — deterministic replay)
    sample_seed: int = 0
    #: run analysis.check over every step program at build (ERROR
    #: findings raise)
    verify: bool = True
    #: static peak-HBM budget in bytes for each step program (weights
    #: + KV page pool + activations + scratch, from the compiled
    #: module's live ranges — apex_tpu.analysis.memory).  None skips
    #: the gate; with ``verify=True`` an over-budget program fails the
    #: BUILD, so a pool that never fit can't reach the first request.
    hbm_budget_bytes: Optional[int] = None

    def __post_init__(self):
        if self.kv_wire not in ("f32", "int8"):
            raise ValueError(f"kv_wire must be f32|int8, got {self.kv_wire!r}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if self.decode_block < 1:
            raise ValueError(
                f"decode_block must be >= 1, got {self.decode_block}")
        if self.weight_wire not in ("f32", "int8"):
            raise ValueError(
                f"weight_wire must be f32|int8, got {self.weight_wire!r}"
            )
        usable = self.num_pages - 1
        if usable < self.max_pages_per_seq:
            raise ValueError(
                f"pool of {usable} usable pages cannot hold even one "
                f"max-length sequence ({self.max_pages_per_seq} pages)"
            )

    @property
    def max_context(self) -> int:
        return self.max_pages_per_seq * self.page_size

    def buckets(self) -> Tuple[int, ...]:
        if self.prefill_buckets:
            for b in self.prefill_buckets:
                if b % self.page_size or b > self.max_context:
                    raise ValueError(
                        f"bucket {b} must be a page multiple within "
                        f"max context {self.max_context}"
                    )
            return tuple(sorted(self.prefill_buckets))
        return _default_buckets(self.page_size, self.max_context)


class _HostArgs:
    """The host arguments of one step program, as ONE int32 vector.

    A host-to-device transfer costs the serving loop about 0.2 ms on
    the chip's host however small it is (PERF.md section 5: six small
    arrays in one ``device_put`` 1.13 ms, the same words as one vector
    0.25 ms, as one numpy argument of the compiled call 0.07 ms), so a
    call's tokens, lengths, tables, temperatures and scalars ride one
    vector that the compiled call takes as its only host argument:
    :meth:`pack` lays the fields end to end on the host (every field
    is 32 bits wide; floats and unsigned keep their bits),
    :meth:`unpack` slices and bitcasts them back at the head of the
    compiled program.
    """

    def __init__(self, *fields):
        #: ``((shape, dtype), ...)`` in argument order
        self.fields = tuple((tuple(s), np.dtype(d)) for s, d in fields)
        if any(d.itemsize != 4 for _, d in self.fields):
            raise ValueError("host argument fields must be 32 bits wide")
        sizes = [math.prod(s) for s, _ in self.fields]
        ends = list(itertools.accumulate(sizes))
        #: each field's ``(start, end)`` in the vector
        self.spans = tuple((hi - n, hi) for n, hi in zip(sizes, ends))
        self.size = ends[-1]

    def pack(self, *values) -> np.ndarray:
        out = np.empty((self.size,), np.int32)
        for (_, dtype), (lo, hi), value in zip(
            self.fields, self.spans, values, strict=True
        ):
            out[lo:hi] = (
                np.ascontiguousarray(value, dtype).reshape(-1).view(np.int32)
            )
        return out

    def unpack(self, packed):
        """Traced: the fields back out of the vector, in order."""
        return tuple(
            jax.lax.bitcast_convert_type(
                packed[lo:hi].reshape(shape), dtype
            )
            for (shape, dtype), (lo, hi) in zip(self.fields, self.spans)
        )

    def example(self):
        return jnp.zeros((self.size,), jnp.int32)


# ---------------------------------------------------------------------------
# the program table: what each kind of step program IS, written once
# ---------------------------------------------------------------------------


_INT, _FLOAT = ((), np.int32), ((), np.float32)


def _per_slot(s: ServeConfig, dtype=np.int32):
    return ((s.max_batch,), dtype)


def _tables(s: ServeConfig):
    return ((s.max_batch, s.max_pages_per_seq), np.int32)


def _prompt(s: ServeConfig, bucket: int):
    # tokens, page ids
    return (((bucket, 1), np.int32), ((bucket // s.page_size,), np.int32))


# ``fields(engine, bucket)``: a kind's host arguments, in order


def _prefill_fields(eng, bucket):
    # tokens, page ids, length, call index, temperature — and, for a model
    # with recurrent layers, the decode slot its state is left in
    return _prompt(eng.serve, bucket) + (_INT, _INT, _FLOAT) + (
        (_INT,) if eng.stateful else ()
    )


def _chunk_fields(eng, bucket):
    # tokens, chunk page ids, page table row, length, offset, call
    # index, temperature
    s = eng.serve
    row = ((s.max_pages_per_seq,), np.int32)
    return _prompt(s, bucket) + (row, _INT, _INT, _INT, _FLOAT)


def _decode_fields(eng, bucket):
    # tokens, lengths, page tables, temperatures, and the two integers
    # each slot's sampling key is folded from — and, for a decode block,
    # the iterations each slot runs
    s = eng.serve
    return (
        _per_slot(s), _per_slot(s), _tables(s), _per_slot(s, np.float32),
        _per_slot(s, np.uint32), _per_slot(s),
    ) + ((_per_slot(s),) if s.decode_block > 1 else ())


def _verify_fields(eng, bucket):
    # decode's, with the host's verdict on the round's draft (0 under a
    # serve.draft fault) before the key integers
    decode = _decode_fields(eng, bucket)
    return decode[:4] + (_INT,) + decode[4:]


def _rollback_fields(eng, bucket):
    # starts, counts, page tables
    s = eng.serve
    return (_per_slot(s), _per_slot(s), _tables(s))


def _fork_fields(eng, bucket):
    # source page, destination page
    return (_INT, _INT)


# The traced bodies, ``body(engine, host, *device_args, packed)``:
# ``host`` is the kind's layout, ``packed`` the call's one host argument.


def _prefill_step(eng, host, params, kv_pages, base_key, packed, *,
                  draft: bool = False):
    """The target's and the draft's prefill: one body, two model
    configs.  The call's key is folded HERE from its index: the host
    folds nothing."""
    tokens, page_ids, length, call, temp, *slot = host.unpack(packed)
    return model_lib.prefill_body(
        eng._draft_cfg if draft else eng.cfg, params, kv_pages, tokens,
        length, page_ids, temp, model_lib.fold_in(base_key, call),
        page_size=eng.serve.page_size, top_k=eng.serve.top_k,
        **({"slot": slot[0]} if slot else {}),
    )


def _chunk_step(eng, host, params, kv_pages, base_key, packed):
    (tokens, chunk_page_ids, page_table, length, offset, call,
     temp) = host.unpack(packed)
    return model_lib.chunk_prefill_body(
        eng.cfg, params, kv_pages, tokens, length, offset,
        chunk_page_ids, page_table, temp,
        model_lib.fold_in(base_key, call),
        page_size=eng.serve.page_size, top_k=eng.serve.top_k,
    )


def _decode_step(eng, host, params, kv_pages, base_key, packed):
    tokens, lengths, page_tables, temps, streams, gens, *steps = (
        host.unpack(packed)
    )
    block = {}
    if steps:
        # a decode block: iteration j's keys are emission gens + j's
        k = eng.serve.decode_block
        gens = gens[None, :] + jnp.arange(k, dtype=gens.dtype)[:, None]
        block = {"steps": steps[0], "block": k}
    # per-slot keys fold_in(fold_in(base, streams[b]), gens[b]), folded
    # HERE from the two integer vectors the host packs
    return model_lib.decode_body(
        eng.cfg, params, kv_pages, tokens, lengths, page_tables,
        temps, model_lib.slot_keys(base_key, streams, gens),
        page_size=eng.serve.page_size, top_k=eng.serve.top_k, **block,
    )


def _fork_step(eng, host, kv_pages, packed):
    # copy-on-write fork: duplicate one page's rows (codes AND scale
    # planes under the int8 wire) across every layer
    src, dst = host.unpack(packed)
    return {
        name: arr.at[:, dst].set(arr[:, src])
        for name, arr in kv_pages.items()
    }


def _draft_step(eng, host, params, kv_pages, base_key, packed):
    tokens, lengths, page_tables, temps, streams, gens = (
        host.unpack(packed)
    )
    return spec_lib.draft_body(
        eng._draft_cfg, params, kv_pages, tokens, lengths, page_tables,
        temps, model_lib.stream_keys(base_key, streams), gens,
        k=eng.spec.k, page_size=eng.serve.page_size,
        top_k=eng.serve.top_k,
    )


def _verify_step(eng, host, params, kv_pages, base_key, draft_tokens,
                 draft_probs, draft_finite, packed):
    (tokens, lengths, page_tables, temps, draft_ok, streams,
     gens) = host.unpack(packed)
    if eng.spec.k:
        # draft_finite is the draft program's own screen (still on the
        # device), draft_ok the host's verdict on the whole round (0
        # under a serve.draft fault)
        draft_tokens, draft_probs = spec_lib.pin_failed_drafts(
            draft_tokens, draft_probs,
            draft_finite & (draft_ok != 0), eng.cfg.vocab_size,
        )
    return spec_lib.verify_body(
        eng.cfg, params, kv_pages, tokens, draft_tokens,
        lengths, page_tables, temps, draft_probs,
        model_lib.stream_keys(base_key, streams), gens,
        page_size=eng.serve.page_size, top_k=eng.serve.top_k,
    )


def _rollback_step(eng, host, kv_pages, packed):
    starts, counts, page_tables = host.unpack(packed)
    # the stale span after a round is [new ctx, old ctx + k]: at most
    # k + 1 rows when nothing was accepted
    return spec_lib.rollback_body(
        kv_pages, starts, counts, page_tables,
        k=eng.spec.k + 1, page_size=eng.serve.page_size,
    )


def _target_args(eng):
    return (eng.step_params, eng.cache, eng._rng_base)


def _draft_args(eng):
    return (eng.draft_step_params, eng.draft_cache, eng._rng_base)


def _verify_args(eng):
    s, k = eng.serve, eng.spec.k
    return _target_args(eng) + (
        jnp.zeros((s.max_batch, k), jnp.int32),
        jnp.zeros((k, s.max_batch, eng.cfg.vocab_size), jnp.float32),
        jnp.ones((s.max_batch,), jnp.bool_),
    )


@dataclasses.dataclass(frozen=True)
class _Program:
    """One KIND of step program.  ``kind`` is its name on the board, in
    :attr:`InferenceEngine.compile_counts` and on its retrace sentinel
    (a per-bucket kind's programs are ``<kind>_<bucket>``); the jitted
    function is ``serve_<that name>`` — the module name a device trace
    shows (``jit_serve_decode``)."""

    kind: str
    #: ``(engine, bucket) -> _HostArgs fields``: the host arguments
    fields: Callable
    #: the traced ``body(engine, host, *device_args, packed)``
    body: Callable
    #: ``engine -> device arguments`` (example = the live ones)
    device_args: Callable
    #: which device argument is donated: the cache set (the K/V pool;
    #: latent pages and the per-slot recurrent slab of a hybrid stack)
    donate: int = 1
    per_bucket: bool = False
    #: exists only on an engine with a SpecConfig
    spec: bool = False
    #: warmed by ``build(chunked=True)`` only
    chunked: bool = False
    #: ``rebuild()`` compiles a replacement (the incumbent serves until
    #: it is ready) ...
    rebuilt: bool = False
    #: ... and ``rebuild(full=True)`` drops these, to recompile on
    #: next use
    dropped: bool = False


def _program_name(kind: str, bucket: Optional[int] = None) -> str:
    return kind if bucket is None else f"{kind}_{bucket}"


#: every program the engine can compile, in ``build()``'s order (the
#: per-bucket kinds bucket by bucket, then the rest)
_PROGRAMS: Dict[str, _Program] = {p.kind: p for p in (
    _Program("prefill", _prefill_fields, _prefill_step, _target_args,
             per_bucket=True, dropped=True),
    _Program("chunk_prefill", _chunk_fields, _chunk_step, _target_args,
             per_bucket=True, chunked=True, dropped=True),
    _Program("draft_prefill", _prefill_fields,
             functools.partial(_prefill_step, draft=True), _draft_args,
             per_bucket=True, spec=True, dropped=True),
    _Program("fork_page", _fork_fields, _fork_step,
             lambda eng: (eng.cache,), donate=0, chunked=True),
    _Program("decode", _decode_fields, _decode_step, _target_args,
             rebuilt=True),
    _Program("draft_decode", _decode_fields, _draft_step, _draft_args,
             spec=True, rebuilt=True),
    _Program("verify", _verify_fields, _verify_step, _verify_args,
             spec=True, rebuilt=True),
    _Program("rollback", _rollback_fields, _rollback_step,
             lambda eng: (eng.cache,), donate=0, spec=True),
    _Program("draft_rollback", _rollback_fields, _rollback_step,
             lambda eng: (eng.draft_cache,), donate=0, spec=True),
)}


class InferenceEngine:
    """AOT prefill/decode over the paged cache for a GPT param tree.

    >>> eng = InferenceEngine(cfg, params, ServeConfig(max_batch=4))
    >>> eng.build()                      # compile + verify (analysis)
    >>> logits, tok = eng.prefill(prompt_ids, page_ids)
    >>> toks = eng.decode(tokens, lengths, page_tables)

    The engine holds the cache arrays and rebinds them after every
    donated call; callers pass page ids / tables / lengths (the
    scheduler's bookkeeping) and get tokens back.
    """

    def __init__(
        self,
        cfg: GptConfig,
        params,
        serve: Optional[ServeConfig] = None,
        *,
        spec: Optional[spec_lib.SpecConfig] = None,
        registry=None,
    ):
        self.cfg = model_lib.validate_config(cfg)
        self.serve = serve or ServeConfig()
        #: the model's layer kinds (None: the homogeneous GPT stack), and
        #: what they ask of the engine: a decode slot per prefill
        #: (recurrent state), the MoE counts behind each token readback
        self.kinds = model_lib.layer_kinds(cfg)
        #: ``{kinds}`` on the engine spans of a model that has them
        self._span_kinds = {} if self.kinds is None else {
            "kinds": ",".join(sorted({k for pair in self.kinds for k in pair}))
        }
        self.stateful = model_lib.is_stateful(cfg)
        self.routed = model_lib.is_routed(cfg)
        #: what a hybrid stack's cache set holds, as its mixer kinds
        #: declared it (``cache_lib.CacheKind``; ``()``: the GPT K/V pool)
        self.cache_kinds = () if self.kinds is None else (
            cache_lib.hybrid_cache_kinds(cfg, self.serve.page_size))
        model_lib.validate_features(cfg, spec=spec is not None)
        if self.kinds is not None and (
            self.serve.kv_wire != "f32" or self.serve.weight_wire != "f32"
        ):
            raise ValueError(
                "the int8 KV and weight wires are the GPT stack's: a "
                "hybrid stack's cache set and weights stay in their own "
                "dtypes"
            )
        if self.serve.decode_block > 1 and self.kinds is None:
            raise ValueError(
                "decode_block > 1 is a hybrid stack's: the GPT stack's "
                "decode program emits one token a call"
            )
        #: a routed model's last step's counts ``(pairs routed to held
        #: experts, distinct held experts touched)``, summed over layers
        self.last_moe_counts: Optional[np.ndarray] = None
        if self.serve.max_context > cfg.max_seq_len:
            raise ValueError(
                f"max context {self.serve.max_context} exceeds the "
                f"model's max_seq_len {cfg.max_seq_len}"
            )
        if self.kinds is None and cfg.hidden_size % cfg.num_heads:
            # the GPT stack's head width is hidden_size / num_heads (a
            # hybrid stack states its own head_dim)
            raise ValueError("num_heads must divide hidden_size")
        self.registry = registry
        #: how many step trees this engine has derived
        #: (``serve/weights/casts``): one per installed weight tree
        self.weight_casts = 0
        self._draft_params = None
        #: the trees the step programs take (docs/serving.md
        #: "Weights"): :attr:`params` / :attr:`draft_params` with the
        #: block's matmul weights and biases cast to the compute dtype
        #: once, at install.  The draft's is the target's on a self-draft
        #: engine.
        self.step_params = self.draft_step_params = None
        self.params = self._on_wire(params)
        self.pool = cache_lib.PagePool(
            self.serve.num_pages, self.serve.page_size
        )
        self.cache = self._new_cache(cfg)
        self._layouts: Dict[Tuple[str, Optional[int]], _HostArgs] = {}
        #: the compiled executables, ``(kind, bucket or None)`` ->
        #: executable: every program of :data:`_PROGRAMS` built so far
        self._programs: Dict[Tuple[str, Optional[int]], object] = {}
        #: speculative decoding (docs/serving.md "Speculative
        #: decoding"): None = plain serving; a SpecConfig adds the
        #: draft model's params + KV pool and the draft/verify/rollback
        #: step programs, all compiled and verified like every other
        #: program
        self.spec = spec
        self._draft_cfg: Optional[GptConfig] = None
        self.draft_cache = None
        #: speculative round counter — the ``serve.draft`` chaos index
        self.spec_rounds = 0
        self.draft_prefill_calls = 0
        if spec is not None:
            dcfg = model_lib.validate_config(spec.draft_cfg or cfg)
            if dcfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {dcfg.vocab_size} != target vocab "
                    f"{cfg.vocab_size} (proposals must share the "
                    f"token space)"
                )
            if self.serve.max_context > dcfg.max_seq_len:
                raise ValueError(
                    f"max context {self.serve.max_context} exceeds the "
                    f"draft model's max_seq_len {dcfg.max_seq_len}"
                )
            if dcfg.hidden_size % dcfg.num_heads:
                raise ValueError("draft num_heads must divide hidden_size")
            self._draft_cfg = dcfg
            # None = self-draft: share the (possibly wire-packed) weights
            self.update_draft_params(spec.draft_params)
            # the draft KV pool mirrors the target's page geometry so
            # ONE PagePool's page ids index both (draft pages ride the
            # "draft" namespace; only the per-page row shapes differ)
            self.draft_cache = self._new_cache(dcfg)
        # the fused sampler's base key: on the device once, an argument
        # of every step program, which folds the call's integers (call
        # index; stream seed and emission index) into it IN-PROGRAM
        self._rng_base = jax.device_put(
            jax.random.PRNGKey(self.serve.sample_seed)
        )
        #: optional :class:`~apex_tpu.observability.spans.SpanRecorder`
        #: (the scheduler attaches its recorder here automatically).
        #: Every prefill/decode call records an ``engine/stage`` phase
        #: (the call's host arguments packed into one numpy vector,
        #: retrace sentinel) and
        #: an ``engine/prefill`` / ``engine/decode`` phase (compiled
        #: call → first host read) — here, or with none attached in
        #: the process ring (:func:`~apex_tpu.observability.spans.
        #: process_recorder`)
        self.spans = None
        #: monotonically increasing call counters — the correlation
        #: ids linking a request's span chain to the engine batch
        #: iterations it rode (always counted, spans or not)
        self.decode_iters = 0
        self.prefill_calls = 0
        #: per-program AOT compile counter — the observable
        #: retrace-freedom pin (steady state never increments it; a
        #: supervised :meth:`rebuild` does, honestly)
        self.compile_counts: Dict[str, int] = {}
        #: supervised recoveries (:meth:`rebuild` calls) — 0 in steady
        #: state; every increment is a fault the scheduler survived
        self.rebuilds = 0
        #: the in-step non-finite screens of the LAST prefill/decode
        #: call — ``last_prefill_finite`` a bool, ``last_decode_finite``
        #: an ``(max_batch,)`` bool array (None before the first call).
        #: Computed INSIDE the compiled steps (no logits readback); the
        #: scheduler's poisoned-request quarantine reads them.
        self.last_prefill_finite: bool = True
        self.last_decode_finite: Optional[np.ndarray] = None
        self.reports: Dict[str, object] = {}
        self._sentinels: Dict[str, object] = {}
        self._publish_build_gauges()

    def _new_cache(self, cfg: GptConfig) -> dict:
        """A zeroed KV pool for ``cfg`` at this engine's page geometry
        (the draft's mirrors the target's, so ONE PagePool's page ids
        index both)."""
        s = self.serve
        if model_lib.layer_kinds(cfg) is not None:
            return cache_lib.init_hybrid_cache(
                cfg, s.num_pages, s.page_size, s.max_batch
            )
        return cache_lib.init_kv_pages(
            cfg.num_layers, s.num_pages, cfg.num_heads, s.page_size,
            cfg.hidden_size // cfg.num_heads,
            dtype=cfg.dtype, kv_wire=s.kv_wire,
        )

    # -- weights ----------------------------------------------------------
    def _on_wire(self, tree):
        if self.serve.weight_wire == "int8":
            return model_lib.quantize_params(tree)
        return tree

    def _install(self, cfg: GptConfig, tree):
        """The step tree of a weight tree being installed: what every
        program of :data:`_PROGRAMS` is traced, compiled and called on
        (:func:`apex_tpu.serve.model.step_params`, docs/serving.md
        "Weights").  Same avals whatever the values, so a swap keeps
        every AOT executable valid."""
        self.weight_casts += 1
        return model_lib.step_params(cfg, tree)

    @property
    def params(self):
        """The installed weight tree: the object the caller handed in
        (wire-packed at construction under ``weight_wire="int8"``), so a
        rollback can hand the incumbent back verbatim.  Assigning
        installs a tree: it is stored as it is and
        :attr:`step_params`, the tree the step programs take, is
        derived from it once."""
        return self._params

    @params.setter
    def params(self, tree) -> None:
        self._params = tree
        self.step_params = self._install(self.cfg, tree)
        self._publish_weight_gauges()

    @property
    def draft_params(self):
        """The draft model's installed weight tree (the target's own on
        a self-draft engine); :meth:`update_draft_params` installs one,
        :attr:`draft_step_params` is what the draft programs take."""
        return self._draft_params

    def _publish_weight_gauges(self) -> None:
        installed = {id(leaf) for leaf in jax.tree_util.tree_leaves(
            (self._params, self._draft_params)
        )}
        # id-keyed: a self-draft's aliased step tree counts once
        derived = {
            id(leaf): leaf.nbytes for leaf in jax.tree_util.tree_leaves(
                (self.step_params, self.draft_step_params)
            ) if id(leaf) not in installed
        }
        board.set("serve/weights/step_bytes", sum(derived.values()))
        board.set("serve/weights/casts", self.weight_casts)

    # -- build ------------------------------------------------------------
    def _publish_build_gauges(self) -> None:
        s = self.serve
        board.set("serve/page_size", s.page_size)
        board.set("serve/num_pages", s.num_pages - 1)
        board.set("serve/max_batch", s.max_batch)
        board.set("serve/max_context", s.max_context)
        board.set("serve/kv_wire", s.kv_wire)
        board.set("serve/weight_wire", s.weight_wire)
        if self.spec is not None:
            board.set("serve/spec_k", self.spec.k)
            board.set("serve/spec_mode", self.spec.mode)

    def _host_args(self, kind: str,
                   bucket: Optional[int] = None) -> _HostArgs:
        """The :class:`_HostArgs` layout of a program kind — shared by
        the builder (``unpack`` at the program's head) and the serving
        call (``pack``), so the two cannot drift."""
        key = (kind, bucket)
        if key not in self._layouts:
            self._layouts[key] = _HostArgs(
                *_PROGRAMS[kind].fields(self, bucket)
            )
        return self._layouts[key]

    def _kinds(self):
        """The program kinds this engine has, in :data:`_PROGRAMS`'
        order."""
        return [
            prog for prog in _PROGRAMS.values()
            if self.spec is not None or not prog.spec
        ]

    def _trace_args(self, kind: str, bucket: Optional[int] = None):
        """``(fn, example args)`` of one program: the kind's body bound
        to this engine and its layout, named as the device trace will
        show it."""
        prog = _PROGRAMS[kind]
        host = self._host_args(kind, bucket)
        fn = functools.partial(prog.body, self, host)
        fn.__name__ = f"serve_{_program_name(kind, bucket)}"
        return fn, (*prog.device_args(self), host.example())

    def _pool_intent(self, cache) -> dict:
        """The ``memory-pool-copy`` intent for a program that takes
        ``cache``: the pool stays one buffer in one layout from entry
        to exit (docs/serving.md "The KV pool").  An ERROR where it
        costs, on the TPU at the bf16/f32 wire; a WARNING for the int8
        wire (its scale planes are whole tiles since PR 36 and
        ``serve_decode`` / ``serve_prefill_1024`` compile clean, but the
        other programs were not compiled under it: ROADMAP S15) and for
        the CPU compiler's own copy insertion."""
        from apex_tpu import analysis

        strict = (
            jax.default_backend() == "tpu" and self.serve.kv_wire != "int8"
        )
        # a hybrid stack's page kinds and recurrent slabs are under the
        # same rule; what a kind declares ``in_place=False`` is not (the
        # convolution tails: a step rewrites a layer of them whole, 9 MB
        # at the benchmark's shapes)
        exempt = {k.name for k in self.cache_kinds if not k.in_place}
        return {
            "shapes": [
                leaf.shape for name, leaf in cache.items()
                if name not in exempt
            ],
            "severity": None if strict else analysis.WARNING,
        }

    def _compile(self, name: str, fn, args, *, donate: int = 1):
        from apex_tpu import analysis

        compiled = (
            jax.jit(fn, donate_argnums=(donate,)).lower(*args).compile()
        )
        if self.serve.verify:
            # lint the executable we just paid for (lint_hlo/lint_jaxpr
            # instead of analysis.check, which would trace+compile the
            # identical program a second time): HLO-level transfer +
            # donation-aliasing + static peak-HBM budget over the
            # compiled text (the KV page pool is a donated argument
            # with a static shape, so the pool is budgeted exactly),
            # jaxpr-level transfer/promotion over a cheap re-trace
            hlo_text = compiled.as_text()
            report = analysis.lint_hlo(
                hlo_text,
                donated=len(jax.tree_util.tree_leaves(args[donate])),
                hbm_budget=self.serve.hbm_budget_bytes,
                expect_pool=self._pool_intent(args[donate]),
                name=f"serve/{name}",
            )
            est = analysis.memory.estimate_peak(hlo_text)
            analysis.memory.publish_peak(est, prefix=f"serve/hbm/{name}")
            board.set(
                f"serve/hbm/{name}/temp_bytes",
                int(compiled.memory_analysis().temp_size_in_bytes),
            )
            board.set("serve/peak_hbm_bytes", max(
                int(board.get("serve/peak_hbm_bytes") or 0),
                est["peak_bytes"],
            ))
            report.extend(
                analysis.lint_jaxpr(
                    jax.make_jaxpr(fn)(*args), name=f"serve/{name}"
                ).findings
            )
            analysis.publish_report(report)
            self.reports[name] = report
            errors = report.errors()
            if errors:
                raise RuntimeError(
                    f"serve step {name} failed graph lint with "
                    f"{len(errors)} ERROR finding(s):\n{report.render()}"
                )
        self.compile_counts[name] = self.compile_counts.get(name, 0) + 1
        self._sentinels[name] = analysis.RetraceSentinel(name=name)
        return compiled

    def build(self, buckets: Optional[Tuple[int, ...]] = None, *,
              chunked: bool = False):
        """Compile (and verify) the decode step and every prefill
        bucket eagerly.  Lazy compilation still happens on first use of
        a bucket that was skipped here.  ``chunked=True`` additionally
        warms every chunk-prefill bucket and the COW fork program —
        a prefix-cache/chunked-prefill deployment should pay those
        compiles at build, not inside the first cache hit's TTFT."""
        warmed = [
            prog for prog in self._kinds() if chunked or not prog.chunked
        ]
        for b in buckets if buckets is not None else self.serve.buckets():
            for prog in warmed:
                if prog.per_bucket:
                    self._program(prog.kind, b)
        for prog in warmed:
            if not prog.per_bucket:
                self._program(prog.kind)
        return self

    def rebuild(self, *, full: bool = False):
        """Supervised recovery (docs/serving.md "Failure semantics"):
        re-run the AOT build — including the build-time ``verify``
        lint, so the replacement program is re-PROVEN, not assumed —
        and swap it in atomically, while the KV cache arrays and the
        page pool are retained, so surviving requests resume decoding
        from their existing pages with the generated prefix intact.

        The incumbent decode program stays SERVING until the
        replacement is ready: a transient fault does not corrupt a
        compiled executable, so recovery must not pause the batch for
        a recompile (if the incumbent is genuinely wedged it faults
        again and the scheduler's ``rebuild_limit`` bounds the loop —
        the scheduler defers this call to an idle point and escalates
        to a synchronous rebuild on a repeat fault).  By default only
        the decode program is rebuilt; ``full=True`` additionally
        drops every prefill bucket, which then recompiles lazily on
        next use.  The swap is one atomic attribute write.
        """
        self.rebuilds += 1
        if full:
            for key in [
                k for k in self._programs if _PROGRAMS[k[0]].dropped
            ]:
                del self._programs[key]
                del self._sentinels[_program_name(*key)]
        for prog in self._kinds():
            if prog.rebuilt:
                self._programs[prog.kind, None] = self._compile(
                    prog.kind, *self._trace_args(prog.kind),
                    donate=prog.donate,
                )
        board.set("serve/engine_rebuilds", self.rebuilds)
        return self

    def _program(self, kind: str, bucket: Optional[int] = None):
        """The compiled executable of one program, compiled (and
        verified) on first use."""
        key = (kind, bucket)
        if key not in self._programs:
            self._programs[key] = self._compile(
                _program_name(kind, bucket),
                *self._trace_args(kind, bucket),
                donate=_PROGRAMS[kind].donate,
            )
        return self._programs[key]

    @property
    def retraces(self) -> int:
        return sum(s.retraces for s in self._sentinels.values())

    def lint(self, bucket: Optional[int] = None):
        """One merged :class:`apex_tpu.analysis.Report` over the
        prefill (smallest bucket by default) and decode step programs —
        the ``tools/graph_lint.py --target serve`` surface.  Unlike the
        build-time ``verify``, this never raises: findings come back
        for rendering."""
        from apex_tpu import analysis

        bucket = bucket or self.serve.buckets()[0]
        fn, args = self._trace_args("prefill", bucket)
        report = analysis.check(
            jax.jit(fn, donate_argnums=(1,)), *args,
            donate_argnums=(1,),
            hbm_budget=self.serve.hbm_budget_bytes,
            expect_pool=self._pool_intent(self.cache),
            name=f"serve/prefill_{bucket}",
        )
        fn, args = self._trace_args("decode")
        dec = analysis.check(
            jax.jit(fn, donate_argnums=(1,)), *args,
            donate_argnums=(1,),
            hbm_budget=self.serve.hbm_budget_bytes,
            expect_pool=self._pool_intent(self.cache),
            name="serve/decode",
        )
        analysis.attach_shard_sections(report, [
            (f"serve/prefill_{bucket}", report.hlo_text),
            ("serve/decode", dec.hlo_text),
        ])
        report.merge(dec)
        report.target = "serve"
        return report

    # -- serving calls ----------------------------------------------------
    def bucket_for(self, prompt_len: int) -> int:
        for b in self.serve.buckets():
            if prompt_len <= b:
                return b
        raise ValueError(
            f"prompt of {prompt_len} tokens exceeds the max context "
            f"{self.serve.max_context}"
        )

    @staticmethod
    def _chaos_gate(site: str, call_idx: int):
        """Serving chaos hook (one ``APEX_TPU_CHAOS`` spec drives train
        AND serve drills): ``raise`` mode raises :class:`~apex_tpu.
        resilience.chaos.InjectedFault` standing in for a wedged or
        crashed step, ``stall`` sleeps (a hung device call — the
        scheduler's per-request decode timeouts see it), ``nan``/
        ``inf`` return the fault so the caller poisons its non-finite
        verdict (the quarantine drill).  ``call_idx`` is the 0-based
        prefill-call / decode-iteration index."""
        fault = chaos.active(site, call_idx)
        if fault is None:
            return None
        if fault.mode == "stall":
            time.sleep(fault.stall_seconds)
            return None
        if fault.mode in ("nan", "inf"):
            return fault
        raise chaos.InjectedFault(site, call_idx, fault.mode)

    def _phase(self, name: str, **args):
        """A host phase on the attached recorder, else on the process
        ring (docs/serving.md "Host phases")."""
        return host_recorder(self.spans).phase(
            name, track=TRACK_ENGINE, **args
        )

    def _temps(self, temps):
        if temps is None:
            return np.zeros((self.serve.max_batch,), np.float32)
        return np.asarray(temps, np.float32)

    def _read_tokens(self, next_tokens) -> np.ndarray:
        """The step's ONE token readback.  A routed model's carries its MoE
        counts behind the tokens (:func:`apex_tpu.serve.model.
        _with_counts`): they land on :attr:`last_moe_counts`."""
        out = np.asarray(next_tokens)
        if self.routed:
            self.last_moe_counts = out[-2:]
            out = out[:-2]
        return out

    def prefill(self, prompt_ids, page_ids, *, temperature: float = 0.0,
                slot: Optional[int] = None, lazy: bool = False):
        """Run the prompt through the bucketed prefill: writes its K/V
        into ``page_ids`` (null-padded to the bucket's page count) and
        returns ``(last_logits (V,), first_token)``.  The first token
        is sampled in-step (``temperature<=0`` = greedy argmax); the
        in-step non-finite screen lands on
        :attr:`last_prefill_finite`.  ``slot`` is the decode slot a model
        with recurrent layers leaves the prompt's state in (required
        there, ignored elsewhere).

        ``lazy=True`` dispatches the program and returns ``(logits,
        pending)`` without reading anything back: :meth:`resolve_prefill`
        reads the token (and waits for the device) later, so that several
        prefills queue on the device back to back while the host stages
        the next (the scheduler's admissions under a decode block)."""
        if self.stateful and slot is None:
            raise ValueError(
                "a model with recurrent layers prefills into a decode slot"
            )
        poison = self._chaos_gate(chaos.SERVE_PREFILL, self.prefill_calls)
        n = len(prompt_ids)
        bucket = self.bucket_for(n)
        name = f"prefill_{bucket}"
        with self._phase("engine/stage", program=name):
            np_b = bucket // self.serve.page_size
            tokens = np.zeros((bucket, 1), np.int32)
            tokens[:n, 0] = np.asarray(prompt_ids, np.int32)
            ids = np.full((np_b,), cache_lib.NULL_PAGE, np.int32)
            ids[: len(page_ids)] = np.asarray(page_ids, np.int32)
            compiled = self._program("prefill", bucket)
            args = (
                self.step_params, self.cache, self._rng_base,
                self._host_args("prefill", bucket).pack(
                    tokens, ids, n, self.prefill_calls, temperature,
                    *((slot,) if self.stateful else ()),
                ),
            )
            self._sentinels[name].observe(*args)
            if lazy:
                logits, next_token, finite, self.cache = compiled(*args)
        self.prefill_calls += 1
        span = dict(bucket=bucket, tokens=n, call=self.prefill_calls,
                    **self._span_kinds)
        if lazy:
            return logits, (next_token, finite, poison, span)
        # int(next_token) syncs, so the phase covers the real device
        # time, not just the async dispatch
        with self._phase("engine/prefill", **span):
            logits, next_token, finite, self.cache = compiled(*args)
            # logits stay ON DEVICE (lazy jax.Array): only the sampled
            # token and the scalar finite screen cross to the host —
            # the logits matrix is (V,)/(B, V) and most callers never
            # read it
            first = int(self._read_tokens(next_token).reshape(-1)[0])
            self.last_prefill_finite = bool(finite) and poison is None
        return logits, first

    def resolve_prefill(self, pending) -> int:
        """Read a ``lazy`` :meth:`prefill`'s first token (the wait for the
        device is this ``engine/prefill`` phase); the non-finite screen and
        a routed model's counts land where a plain prefill leaves them."""
        next_token, finite, poison, span = pending
        with self._phase("engine/prefill", **span):
            first = int(self._read_tokens(next_token).reshape(-1)[0])
            self.last_prefill_finite = bool(finite) and poison is None
        return first

    def chunk_prefill(self, chunk_ids, offset, page_table_row,
                      chunk_page_ids, *,
                      temperature: float = 0.0) -> Tuple[np.ndarray, int]:
        """One page-multiple prefill chunk with carry-in KV offset
        (:func:`apex_tpu.serve.model.chunk_prefill_body`): positions
        before ``offset`` are read from the paged cache through
        ``page_table_row`` — committed prefix-cache pages and this
        request's own earlier chunks alike — and the chunk's K/V are
        written to ``chunk_page_ids`` (null entries skip pages a
        borrowed cache run already holds).  Returns ``(last_logits
        (V,), next_token)`` for the chunk's final live position; the
        scheduler consumes the token only from the FINAL chunk.  Rides
        the ``serve.prefill`` chaos site and
        :attr:`last_prefill_finite` exactly like :meth:`prefill`."""
        poison = self._chaos_gate(chaos.SERVE_PREFILL, self.prefill_calls)
        n = len(chunk_ids)
        bucket = self.bucket_for(n)
        name = f"chunk_prefill_{bucket}"
        with self._phase("engine/stage", program=name):
            np_b = bucket // self.serve.page_size
            tokens = np.zeros((bucket, 1), np.int32)
            tokens[:n, 0] = np.asarray(chunk_ids, np.int32)
            ids = np.full((np_b,), cache_lib.NULL_PAGE, np.int32)
            ids[: len(chunk_page_ids)] = np.asarray(
                chunk_page_ids, np.int32
            )
            table = np.full(
                (self.serve.max_pages_per_seq,), cache_lib.NULL_PAGE,
                np.int32,
            )
            table[: len(page_table_row)] = np.asarray(
                page_table_row, np.int32
            )
            compiled = self._program("chunk_prefill", bucket)
            args = (
                self.step_params, self.cache, self._rng_base,
                self._host_args("chunk_prefill", bucket).pack(
                    tokens, ids, table, n, offset, self.prefill_calls,
                    temperature,
                ),
            )
            self._sentinels[name].observe(*args)
        self.prefill_calls += 1
        with self._phase("engine/prefill", bucket=bucket, tokens=n,
                         offset=int(offset), call=self.prefill_calls,
                         chunked=True):
            logits, next_token, finite, self.cache = compiled(*args)
            first = int(next_token)
            self.last_prefill_finite = bool(finite) and poison is None
        return logits, first

    def fork_page(self, src: int, dst: int) -> None:
        """Copy-on-write fork: duplicate page ``src``'s content into
        ``dst`` across every layer (codes AND scale planes under the
        int8 KV wire) through one tiny compiled donated program — the
        device half of the scheduler's shared-tail-page fork."""
        compiled = self._program("fork_page")
        args = (self.cache, self._host_args("fork_page").pack(src, dst))
        self._sentinels["fork_page"].observe(*args)
        self.cache = compiled(*args)

    def decode(self, tokens, lengths, page_tables, temps=None, *,
               streams=None, gens=None, steps=None):
        """One decode iteration over the full slot array.  ``lengths``
        counts each slot's context INCLUDING the token being fed (0 =
        idle slot).  Returns ``(logits (B, V), next_tokens (B,))`` —
        ``next_tokens`` on host (the scheduler needs them), ``logits``
        left as a lazy on-device array so the hot serving loop never
        pays the (B, V) device→host copy it does not read.  The
        per-slot in-step non-finite screen lands on
        :attr:`last_decode_finite` (the quarantine evidence).

        ``streams``/``gens`` (both ``(B,)``) thread per-slot stream
        seeds and emission indices: each slot samples under the RAW
        ``fold_in(stream_key, gen)`` — the same key a ``k = 0``
        speculative round would consume, which is what makes the two
        paths bit-identical.  None keeps the legacy per-iteration key
        chain, ``fold_in(fold_in(base, iteration), slot)``: the same
        two folds of the same program, fed ``(iteration, slot index)``
        in place of ``(stream seed, emission index)``.

        With ``ServeConfig.decode_block = k > 1`` the program runs ``k``
        iterations: ``steps`` ``(B,)`` says how many each slot runs (None:
        ``k`` for every live slot), ``next_tokens`` comes back ``(k, B)``
        (row ``j`` is iteration ``j``'s token; a slot's rows from its
        ``steps`` on repeat its last) and ``logits`` is the last
        iteration's."""
        poison = self._chaos_gate(chaos.SERVE_DECODE, self.decode_iters)
        with self._phase("engine/stage", program="decode"):
            compiled = self._program("decode")
            if streams is None:
                b = self.serve.max_batch
                streams = np.full((b,), self.decode_iters, np.uint32)
                gens = np.arange(b, dtype=np.int32)
            block = self.serve.decode_block
            if block > 1 and steps is None:
                steps = np.where(np.asarray(lengths) > 0, block, 0)
            args = (
                self.step_params, self.cache, self._rng_base,
                self._host_args("decode").pack(
                    tokens, lengths, page_tables, self._temps(temps),
                    streams, gens, *((steps,) if block > 1 else ()),
                ),
            )
            self._sentinels["decode"].observe(*args)
        self.decode_iters += 1
        # np.asarray(next_tokens) syncs — real device time
        with self._phase("engine/decode", iter=self.decode_iters,
                         batch=int((np.asarray(lengths) > 0).sum()),
                         **self._span_kinds):
            logits, next_tokens, finite, self.cache = compiled(*args)
            out = self._read_tokens(next_tokens)
            if block > 1:
                out = out.reshape(block, -1)
            finite_np = np.array(finite)
        if poison is not None:
            # an injected poisoned-logits fault: flag the first LIVE
            # slot exactly as the in-step screen would flag a real
            # non-finite row — the quarantine path downstream is the
            # production path, only the evidence is simulated
            live = np.flatnonzero(np.asarray(lengths) > 0)
            if live.size:
                finite_np[live[0]] = False
        self.last_decode_finite = finite_np
        return logits, out

    def reset_cache(self) -> None:
        """Re-zero the paged KV arrays (target AND draft).  Only legal
        with an EMPTY pool — live pages hold state requests will read.
        The deploy path calls this on every weight swap: freed pages
        are never scrubbed (a finite stale row costs nothing under the
        attention mask's exact-zero weights), but K/V written by
        NaN-poisoned weights breaks that bargain — ``0 * NaN`` is NaN,
        so one poisoned tenancy would haunt every later request (and
        the rollback's bit-exact fingerprint) through pages it no
        longer owns."""
        if self.pool.in_use != 0:
            raise RuntimeError(
                f"reset_cache with {self.pool.in_use} pages in use"
            )
        self.cache = self._new_cache(self.cfg)
        if self.draft_cache is not None:
            self.draft_cache = self._new_cache(self._draft_cfg)

    def probe_stream(self, prompt_ids, max_new_tokens: int):
        """Golden-probe hook (:mod:`apex_tpu.observability.canary`):
        run ONE prompt greedily (temperature 0) through prefill plus a
        single-slot decode loop and return ``(tokens,
        prefill_logits_bytes, finite)`` — the raw material of a model
        fingerprint.  Greedy argmax ignores the sampler rng, so the
        stream is a pure function of the weights + compiled programs;
        the prefill last-logits float32 bytes make the caller's digest
        sensitive to corruptions too small to flip any argmax.

        Pages come from the engine's own pool and are freed before
        returning; callers probe QUIET engines (drained replicas,
        freshly built engines), so the transient page hold never
        competes with live requests.  ``finite`` folds in the in-step
        non-finite screens — NaN-poisoned weights fingerprint honestly
        instead of crashing the probe."""
        n = len(prompt_ids)
        total = n + int(max_new_tokens)
        if total > self.serve.max_context:
            raise ValueError(
                f"probe needs {total} tokens of context, "
                f"max_context={self.serve.max_context}"
            )
        pages_needed = -(-total // self.serve.page_size)
        if pages_needed > self.serve.max_pages_per_seq:
            raise ValueError(
                f"probe needs {pages_needed} pages/seq, "
                f"max_pages_per_seq={self.serve.max_pages_per_seq}"
            )
        page_ids = self.pool.alloc(pages_needed)
        if page_ids is None:
            raise RuntimeError(
                f"probe_stream: page pool exhausted "
                f"({pages_needed} pages needed) — probe a quiet engine"
            )
        try:
            # prefill takes only the prompt-covering pages (its ids
            # buffer is bucket-sized); decode reaches the growth pages
            # through the full page-table row below
            prompt_pages = page_ids[: -(-n // self.serve.page_size)]
            logits, first = self.prefill(
                prompt_ids, prompt_pages, temperature=0.0,
                slot=0 if self.stateful else None,
            )
            logits_bytes = np.asarray(logits, np.float32).tobytes()
            finite = bool(self.last_prefill_finite)
            tokens = [first]
            b = self.serve.max_batch
            table = np.full(
                (b, self.serve.max_pages_per_seq),
                cache_lib.NULL_PAGE, np.int32,
            )
            table[0, :pages_needed] = np.asarray(page_ids, np.int32)
            for i in range(int(max_new_tokens) - 1):
                tok = np.zeros((b,), np.int32)
                lengths = np.zeros((b,), np.int32)
                tok[0] = tokens[-1]
                lengths[0] = n + i + 1  # ctx incl. the fed token
                _, next_tokens = self.decode(
                    tok, lengths, table,
                    **({"steps": (lengths > 0).astype(np.int32)}
                       if self.serve.decode_block > 1 else {}),
                )
                finite = finite and bool(
                    np.asarray(self.last_decode_finite)[0]
                )
                tokens.append(int(np.asarray(next_tokens).reshape(-1)[0]))
        finally:
            self.pool.free(page_ids)
        return tokens, logits_bytes, finite

    # -- speculative serving calls ----------------------------------------
    def draft_prefill(self, prompt_ids, page_ids) -> None:
        """Prefill the DRAFT model's KV for a prompt into the request's
        draft-namespace pages (the in-step sampled token is discarded —
        the target prefill's token is the stream's first).  Uses its
        own call counter so a speculative deployment leaves the target
        prefill/decode rng chains untouched (the greedy bit-identity
        gate compares spec and plain runs of the same workload)."""
        n = len(prompt_ids)
        bucket = self.bucket_for(n)
        np_b = bucket // self.serve.page_size
        tokens = np.zeros((bucket, 1), np.int32)
        tokens[:n, 0] = np.asarray(prompt_ids, np.int32)
        ids = np.full((np_b,), cache_lib.NULL_PAGE, np.int32)
        ids[: len(page_ids)] = np.asarray(page_ids, np.int32)
        compiled = self._program("draft_prefill", bucket)
        name = f"draft_prefill_{bucket}"
        args = (
            self.draft_step_params, self.draft_cache, self._rng_base,
            self._host_args("draft_prefill", bucket).pack(
                tokens, ids, n, self.draft_prefill_calls, 0.0
            ),
        )
        self._sentinels[name].observe(*args)
        self.draft_prefill_calls += 1
        _logits, _tok, _finite, self.draft_cache = compiled(*args)

    def spec_step(self, tokens, lengths, page_tables, draft_tables,
                  temps, streams, gens):
        """One speculative round over the full slot array: the draft
        program proposes ``k`` tokens per live slot, then ONE verify
        program scores all ``k + 1`` positions and runs acceptance
        on-device.  Returns ``(out_tokens (B, k+1), n_accept (B,),
        finite (B,))`` on host — slot ``b`` emits ``out_tokens[b,
        :n_accept[b] + 1]``.

        Rides the ``serve.draft`` chaos site (a faulted draft degrades
        to zero-acceptance proposals — stream correctness never
        depends on the draft) and the ``serve.decode`` site for the
        verify step exactly like :meth:`decode`."""
        spec = self.spec
        round_idx = self.spec_rounds
        # the round cursor advances on ATTEMPTS, and before the chaos
        # gate: a raise-mode serve.draft fault must burn its round
        # index, or a planted one-shot storm re-fires at the same
        # index forever and wedges speculation permanently
        self.spec_rounds += 1
        fault = self._chaos_gate(chaos.SERVE_DRAFT, round_idx)
        poison = self._chaos_gate(chaos.SERVE_DECODE, self.decode_iters)
        with self._phase("engine/stage", program="spec"):
            # the draft program's dispatch is part of staging the
            # verify call: its device time is waited for under
            # engine/decode, at the first host read.  The draft's
            # proposals, distributions and finite screen stay on the
            # device, and the verify program itself pins the proposals
            # of a failed draft (spec.pin_failed_drafts) — draft_ok is
            # the host's half of that verdict (a serve.draft fault)
            temps = self._temps(temps)
            d_args = (
                self.draft_step_params, self.draft_cache, self._rng_base,
                self._host_args("draft_decode").pack(
                    tokens, lengths, draft_tables, temps, streams, gens
                ),
            )
            compiled = self._program("draft_decode")
            self._sentinels["draft_decode"].observe(*d_args)
            d_tokens, d_probs, d_finite, self.draft_cache = compiled(
                *d_args
            )
            v_args = (
                self.step_params, self.cache, self._rng_base, d_tokens,
                d_probs, d_finite,
                self._host_args("verify").pack(
                    tokens, lengths, page_tables, temps,
                    fault is None, streams, gens,
                ),
            )
            compiled = self._program("verify")
            self._sentinels["verify"].observe(*v_args)
        self.decode_iters += 1
        live_n = int((np.asarray(lengths) > 0).sum())
        # np.asarray(out_tokens) syncs — real device time
        with self._phase("engine/decode", iter=self.decode_iters,
                         batch=live_n, spec=True,
                         drafted=spec.k * live_n) as ph:
            out_tokens, n_accept, finite, self.cache = compiled(*v_args)
            out = np.asarray(out_tokens)
            acc = np.asarray(n_accept)
            finite_np = np.array(finite)
            ph.set(accepted=int(acc.sum()))
        if poison is not None:
            live = np.flatnonzero(np.asarray(lengths) > 0)
            if live.size:
                finite_np[live[0]] = False
        self.last_decode_finite = finite_np
        return out, acc, finite_np

    def rollback(self, starts, counts, page_tables) -> None:
        """Zero the target-KV rows of rejected positions ``[starts[b],
        starts[b] + counts[b])`` through each slot's page table (the
        compiled truncation program — spec.py :func:`~apex_tpu.serve.
        spec.rollback_body`).  The scheduler COW-forked any shared tail
        page BEFORE the round, so every touched page is private."""
        compiled = self._program("rollback")
        args = (
            self.cache,
            self._host_args("rollback").pack(starts, counts, page_tables),
        )
        self._sentinels["rollback"].observe(*args)
        self.cache = compiled(*args)

    def draft_rollback(self, starts, counts, page_tables) -> None:
        """:meth:`rollback` for the draft KV pool (draft page ids)."""
        compiled = self._program("draft_rollback")
        args = (
            self.draft_cache,
            self._host_args("draft_rollback").pack(
                starts, counts, page_tables
            ),
        )
        self._sentinels["draft_rollback"].observe(*args)
        self.draft_cache = compiled(*args)

    def update_draft_params(self, draft_params) -> None:
        """Swap the draft weights in place (a fleet redeploy shipping a
        refreshed draft beside the target); wire-packs under int8
        weights.  ``None`` means "no new draft shipped": a SELF-draft
        engine re-aliases the (possibly just-redeployed) target params
        so the draft never goes stale against its own target; a
        distinct-draft engine keeps the draft it has.  The compiled
        draft programs are shape-specialized, so a different draft
        ARCHITECTURE needs a new engine."""
        if self.spec is None:
            raise ValueError("engine has no speculative config")
        if draft_params is None:
            if self.spec.draft_params is None:
                # the target's step tree too: nothing is cast twice
                self._draft_params = self._params
                self.draft_step_params = self.step_params
        else:
            self._draft_params = self._on_wire(draft_params)
            self.draft_step_params = self._install(
                self._draft_cfg, self._draft_params
            )
        self._publish_weight_gauges()
