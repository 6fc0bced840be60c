"""Continuous batching — admission, decode slots, SLOs, shedding.

The throughput lever of a serving system is keeping the decode batch
full: a decode iteration costs nearly the same whether 1 or
``max_batch`` sequences ride it (the weights are read either way), so
every empty slot is wasted HBM bandwidth.
:class:`ContinuousBatchingScheduler` admits new sequences INTO the
running batch at page granularity — a prefill is slotted between decode
iterations (bucketed padding keeps the compiled-shape count finite),
the new sequence joins the very next decode, and finished sequences
free their pages to the pool immediately.

Admission control and degradation are explicit:

- a request is admitted when a decode slot is free AND the page pool
  covers its prompt (``PagePool.alloc`` is all-or-nothing);
- a queued request whose **TTFT SLO deadline** has already passed while
  the pool stays exhausted is **shed** (rejected loudly — the client
  can retry elsewhere) instead of silently blowing its latency budget;
- when a RUNNING sequence needs a growth page and the pool is empty,
  the youngest running request is shed to keep the older ones making
  progress (LIFO victim: it has the least sunk prefill cost).

Every shed carries a **reason** (:data:`SHED_REASONS`): the single
``serve/shed`` counter is split into per-reason counters so "we shed
3%" becomes "we shed 3%, all of it deadline-in-queue — admission is
starved, not the decode batch".

**Failure is a scheduling input** (docs/serving.md "Failure semantics
& degradation ladder").  The request lifecycle carries recovery
guarantees:

- **bounded re-admission retries** — a prefill/decode fault or a
  blown per-request decode timeout sends the request to the
  ``retrying`` phase with its pages and generated prefix RETAINED;
  re-admission resumes decode from the last completed iteration (no
  re-prefill once the first token exists), bounded by ``max_retries``
  and ledgered as ``shed(retries_exhausted)`` past it.  A model with
  RECURRENT layers keeps its pages and prefix too, but its per-slot
  state is not retained across the fault (the slot may be another
  request's by then, and the faulted step may have advanced it): its
  re-admission PREFILLS AGAIN over prompt + generated prefix into the
  slot it is given, and is shed as ``oversize`` when that no longer
  fits a prefill bucket (docs/serving.md "Layer kinds and the cache
  set");
- **poisoned-request quarantine** — a non-finite logits row (the
  engine's in-step screen) evicts ONLY the offending slot, ledgered
  ``shed(poisoned)``; the rest of the batch keeps decoding;
- **engine supervision** — a crashed decode step moves every running
  request to ``retrying`` (re-admitted on the very next iteration,
  riding the incumbent compiled program) and schedules the engine's
  supervised :meth:`~apex_tpu.serve.engine.InferenceEngine.rebuild`
  for the next idle point, escalating to a synchronous rebuild on a
  repeat fault (bounded by ``rebuild_limit``) — one transient fault
  never turns into a recompile-sized latency cliff for the whole
  queue;
- **graceful drain** (:meth:`ContinuousBatchingScheduler.drain`) —
  rolling-restart shutdown: stop admitting new work, finish running
  (and retrying) decodes, shed the never-admitted queue loudly as
  ``shed(draining)``, and report the drained state with the page pool
  provably empty.

Overload walks an explicit **degradation ladder**, each rung a
distinct ledger reason on the span state machine, metrics board, and
OpenMetrics export:

1. **backpressure** — a bounded admission queue (``max_queue_depth``)
   fast-rejects at submit time, ``shed(queue_full)``: the client gets
   an immediate retry-elsewhere signal instead of a blown deadline;
2. **max-new-tokens clamping** — past ``clamp_occupancy`` pool
   pressure (or a half-full bounded queue), admissions are clamped to
   ``clamp_max_new_tokens`` (``serve/clamped`` counter + a
   ``req/clamped`` span instant carrying the original budget);
3. **deadline shedding** — the existing TTFT-SLO rung,
   ``shed(deadline)``.

:meth:`leak_check` (``PagePool.leak_check`` against the live ownership
ledger) is asserted after every shed/free path when ``leak_checks=``
is on (the default), so page accounting stays provably exact through
every fault.

Every iteration publishes the serving gauges through the shared
:class:`~apex_tpu.observability.metrics.MetricRegistry` — queue depth,
batch fill, page-pool occupancy, tokens/s, TTFT — the same spine
training telemetry rides, so :class:`~apex_tpu.observability.health.
TTFTRule` / :class:`~apex_tpu.observability.health.QueueDepthRule`
watchdogs page the same health layer (``docs/serving.md``).

**Prefix caching & chunked prefill** (``docs/serving.md``): with
``prefix_cache=True`` every admitted prompt is matched against a
content-addressed cache of committed KV page runs
(:class:`~apex_tpu.serve.cache.PrefixCache`) — hit pages are borrowed
(refcounted, copy-on-write on the first divergent append) and their
prefill is SKIPPED; only the prompt's final chunk re-runs, so a shared
system prompt is paid for once.  ``prefill_chunk_tokens=`` additionally
slices cold prefills into page-multiple chunks advanced one per step
between decode iterations (the ``prefilling`` slot phase), so a long
cold prompt no longer stalls running streams.  Both default OFF.

**Speculative decoding** (``docs/serving.md`` "Speculative decoding"):
when the engine carries a :class:`~apex_tpu.serve.spec.SpecConfig`,
spec-eligible slots ride a propose → verify → accept/rollback round
per iteration instead of a single-token decode — a small draft model
proposes ``k`` tokens from its own KV pages (allocated in the
``draft`` PagePool namespace, never shared into the prefix cache) and
ONE target step scores all ``k+1`` positions.  Greedy acceptance is an
exact argmax match, so the emitted stream is bit-identical to plain
decode by construction; temperature mode uses the rejection sampler
that provably preserves the target distribution.  The scheduler owns
the per-slot state machine: mixed spec/plain batches, demotion on
draft faults (``serve.draft`` chaos site — a broken draft can slow a
stream but never corrupt it), COW-forking the whole speculative window
BEFORE a round so rejected-tail truncation never writes a shared page,
and a degradation-ladder fallback to plain decode when the windowed
acceptance rate collapses below ``min_accept_rate`` (sticky until
:meth:`resume`).

**TTFT attribution** (``docs/observability.md``): each completed
request's TTFT decomposes into four components that sum to the
measured TTFT *by construction* (the same remainder discipline
:mod:`~apex_tpu.observability.attribution` applies to step time):

- ``queue_wait`` — time the request sat in the queue while admission
  was **resource-blocked** (no free decode slot, or the page pool
  could not cover the queue head);
- ``cached_prefill`` — the prefix-cache share of the post-admission
  phase (hash/match/borrow and page allocation up to the first engine
  call); exactly 0.0 when the cache is off;
- ``prefill``    — admission to first token (the prefill program);
- ``contention`` — the remainder of the pre-admission wait: the
  request was admissible but the scheduler was busy running decode
  iterations for the requests already in the batch.

Per-component p50/p95/p99 gauges and the queue-wait fraction publish
through the registry on the observation cadence;
:class:`~apex_tpu.observability.health.QueueWaitFractionRule` alerts
when TTFT is dominated by starved admission.  With a
:class:`~apex_tpu.observability.spans.SpanRecorder` attached
(``spans=``), every request additionally records its full span chain
``queued → admitted → prefill → decode[i] → done|shed(reason)`` with
engine decode-iteration correlation ids — the per-request causal
record ``tools/timeline.py`` merges into one Perfetto timeline.

Host phases: what the host does inside ``step()`` is named by
:meth:`~apex_tpu.observability.spans.SpanRecorder.phase` —
``serve/step`` and under it ``serve/admit``, ``serve/chunks``,
``serve/batch``, ``serve/retire``, ``serve/publish``, with the engine's
``engine/stage`` / ``engine/prefill`` / ``engine/decode`` between them
and, inside a call, ``engine/dispatch`` and one ``engine/read`` a host
read (docs/serving.md "Host phases").  They go to the attached recorder and,
with none attached, to the ring every process keeps
(:func:`~apex_tpu.observability.spans.process_recorder`); the request
lifecycle above is recorded on an attached recorder only.

**One decode call in flight** (docs/serving.md "Host phases"): while no
request waits, no chunked prefill runs, the pass is no speculative round
and no rider ends with the call by its budget (:meth:`~ContinuousBatchingScheduler.
_can_overlap`), a plain decode call is left unread past its step; the
next step dispatches call N + 1, fed each rider's token from call N's
output on the device, and reads call N after that launch — the host's
reads, bookkeeping and launch run inside the next program.  A step reads
one call: a call with a rider that ends with it, or one the rule no longer
lets stay in flight, is read by the next step, which does nothing else.
An EOS or a quarantine call N reveals is acted on when N is read, and the
rider's N + 1 token is discarded.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import time
import zlib
from typing import Deque, Dict, List, Optional

import numpy as np

from apex_tpu.observability.meter import percentile as _percentile
from apex_tpu.observability.ometrics import (
    DEFAULT_LATENCY_BUCKETS_MS,
    Histogram,
)
from apex_tpu.observability.spans import host_recorder
from apex_tpu.ops import _dispatch
from apex_tpu.ops.paged_attention import walk_live_share
from apex_tpu.resilience import chaos
from apex_tpu.serve import model as model_lib
from apex_tpu.serve.cache import NULL_PAGE, PrefixCache

__all__ = [
    "Request",
    "ContinuousBatchingScheduler",
    "declare_serve_metrics",
    "ttft_attribution",
    "SHED_REASONS",
    "SHED_REROUTED",
    "TTFT_COMPONENTS",
]

_ids = itertools.count()

QUEUED = "queued"
RUNNING = "running"
#: chunked prefill in flight: the request holds a decode slot (so its
#: pages and position are pinned) but rides NO decode iteration until
#: its final prefill chunk produced the first token
PREFILLING = "prefilling"
#: fault recovery: the request left the batch (or never reached it)
#: after a fault and waits at the queue front for bounded re-admission
#: with its pages and generated prefix retained
RETRYING = "retrying"
DONE = "done"
SHED = "shed"

#: shed reasons, each with its own ``serve/shed_<reason>`` counter:
#: ``deadline`` (queued past its TTFT SLO while the pool stayed
#: exhausted), ``growth_victim`` (youngest running request shed to free
#: a growth page), ``pool_exhausted`` (a running request could not grow
#: even after a victim shed), ``oversize`` (prompt exceeds the max
#: context), ``poisoned`` (non-finite logits row — quarantined, only
#: the offending slot), ``queue_full`` (backpressure fast-reject at the
#: bounded admission queue), ``retries_exhausted`` (a faulting request
#: burned its re-admission budget), ``draining`` (never-admitted work
#: rejected during a graceful rolling-restart drain), ``rerouted``
#: (never-admitted work a :meth:`~ContinuousBatchingScheduler.drain`
#: ``handoff=`` target accepted — the request is NOT terminal: it left
#: THIS replica's ledger and continues on another one).
SHED_DEADLINE = "deadline"
SHED_GROWTH_VICTIM = "growth_victim"
SHED_POOL_EXHAUSTED = "pool_exhausted"
SHED_OVERSIZE = "oversize"
SHED_POISONED = "poisoned"
SHED_QUEUE_FULL = "queue_full"
SHED_RETRIES_EXHAUSTED = "retries_exhausted"
SHED_DRAINING = "draining"
SHED_REROUTED = "rerouted"
SHED_REASONS = (
    SHED_DEADLINE, SHED_GROWTH_VICTIM, SHED_POOL_EXHAUSTED, SHED_OVERSIZE,
    SHED_POISONED, SHED_QUEUE_FULL, SHED_RETRIES_EXHAUSTED, SHED_DRAINING,
    SHED_REROUTED,
)

#: TTFT attribution components (ms); they sum to the measured TTFT by
#: construction — see the module docstring.  ``cached_prefill`` is the
#: prefix-cache share of the post-admission phase (hash/match/borrow/
#: alloc up to the first engine call); it is EXACTLY 0.0 when the
#: cache is off, so the legacy three-component sum is unchanged.
TTFT_COMPONENTS = ("queue_wait", "cached_prefill", "prefill", "contention")

def ttft_attribution(comps) -> Dict[str, object]:
    """Aggregate per-request TTFT components
    (:meth:`Request.ttft_components` dicts) into per-component
    p50/p95/p99 + the queue-wait fraction — the ONE aggregation behind
    both the scheduler's ``serve/ttft_*`` registry gauges and the
    ``tools/serve_bench.py`` artifact, so the two surfaces
    ``verify_tier1.sh`` cross-checks can never drift apart."""
    out: Dict[str, object] = {}
    for comp in TTFT_COMPONENTS:
        vals = sorted(c[f"{comp}_ms"] for c in comps)
        out[f"{comp}_ms"] = {
            tag: _percentile(vals, q)
            for q, tag in ((0.50, "p50"), (0.95, "p95"), (0.99, "p99"))
        }
    total_ttft = sum(c["ttft_ms"] for c in comps)
    out["queue_wait_fraction"] = (
        sum(c["queue_wait_ms"] for c in comps) / total_ttft
        if total_ttft > 0 else 0.0
    )
    out["samples"] = len(comps)
    return out


#: default for ``ContinuousBatchingScheduler(registry=...)``: inherit
#: the engine's registry.  Pass ``registry=None`` to run with NO
#: telemetry (e.g. a baseline probe that must not pollute the engine
#: registry's observation stream).
ENGINE_REGISTRY = object()


@dataclasses.dataclass
class Request:
    """One generation request and its lifecycle ledger."""

    prompt: List[int]
    max_new_tokens: int = 16
    #: TTFT SLO in milliseconds; None = best-effort (never shed by
    #: deadline, only as a growth-page victim)
    slo_ttft_ms: Optional[float] = None
    eos_token: Optional[int] = None
    #: per-request decode timeout: a decode iteration this request rode
    #: exceeding it discards the iteration's token for THIS request and
    #: sends it through bounded re-admission retry (prefix preserved).
    #: None inherits the scheduler's default (usually also None).
    decode_timeout_ms: Optional[float] = None
    #: sampling temperature for the fused in-step sampler; <= 0 is
    #: greedy argmax (bit-identical to the pre-sampler engine)
    temperature: float = 0.0
    #: per-request sampling-stream seed: every temperature draw for
    #: this stream keys off ``fold_in(engine base, stream_seed)`` then
    #: the emission index — a function of request identity and stream
    #: position, never of engine call counters, so a speculative
    #: rollback replays identically and a ``k = 0`` spec stream equals
    #: the plain one.  None derives a seed from :attr:`rid` (distinct
    #: per request); pass an explicit seed to reproduce a stream
    #: across schedulers/replicas.
    stream_seed: Optional[int] = None
    rid: int = dataclasses.field(default_factory=lambda: next(_ids))

    # -- runtime ledger (scheduler-owned) --------------------------------
    status: str = QUEUED
    tokens: List[int] = dataclasses.field(default_factory=list)
    pages: List[int] = dataclasses.field(default_factory=list)
    #: KV positions written (prompt + generated-and-fed tokens)
    ctx_len: int = 0
    submitted_at: Optional[float] = None
    #: popped from the queue with pages granted (prefill dispatch)
    admitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    done_at: Optional[float] = None
    #: why this request was shed (one of :data:`SHED_REASONS`), else None
    shed_reason: Optional[str] = None
    #: accumulated seconds the request sat in the queue while admission
    #: was resource-blocked (the ``queue_wait`` TTFT component)
    queue_blocked_s: float = 0.0
    #: start of the current resource-blocked interval (scheduler-owned)
    blocked_since: Optional[float] = None
    #: engine decode iterations this request rode (correlation ids
    #: into the ``serve/engine`` span track)
    first_decode_iter: Optional[int] = None
    last_decode_iter: Optional[int] = None
    #: re-admission retries consumed (bounded by the scheduler's
    #: ``max_retries``); the last cause rides the span record
    retries: int = 0
    #: original ``max_new_tokens`` when the overload ladder clamped it
    #: (None = never clamped)
    clamped_from: Optional[int] = None
    # -- prefix cache / chunked prefill (scheduler-owned) ----------------
    #: prompt tokens already covered by KV pages (cache hit + completed
    #: prefill chunks); equals ``len(prompt)`` once prefill is done
    prefill_pos: int = 0
    #: prompt tokens the prefix cache covered at admission (0 = miss)
    cache_hit_tokens: int = 0
    #: leading pages of :attr:`pages` borrowed from the cache (refcount
    #: shared — chunk writes to them are redirected to the null page)
    cache_hit_pages: int = 0
    #: the cache was already probed for this request (the match/borrow
    #: runs ONCE, even when admission then blocks on the pool)
    cache_probed: bool = False
    #: first engine prefill/chunk call for this request — splits the
    #: post-admission phase into ``cached_prefill`` (match/borrow/alloc)
    #: and ``prefill`` (compute); None = cache off, component is 0.0
    prefill_started_at: Optional[float] = None
    # -- speculative decoding (scheduler-owned) --------------------------
    #: draft-model KV pages (``"draft"`` pool namespace) mirroring
    #: :attr:`pages` position-for-position; freed on every retire path
    draft_pages: List[int] = dataclasses.field(default_factory=list)
    #: False once this request's draft state is unusable (draft prefill
    #: faulted): the stream decodes plain — spec is an accelerator, a
    #: broken draft must never cost the stream more than speed
    spec_ok: bool = True

    @property
    def ttft_ms(self) -> Optional[float]:
        if self.submitted_at is None or self.first_token_at is None:
            return None
        return 1e3 * (self.first_token_at - self.submitted_at)

    def ttft_components(self) -> Optional[Dict[str, float]]:
        """``{ttft_ms, queue_wait_ms, cached_prefill_ms, prefill_ms,
        contention_ms}`` — the four components sum to ``ttft_ms`` by
        construction (contention is the remainder of the pre-admission
        wait; ``cached_prefill_ms`` is exactly 0.0 when the prefix
        cache is off)."""
        if (
            self.submitted_at is None
            or self.admitted_at is None
            or self.first_token_at is None
        ):
            return None
        queue_wait = 1e3 * self.queue_blocked_s
        cached = (
            1e3 * (self.prefill_started_at - self.admitted_at)
            if self.prefill_started_at is not None else 0.0
        )
        prefill = 1e3 * (
            self.first_token_at
            - (self.prefill_started_at
               if self.prefill_started_at is not None
               else self.admitted_at)
        )
        contention = (
            1e3 * (self.admitted_at - self.submitted_at) - queue_wait
        )
        return {
            "ttft_ms": self.ttft_ms,
            "queue_wait_ms": queue_wait,
            "cached_prefill_ms": cached,
            "prefill_ms": prefill,
            "contention_ms": contention,
        }


@dataclasses.dataclass
class _DecodeCall:
    """A plain decode call the scheduler dispatched, until it is read."""

    #: its token array as ``engine.decode`` returned it
    out: object
    #: ``engine.decode_iters`` once it was dispatched: the call's index
    index: int
    #: ``(slot, request)`` of every slot that rode it
    riders: list
    #: ``(B,)`` iterations each slot runs
    steps: np.ndarray
    #: its dispatch on the scheduler's clock (a decode timeout runs from it)
    t0: float
    #: left in flight only until the next step starts, which reads it and
    #: does nothing else: a rider ends with it
    land: bool = False


def declare_serve_metrics(registry, *, stateful: bool = False,
                          routed: bool = False, latent: bool = False,
                          paged: bool = True, ssm: bool = False) -> None:
    """Declare the serving metric set on a registry (idempotent); the
    metrics of a layer kind only for a model that has it."""
    if paged:
        # of the pages the paged decode kernel's walk copied in for the
        # step's decode call, the share that held live positions
        registry.gauge("serve/decode_walk_live_share")
    if routed:
        # per step program, summed over the routed layers: (token,
        # expert) pairs routed to the experts this chip holds, and
        # distinct held experts touched (= expert weights streamed)
        registry.counter("serve/moe/routed_local_tokens")
        registry.counter("serve/moe/experts_touched")
    if stateful:
        registry.gauge("serve/state/slots_in_use")
        registry.gauge("serve/state/bytes", "bytes")
    if ssm:
        # prefills that replaced a slot's state-space state, and the bytes
        # of it the step's decode call had to read and write an iteration
        registry.counter("serve/ssm/slots_written")
        registry.gauge("serve/ssm/state_bytes_per_iter", "bytes")
    if latent:
        registry.gauge("serve/latent/pages_in_use")
        # latent rows the step's decode and chunk calls read, summed over
        # the latent-attention layers (from the lengths the host holds)
        registry.counter("serve/latent_rows_read")
    for g in ("serve/queue_depth", "serve/batch_fill",
              "serve/page_occupancy", "serve/tokens_per_s",
              "serve/ttft_ms", "serve/draining"):
        registry.gauge(g)
    for c in ("serve/admitted", "serve/completed", "serve/shed",
              "serve/tokens_out", "serve/prefills", "serve/decode_steps",
              # page-multiple prefill chunks run (docs/serving.md
              # "Prefix caching & chunked prefill")
              "serve/prefill_chunks",
              # decode calls dispatched with the call before them unread
              # (docs/serving.md "Host phases": one call in flight)
              "serve/decode_overlapped",
              # the failure/degradation ledger (docs/serving.md
              # "Failure semantics"): retries + re-admissions, clamped
              # admissions, per-request decode timeouts, engine faults
              # and supervised rebuilds, chaos-visible admission and
              # page-allocation faults, graceful drains
              "serve/retries", "serve/readmitted", "serve/clamped",
              "serve/decode_timeouts", "serve/engine_faults",
              "serve/engine_rebuilds", "serve/admission_faults",
              "serve/kv_alloc_faults", "serve/drains",
              # prefix-cache ledger (docs/serving.md "Prefix caching"):
              # admission hits/misses, tokens whose prefill the cache
              # skipped, COW tail-page forks, committed runs, LRU
              # evictions under pool pressure + forced chaos sweeps
              "serve/prefix_hits", "serve/prefix_misses",
              "serve/prefix_hit_tokens", "serve/prefix_forks",
              "serve/prefix_commits", "serve/prefix_evictions",
              "serve/prefix_evict_faults",
              # speculative-decoding ledger (docs/serving.md
              # "Speculative decoding"): rounds, proposals drafted /
              # accepted / rejected, rollback programs run, ladder
              # fallbacks to plain decode, faulted draft calls
              "serve/spec_rounds", "serve/spec_drafted",
              "serve/spec_accepted", "serve/spec_rejected",
              "serve/spec_rollbacks", "serve/spec_fallbacks",
              "serve/draft_faults"):
        registry.counter(c)
    registry.gauge("serve/prefix_cached_pages")
    # windowed acceptance rate + emitted tokens per slot decode step —
    # the SpecAcceptanceRule watchdog and the bench read these
    registry.gauge("serve/spec_accept_rate")
    registry.gauge("serve/spec_tokens_per_step")
    # per-reason shed breakdown (sums to serve/shed)
    for reason in SHED_REASONS:
        registry.counter(f"serve/shed_{reason}")
    # TTFT attribution: per-component percentiles over the recent
    # completion window, plus the fraction the watchdog judges
    for comp in TTFT_COMPONENTS:
        for tag in ("p50", "p95", "p99"):
            registry.gauge(f"serve/ttft_{comp}_ms_{tag}", "ms")
    registry.gauge("serve/ttft_queue_wait_fraction")


class ContinuousBatchingScheduler:
    """Drive an :class:`~apex_tpu.serve.engine.InferenceEngine` with
    continuous batching.

    >>> sched = ContinuousBatchingScheduler(engine)
    >>> sched.submit(Request(prompt=[...], max_new_tokens=32))
    >>> while sched.pending:
    ...     sched.step()

    ``spans`` attaches a :class:`~apex_tpu.observability.spans.
    SpanRecorder`: the scheduler records each request's lifecycle span
    chain and hands the same recorder to the engine for its
    prefill/decode-iteration spans (taking over from any previous
    scheduler's recorder, and sharing a non-default ``clock`` with the
    recorder so the whole record stays on one time basis).
    """

    def __init__(self, engine, *, registry=ENGINE_REGISTRY,
                 clock=time.monotonic, window: int = 32,
                 spans=None, attribution_window: int = 128,
                 max_queue_depth: Optional[int] = None,
                 max_retries: int = 2,
                 decode_timeout_ms: Optional[float] = None,
                 clamp_max_new_tokens: Optional[int] = None,
                 clamp_occupancy: float = 0.75,
                 clamp_queue_depth: Optional[int] = None,
                 rebuild_limit: int = 2,
                 leak_checks: bool = True,
                 prefix_cache: bool = False,
                 prefill_chunk_tokens: Optional[int] = None):
        self.engine = engine
        self.pool = engine.pool
        self.serve = engine.serve
        self.clock = clock
        # what the model's layer kinds ask of this loop: a slot's
        # recurrent state is written by the prefill that admits into it;
        # a routed model's steps hand back the MoE counts
        self._stateful = bool(getattr(engine, "stateful", False))
        self._routed = bool(getattr(engine, "routed", False))
        # what the cache set holds is read from the declaration its mixer
        # kinds made (``engine.cache_kinds``): a slot's share of the
        # recurrent slabs, and of the state-space one alone
        kinds = getattr(engine, "cache_kinds", ())
        self._latent = any(k.name == "latent" for k in kinds)
        # each latent-attention layer reads its own rows
        self._latent_layers = sum(
            k.layers for k in kinds if k.name == "latent")
        self._slot_state_bytes = sum(
            k.row_bytes for k in kinds if k.per == "slot" and k.in_place)
        self._ssm_slot_bytes = sum(
            k.row_bytes for k in kinds if k.name == "ssm")
        # a K/V page pool: the paged decode kernel walks it, and
        # `serve/decode_walk_live_share` is reckoned from the lengths of
        # the step's plain decode call
        self._paged = "k" in engine.cache
        self._decode_lengths = None
        # under a decode block the admissions of a step are dispatched back
        # to back and read once (:meth:`_resolve_prefills`): the device
        # never waits for the host between two of them
        self._lazy_prefill = self.serve.decode_block > 1
        self._dispatched: List = []
        # the plain decode call left in flight past a step, unread
        # (:meth:`_can_overlap`); its riders' tokens are still on the device
        self._flight: Optional[_DecodeCall] = None
        model_lib.validate_features(
            engine.cfg, prefix_cache=prefix_cache,
            chunked_prefill=prefill_chunk_tokens is not None,
        )
        # cross-request prefix cache + chunked prefill (docs/serving.md
        # "Prefix caching & chunked prefill"); both default OFF — the
        # monolithic cold path stays byte-for-byte the legacy one
        self.prefix = PrefixCache(self.pool) if prefix_cache else None
        if prefill_chunk_tokens is not None and (
            prefill_chunk_tokens <= 0
            or prefill_chunk_tokens % self.serve.page_size
        ):
            raise ValueError(
                "prefill_chunk_tokens must be a positive multiple of "
                f"page_size={self.serve.page_size}, got "
                f"{prefill_chunk_tokens}"
            )
        self.prefill_chunk_tokens = prefill_chunk_tokens
        # failure/degradation knobs (docs/serving.md "Failure
        # semantics & degradation ladder")
        self.max_queue_depth = max_queue_depth
        self.max_retries = max_retries
        self.decode_timeout_ms = decode_timeout_ms
        self.clamp_max_new_tokens = clamp_max_new_tokens
        self.clamp_occupancy = clamp_occupancy
        self.clamp_queue_depth = clamp_queue_depth
        if clamp_queue_depth is None and max_queue_depth is not None:
            self.clamp_queue_depth = max(1, max_queue_depth // 2)
        self.rebuild_limit = rebuild_limit
        self.leak_checks = leak_checks
        # speculative decoding (docs/serving.md "Speculative
        # decoding"): per-round (drafted, accepted, emitted,
        # slot_steps) window driving the acceptance gauges and the
        # degradation-ladder fallback; sticky until resume()
        self._spec_window: Optional[Deque] = (
            collections.deque(maxlen=engine.spec.window)
            if engine.spec is not None else None
        )
        self._spec_fallback = False
        self.draining = False
        self._drain_handoff = None
        self._drain_rerouted = 0
        self._rebuild_pending = False
        self._rebuilds_started = 0
        self._admissions = 0   # chaos index for the serve.admission site
        self._kv_allocs = 0    # chaos index for the serve.kv_alloc site
        self.leak_checks_run = 0
        self.queue: Deque[Request] = collections.deque()
        self.slots: List[Optional[Request]] = [None] * self.serve.max_batch
        self.completed: List[Request] = []
        self.shed: List[Request] = []
        self._step = 0
        self._riders = 0       # slots that rode this step's decode calls
        # tokens/s over a sliding window of (time, cumulative tokens)
        self._tokens_out = 0
        self._window: Deque = collections.deque(maxlen=window)
        self.registry = (
            engine.registry if registry is ENGINE_REGISTRY else registry
        )
        self.spans = spans
        # this scheduler owns the engine's recorder for its lifetime —
        # a later scheduler on the same engine takes over cleanly
        # (spans=None DETACHES a retired scheduler's recorder) instead
        # of feeding a dead recorder events uncorrelated to any chain
        engine.spans = spans
        if spans is not None:
            if clock is not time.monotonic:
                # ONE time basis per recorder: the request ledger uses
                # this clock, so the engine spans (rec.now()) must too
                # — a mixed-clock record would merge into a timeline
                # that silently misplaces half its tracks.  Export
                # alignment via the wall-clock anchor assumes the
                # default monotonic clock.
                spans.clock = clock
        # recent completions' TTFT components — the percentile window
        self._comps: Deque[Dict[str, float]] = collections.deque(
            maxlen=attribution_window
        )
        # host-side TTFT distribution: the OpenMetrics histogram an
        # --ops-port scrape exposes and the latency-SLO burn-rate math
        # reads (good = observations under the deadline bucket) — one
        # bisect per admission, registry or not
        self.ttft_hist = Histogram(
            "serve/ttft_hist_ms", DEFAULT_LATENCY_BUCKETS_MS, unit="ms",
            help="TTFT distribution over admitted requests",
        )
        self._published_done = 0
        # the metric state is host numbers (Python floats folded by
        # MetricRegistry.host_update): these are events this loop
        # counts between two compiled programs, and a device-side
        # counter would launch a transfer and a program per event
        self._mstate = None
        if self.registry is not None:
            declare_serve_metrics(
                self.registry, stateful=self._stateful, routed=self._routed,
                ssm=bool(self._ssm_slot_bytes),
                latent=self._latent, paged=self._paged,
            )
            self._mstate = self.registry.host_init()

    # -- bookkeeping ------------------------------------------------------
    @property
    def running(self) -> List[Request]:
        return [r for r in self.slots if r is not None]

    @property
    def pending(self) -> bool:
        return (bool(self.queue) or any(s is not None for s in self.slots)
                or self._flight is not None)

    def slots_in_use(self) -> int:
        """Decode slots a request holds — for a model with recurrent
        layers, the slots whose state is some request's."""
        return sum(1 for r in self.slots if r is not None)

    def _fold_moe(self, counts) -> None:
        """A routed model's counts of one call into the registry: Python
        numbers the engine read inside the call's one token readback."""
        pairs, touched = counts
        self._count("serve/moe/routed_local_tokens", float(pairs))
        self._count("serve/moe/experts_touched", float(touched))

    def batch_fill(self) -> float:
        return len(self.running) / len(self.slots)

    def submit(self, req: Request) -> Request:
        req.status = QUEUED
        now = self.clock()
        if req.submitted_at is None:
            # a re-routed request (fleet handoff / crash evacuation)
            # keeps its ORIGINAL submission time: its end-to-end TTFT
            # and SLO deadline are measured from the client's submit,
            # not from the hop onto this replica
            req.submitted_at = now
        if self.spans is not None:
            self.spans.request_event(
                req.rid, QUEUED, now,
                prompt_tokens=len(req.prompt),
                slo_ttft_ms=req.slo_ttft_ms,
            )
        # degradation rung 1 — backpressure: a bounded queue rejects at
        # the front door (the client can retry elsewhere NOW) instead
        # of queueing work that will only blow its deadline later.  A
        # draining scheduler rejects everything new the same loud way.
        if self.draining:
            self._shed_request(req, SHED_DRAINING)
            return req
        if (
            self.max_queue_depth is not None
            and len(self.queue) >= self.max_queue_depth
        ):
            self._shed_request(req, SHED_QUEUE_FULL)
            return req
        self.queue.append(req)
        return req

    def _page_table_row(self, req: Request) -> np.ndarray:
        row = np.full((self.serve.max_pages_per_seq,), NULL_PAGE, np.int32)
        row[: len(req.pages)] = req.pages
        return row

    def _close_blocked(self, req: Request, now: float) -> None:
        if req.blocked_since is not None:
            req.queue_blocked_s += now - req.blocked_since
            req.blocked_since = None

    def _span_terminal(self, req: Request, status: str,
                       reason: Optional[str]) -> None:
        rec = self.spans
        if rec is None:
            return
        args: Dict[str, object] = {}
        if status == DONE:
            args["tokens"] = len(req.tokens)
        else:
            args["reason"] = reason
            if req.submitted_at is not None and req.done_at is not None:
                args["waited_ms"] = 1e3 * (req.done_at - req.submitted_at)
        if req.first_decode_iter is not None:
            args["first_iter"] = req.first_decode_iter
            args["last_iter"] = req.last_decode_iter
        # a request retired straight out of prefill (finished or shed
        # at its first token) still owns its TTFT attribution — attach
        # it here so the req/prefill span carries the components
        if rec.open_requests.get(req.rid) == "prefill":
            comps = req.ttft_components()
            if comps:
                args.update(comps)
        rec.request_event(req.rid, status, req.done_at, **args)

    def _retire(self, req: Request, status: str,
                reason: Optional[str] = None) -> None:
        if req.pages:
            self.pool.free(req.pages)
            req.pages = []
        if req.draft_pages:
            self.pool.free(req.draft_pages)
            req.draft_pages = []
        req.status = status
        req.shed_reason = reason if status == SHED else None
        req.done_at = self.clock()
        self._close_blocked(req, req.done_at)
        self._span_terminal(req, status, reason)
        if status == DONE:
            self.completed.append(req)
            comps = req.ttft_components()
            if comps is not None:
                self._comps.append(comps)
        else:
            self.shed.append(req)
        if self.leak_checks:
            # every shed/free path funnels through here: page
            # accounting is re-proven exact on each of them
            self.leak_check()

    def _shed_request(self, req: Request, reason: str) -> None:
        self._retire(req, SHED, reason)
        self._count("serve/shed")
        self._count(f"serve/shed_{reason}")

    def _reroute_request(self, req: Request, handoff) -> bool:
        """Offer a never-admitted request to a drain ``handoff``
        target instead of shedding it (docs/serving.md "Fleet
        operations").  Any retained pages are dropped FIRST — pages
        are replica-local, a re-routed request re-prefills elsewhere —
        then the target decides.  On acceptance the request leaves
        this replica's ledger as ``shed(rerouted)`` on the counters
        (so the per-reason breakdown still sums to ``serve/shed``) but
        is NOT terminal: no shed span, no ``self.shed`` entry — the
        handoff target owns its lifecycle now.  On refusal the caller
        falls back to the loud ``shed(draining)`` path."""
        if req.pages:
            self.pool.free(req.pages)
            req.pages = []
        if req.draft_pages:
            self.pool.free(req.draft_pages)
            req.draft_pages = []
        if not handoff(req):
            return False
        self._count("serve/shed")
        self._count(f"serve/shed_{SHED_REROUTED}")
        if self.leak_checks:
            self.leak_check()
        return True

    # -- page accounting ---------------------------------------------------
    def owned_pages(self) -> List[List[int]]:
        """The live ownership ledger: per-request page lists across the
        running slots AND the retrying queue entries (a retrying
        request keeps its pages — that is what makes resume cheap)."""
        owned = [r.pages for r in self.slots if r is not None and r.pages]
        owned.extend(r.pages for r in self.queue if r.pages)
        owned.extend(
            r.draft_pages for r in self.slots
            if r is not None and r.draft_pages
        )
        owned.extend(r.draft_pages for r in self.queue if r.draft_pages)
        return owned

    def leak_check(self) -> None:
        """Assert ``PagePool`` accounting is exact against
        :meth:`owned_pages` (raises ``ValueError`` naming the pages).
        Runs automatically after every shed/free path when
        ``leak_checks=True`` (the default).  The check is
        O(num_pages) per retirement — negligible at test/CI pool
        sizes; a latency-critical deployment with a very large pool
        can pass ``leak_checks=False`` and rely on the chaos drill's
        continuous proof instead."""
        self.pool.leak_check(
            self.owned_pages(),
            cached=self.prefix.cached_pages()
            if self.prefix is not None else (),
        )
        self.leak_checks_run += 1

    def _alloc(self, n: int, ns: str = "kv") -> Optional[List[int]]:
        """Pool allocation behind the ``serve.kv_alloc`` chaos site: an
        active fault forces the all-or-nothing failure path (returns
        None), driving the same shedding/backpressure machinery a
        genuinely exhausted pool drives — no separate failure code.
        An exhausted pool first reclaims idle prefix-cache runs (LRU,
        never a borrowed page) before the failure path is taken —
        cached history is strictly lower-priority than live work.
        ``ns`` is the page namespace (``"draft"`` for speculative draft
        KV — the tag ``leak_check`` screens the prefix cache against)."""
        idx = self._kv_allocs
        self._kv_allocs += 1
        if chaos.active(chaos.SERVE_KV_ALLOC, idx) is not None:
            self._count("serve/kv_alloc_faults")
            return None
        got = self.pool.alloc(n, ns=ns)
        if got is None and self.prefix is not None:
            freed = self.prefix.evict(need=n)
            if freed:
                self._count("serve/prefix_evictions", freed)
                # prove the ledger exact right after the sweep — before
                # the retry hands out pages no request owns yet
                if self.leak_checks:
                    self.leak_check()
                got = self.pool.alloc(n, ns=ns)
        return got

    # -- fault recovery ----------------------------------------------------
    def _send_to_retry(self, req: Request, cause: str) -> None:
        """Bounded re-admission: the request keeps its pages and its
        generated prefix and re-enters through the queue FRONT; past
        ``max_retries`` it is shed as ``retries_exhausted`` instead of
        looping forever on a persistent fault."""
        if req.retries >= self.max_retries:
            self._shed_request(req, SHED_RETRIES_EXHAUSTED)
            return
        req.retries += 1
        req.status = RETRYING
        req.blocked_since = None
        self._count("serve/retries")
        if self.spans is not None:
            self.spans.request_event(
                req.rid, RETRYING, self.clock(),
                cause=cause, attempt=req.retries,
            )
        self.queue.appendleft(req)
        if self.leak_checks:
            self.leak_check()

    def _on_engine_fault(self, error: BaseException) -> None:
        """Supervise an engine decode fault with an escalating policy:

        - every running request moves to ``retrying`` (pages + prefix
          retained) and re-enters the batch on the very next
          iteration, riding the INCUMBENT compiled program — a
          transient fault does not corrupt an executable, and pausing
          the whole batch for a recompile would turn one fault into a
          latency cliff for every queued request;
        - a supervised AOT rebuild (re-verified replacement program)
          is scheduled and runs at the next idle point (queue and
          slots empty, or :meth:`drain`) — off the traffic path, where
          the recompile cannot contend with live prefill/decode;
        - a SECOND fault arriving before the deferred rebuild ran
          escalates: the optimistic read was wrong, the program is
          suspect, and the rebuild runs synchronously NOW (the honest
          pause).  Past ``rebuild_limit`` the fault is re-raised — a
          persistently crashing engine must not loop silently."""
        self._count("serve/engine_faults")
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            self.slots[i] = None
            self._send_to_retry(req, f"engine:{type(error).__name__}")
        if self._rebuilds_started >= self.rebuild_limit:
            raise RuntimeError(
                f"engine fault after {self._rebuilds_started} supervised "
                f"rebuilds (rebuild_limit={self.rebuild_limit})"
            ) from error
        if self._rebuild_pending:
            self._run_rebuild()  # repeat fault: rebuild before retrying
        else:
            self._rebuild_pending = True

    def _run_rebuild(self) -> None:
        self._land_flight()
        self._rebuild_pending = False
        self._rebuilds_started += 1
        self._count("serve/engine_rebuilds")
        try:
            self.engine.rebuild()
        except BaseException as e:
            raise RuntimeError("supervised engine rebuild failed") from e

    def flush_rebuild(self) -> bool:
        """Run a deferred engine rebuild now if one is owed (idle
        point / rolling restart); returns True when a rebuild ran."""
        if not self._rebuild_pending:
            return False
        self._run_rebuild()
        return True

    # -- admission --------------------------------------------------------
    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def _overloaded(self) -> bool:
        """Degradation rung 2's trigger: pool pressure past
        ``clamp_occupancy`` or a bounded queue past
        ``clamp_queue_depth``."""
        if self.pool.occupancy() >= self.clamp_occupancy:
            return True
        return (
            self.clamp_queue_depth is not None
            and len(self.queue) >= self.clamp_queue_depth
        )

    def _readmit(self, req: Request, slot: int) -> bool:
        """Re-admit a retrying request that already has its first
        token: pages and prefix were retained, so it drops straight
        back into a decode slot and resumes from where it left off —
        no re-prefill, no TTFT mutation."""
        now = self.clock()
        req.status = RUNNING
        req.blocked_since = None
        self.slots[slot] = req
        self._count("serve/readmitted")
        if self.spans is not None:
            self.spans.request_event(
                req.rid, "decode", now,
                resumed=True, attempt=req.retries,
            )
        return True

    def _admit_one(self) -> bool:
        """Try to move the queue head into a free slot (prefill now,
        or straight back to decode for a retrying request).  Returns
        True when a request was admitted or shed (progress)."""
        if not self.queue:
            return False
        slot = self._free_slot()
        if slot is None:
            return False
        # one serve/admit phase per admission that runs a prefill; an
        # attempt that ran none (the head waits for pages, re-admits
        # past its first token, sheds, or parks for chunked prefill)
        # leaves no phase
        with self._phase("serve/admit") as ph:
            calls = self.engine.prefill_calls
            progressed = self._admit_head(slot, ph)
            if self.engine.prefill_calls == calls:
                ph.drop()
        return progressed

    def _admit_head(self, slot: int, ph) -> bool:
        """:meth:`_admit_one` past the free-slot check, inside its
        ``serve/admit`` phase ``ph``."""
        # chaos: the serve.admission site — a transient admission-path
        # fault leaves the head queued (retried next iteration), never
        # kills the process
        idx = self._admissions
        self._admissions += 1
        try:
            chaos.maybe_fail(chaos.SERVE_ADMISSION, idx)
        except chaos.InjectedFault:
            self._count("serve/admission_faults")
            return False
        req = self.queue[0]
        if self.draining and req.status != RETRYING:
            # drain admits nothing new; in-flight (retrying) work may
            # still re-enter to finish.  With a handoff target the
            # never-admitted head re-routes instead of shedding.
            self.queue.popleft()
            if self._drain_handoff is not None and self._reroute_request(
                req, self._drain_handoff
            ):
                self._drain_rerouted += 1
            else:
                self._shed_request(req, SHED_DRAINING)
            return True
        resumed = req.status == RETRYING and req.first_token_at is not None
        if resumed and not self._stateful:
            self.queue.popleft()
            return self._readmit(req, slot)
        # what the prefill runs over: the prompt — or, re-admitting a
        # retried request of a model with recurrent layers, the prompt and
        # the generated prefix already fed (the last token is fed by the
        # next decode step, as it would have been)
        text = req.prompt + req.tokens[:-1] if resumed else req.prompt
        if len(text) > (
            self.serve.buckets()[-1] if resumed else self.serve.max_context
        ):
            self.queue.popleft()
            self._shed_request(req, SHED_OVERSIZE)
            return True
        need = self.pool.pages_for(len(text))
        if (
            self.prefix is not None
            and not req.cache_probed
            and req.first_token_at is None
        ):
            # ONE cache probe per request: match + borrow pin the hit
            # run (refcount+1 per page) BEFORE any allocation, so the
            # LRU eviction the allocation below may trigger can never
            # reclaim the pages this request is about to ride.  The
            # borrowed pages sit on ``req.pages`` from here on — the
            # ownership ledger covers them whether the request admits
            # now, waits pool-blocked in the queue, retries, or sheds.
            req.cache_probed = True
            hit_pages, hit_tokens = self.prefix.match(req.prompt)
            if hit_tokens:
                self.prefix.borrow(hit_pages)
                req.pages = list(hit_pages)
                req.cache_hit_pages = len(hit_pages)
                req.cache_hit_tokens = hit_tokens
                self._count("serve/prefix_hits")
                self._count("serve/prefix_hit_tokens", hit_tokens)
            else:
                self._count("serve/prefix_misses")
        if len(req.pages) < need:
            grown = self._alloc(need - len(req.pages))
            pages = None if grown is None else req.pages + grown
            if pages is None:
                # pool exhausted: shed only once the TTFT budget is
                # already blown — before that the request just waits
                if (
                    req.slo_ttft_ms is not None
                    and 1e3 * (self.clock() - req.submitted_at)
                    > req.slo_ttft_ms
                ):
                    self.queue.popleft()
                    self._shed_request(req, SHED_DEADLINE)
                    return True
                return False
        else:
            pages = req.pages  # retained across a prefill retry
        # the ledger owns the target pages from here on — set BEFORE the
        # draft allocation below so a draft-side wait or shed can never
        # strand freshly-allocated target pages outside the ledger
        req.pages = pages
        if self.engine.spec is not None and req.spec_ok:
            # speculative decoding: the draft model mirrors the target's
            # page span in its own "draft" namespace.  All-or-nothing,
            # same wait/shed semantics as the target allocation — a
            # request never admits with a half-provisioned draft cache.
            dneed = need - len(req.draft_pages)
            if dneed > 0:
                dgot = self._alloc(dneed, ns="draft")
                if dgot is None:
                    if (
                        req.slo_ttft_ms is not None
                        and 1e3 * (self.clock() - req.submitted_at)
                        > req.slo_ttft_ms
                    ):
                        self.queue.popleft()
                        self._shed_request(req, SHED_DEADLINE)
                        return True
                    return False
                req.draft_pages.extend(dgot)
        # degradation rung 2 — clamp the token budget while overloaded:
        # admit MORE requests shallower instead of fewer deeper
        if (
            self.clamp_max_new_tokens is not None
            and req.max_new_tokens > self.clamp_max_new_tokens
            and self._overloaded()
        ):
            req.clamped_from = req.max_new_tokens
            req.max_new_tokens = self.clamp_max_new_tokens
            self._count("serve/clamped")
            if self.spans is not None:
                self.spans.instant(
                    "req/clamped", self.clock(), track="serve/requests",
                    lane=req.rid, max_new_tokens=req.max_new_tokens,
                    clamped_from=req.clamped_from,
                )
        self.queue.popleft()
        now = self.clock()
        self._close_blocked(req, now)
        if not resumed:
            req.admitted_at = now
        if self.spans is not None:
            # a chunked prompt's first chunk's bucket (it may be longer
            # than the largest)
            self.spans.request_event(
                req.rid, "prefill", now,
                bucket=self.engine.bucket_for(
                    min(len(text), self.prefill_chunk_tokens or len(text))),
                prompt_tokens=len(text), pages=len(pages),
                **({"cached_tokens": req.cache_hit_tokens}
                   if req.cache_hit_tokens else {}),
                **({"attempt": req.retries} if req.retries else {}),
            )
        if self.prefix is not None or self.prefill_chunk_tokens is not None:
            # prefix-cache / chunked mode: the slot is taken NOW (pages
            # and position pinned) but the prefill itself advances one
            # page-multiple chunk per step, interleaved between decode
            # iterations — a long cold prompt no longer stalls running
            # streams, and a cache hit re-runs only its final chunk
            return self._start_chunked_prefill(req, slot)
        ph.set(rid=req.rid, bucket=self.engine.bucket_for(len(text)),
               prompt_tokens=len(text))
        try:
            _, first = self.engine.prefill(
                text, pages[:need], temperature=req.temperature,
                **({"slot": slot} if self._stateful else {}),
                **({"lazy": True} if self._lazy_prefill else {}),
            )
        except Exception as e:
            # a crashed prefill is transient by default: the request
            # keeps its pages and re-enters through bounded retry (the
            # pages carry no trusted content yet — the retry prefills
            # them again)
            self._count("serve/engine_faults")
            self._send_to_retry(req, f"prefill:{type(e).__name__}")
            return True
        if self._lazy_prefill:
            # the slot is taken; the token is read after the admit loop
            self.slots[slot] = req
            self._dispatched.append((req, slot, first, resumed))
            return True
        return self._prefilled(req, slot, first, resumed)

    def _prefilled(self, req: Request, slot: int, first: int,
                   resumed: bool) -> bool:
        """A prefill's outcome, once its first token is on the host."""
        if not self.engine.last_prefill_finite:
            # poisoned at the first token: quarantine the request, not
            # the process — its logits are not evidence of anything
            self._shed_request(req, SHED_POISONED)
            return True
        if self._routed:
            self._fold_moe(self.engine.last_moe_counts)
        if self._ssm_slot_bytes:
            # the prompt's state-space state replaced the slot's
            self._count("serve/ssm/slots_written")
        if resumed:
            # the slot holds the sequence's state again; the token this
            # prefill sampled is the one already on the stream
            self._count("serve/prefills")
            return self._readmit(req, slot)
        return self._finish_prefill(req, slot, first)

    def _resolve_prefills(self) -> None:
        """Read the first tokens of the prefills this step dispatched
        lazily, in order (one wait for the device covers them all)."""
        for req, slot, pending, resumed in self._dispatched:
            self.slots[slot] = None
            try:
                first = self.engine.resolve_prefill(pending)
            except Exception as e:
                self._count("serve/engine_faults")
                self._send_to_retry(req, f"prefill:{type(e).__name__}")
                continue
            self._prefilled(req, slot, first, resumed)
        self._dispatched.clear()

    def _start_chunked_prefill(self, req: Request, slot: int) -> bool:
        """Enter the ``prefilling`` phase: position the prefill cursor
        past the cache hit (floored to the chunk grain so a hit re-runs
        the exact same FINAL chunk the cold run executed — that is what
        makes the hit's first token bit-identical under a fixed
        ``prefill_chunk_tokens``) and park the request in its slot.
        :meth:`_advance_prefills` runs one chunk per step from here."""
        n = len(req.prompt)
        grain = self.prefill_chunk_tokens or self.serve.page_size
        # never skip the last position: its logits make the first token
        req.prefill_pos = (min(req.cache_hit_tokens, n - 1) // grain) * grain
        if self.prefix is not None:
            req.prefill_started_at = self.clock()
        req.status = PREFILLING
        self.slots[slot] = req
        return True

    def _advance_prefill(self, req: Request, slot: int) -> None:
        """Run ONE prefill chunk for a ``prefilling`` slot.  The chunk
        starts page-aligned (admission floors the cursor, chunks are
        page multiples), so chunk-local KV blocks map 1:1 onto the
        request's absolute pages; blocks that land on borrowed cache
        pages are redirected to the null page — a hit NEVER rewrites a
        page another request may be reading."""
        n = len(req.prompt)
        ps = self.serve.page_size
        start = req.prefill_pos
        end = min(start + (self.prefill_chunk_tokens or n), n)
        first_page = start // ps
        chunk_pages = [
            NULL_PAGE if pi < req.cache_hit_pages else req.pages[pi]
            for pi in range(first_page, (end - 1) // ps + 1)
        ]
        try:
            _, first = self.engine.chunk_prefill(
                req.prompt[start:end], start, req.pages, chunk_pages,
                temperature=req.temperature,
            )
        except Exception as e:
            self._count("serve/engine_faults")
            self.slots[slot] = None
            self._send_to_retry(req, f"prefill:{type(e).__name__}")
            return
        if not self.engine.last_prefill_finite:
            self.slots[slot] = None
            self._shed_request(req, SHED_POISONED)
            return
        self._count("serve/prefill_chunks")
        if self._latent:
            # the chunk's queries read every row up to its end
            self._count("serve/latent_rows_read",
                        float(self._latent_layers * end))
        if self._routed:
            self._fold_moe(self.engine.last_moe_counts)
        req.prefill_pos = end
        if end == n:
            self._finish_prefill(req, slot, first)

    def _advance_prefills(self) -> None:
        work = [(i, req) for i, req in enumerate(self.slots)
                if req is not None and req.status == PREFILLING]
        if not work:
            return
        with self._phase("serve/chunks"):
            for i, req in work:
                self._advance_prefill(req, i)

    def _finish_prefill(self, req: Request, slot: int, first: int) -> bool:
        """First-token bookkeeping shared by the monolithic and chunked
        prefill paths; in cache mode also COMMITS the prompt's pages to
        the prefix cache so every later request sharing the prefix pays
        only its tail chunk."""
        req.ctx_len = len(req.prompt)
        req.tokens.append(first)
        req.first_token_at = self.clock()
        req.status = RUNNING
        self.slots[slot] = req
        self._tokens_out += 1
        self._count("serve/admitted")
        self._count("serve/prefills")
        self._count("serve/tokens_out")
        self._gauge("serve/ttft_ms", req.ttft_ms)
        self.ttft_hist.observe(req.ttft_ms)
        if self.prefix is not None:
            added = self.prefix.commit(
                req.prompt,
                req.pages[: self.pool.pages_for(len(req.prompt))],
            )
            if added:
                self._count("serve/prefix_commits", added)
        if self.engine.spec is not None and req.spec_ok:
            # warm the draft KV over the prompt so proposals start from
            # the same context the target sees.  A crashed draft prefill
            # DEMOTES the request to plain decode — the draft is an
            # accelerator, never a correctness dependency.
            try:
                self.engine.draft_prefill(req.prompt, req.draft_pages)
            except Exception:
                self._count("serve/draft_faults")
                req.spec_ok = False
        if self._finished(req):
            self.slots[slot] = None
            self._retire(req, DONE)
            self._count("serve/completed")
        elif self.spans is not None:
            # entering the decode phase: the closing event carries the
            # full TTFT attribution onto the req/prefill span
            self.spans.request_event(
                req.rid, "decode", req.first_token_at,
                **(req.ttft_components() or {}),
            )
        return True

    def _finished(self, req: Request) -> bool:
        if req.eos_token is not None and req.tokens and (
            req.tokens[-1] == req.eos_token
        ):
            return True
        return self._ends(req, 0)

    # -- decode -----------------------------------------------------------
    def _ensure_target_page(self, req: Request, idx: int) -> bool:
        """Make target page ``idx`` writable: allocate it if the span
        has not reached it yet, and copy-on-write fork it first when it
        is SHARED (a borrowed cache run's tail, or this request's own
        pages after it committed them) — a fresh page gets a device
        copy of the shared one, the shared reference is dropped, and
        appends proceed on the private copy; co-readers never see the
        write."""
        if idx < len(req.pages):
            page = req.pages[idx]
            if self.pool.refcount(page) > 1:
                got = self._alloc(1)
                if got is None:
                    return False
                self.engine.fork_page(page, got[0])
                self.pool.free([page])
                req.pages[idx] = got[0]
                if req.cache_hit_pages > idx:
                    req.cache_hit_pages = idx
                self._count("serve/prefix_forks")
            return True
        while len(req.pages) <= idx:
            got = self._alloc(1)
            if got is None:
                return False
            req.pages.extend(got)
        return True

    def _ensure_growth_page(self, req: Request, ahead: int = 1,
                            held: int = 0) -> bool:
        """The next ``ahead`` appends land at positions ``ctx_len + held
        .. ctx_len + held + ahead - 1`` (one, but for a decode block;
        ``held``: the tokens a call in flight holds for ``req``, their
        positions already provisioned); allocate (or COW-fork) their
        pages if needed."""
        ps = self.serve.page_size
        at = req.ctx_len + held
        return all(
            self._ensure_target_page(req, idx)
            for idx in range(at // ps, (at + ahead - 1) // ps + 1)
        )

    def _block_steps(self, req: Request, held: int = 0) -> int:
        """Iterations ``req`` may ride of the next decode program: the
        block, cut at its token budget and at the context's end (past the
        ``held`` tokens a call in flight holds for it)."""
        return max(1, min(
            self.serve.decode_block,
            req.max_new_tokens - len(req.tokens) - held,
            self.serve.max_context - req.ctx_len - held,
        ))

    def _ends(self, req: Request, n: int) -> bool:
        """Whether ``n`` more tokens end ``req`` by its token budget or the
        context's end — the NEXT fed token would not fit (an EOS only the
        tokens can tell: :meth:`_finished`)."""
        return (len(req.tokens) + n >= req.max_new_tokens
                or req.ctx_len + n + 1 > self.serve.max_context)

    def _ensure_spec_span(self, req: Request) -> bool:
        """Provision the whole speculative window BEFORE the round: a
        spec round may write target KV at positions ``ctx_len`` through
        ``ctx_len + k``, so every page that span touches must be
        private and writable NOW.  This is the real COW obligation of
        speculative decoding — rejected positions are overwritten in
        place, which is only safe because no shared page is ever
        written.  The draft span grows in the ``draft`` namespace
        alongside.  Returns False on allocation failure (the caller
        demotes the slot to plain decode for this round)."""
        ps = self.serve.page_size
        k = self.engine.spec.k
        for idx in range(req.ctx_len // ps, (req.ctx_len + k) // ps + 1):
            if idx >= self.serve.max_pages_per_seq:
                return False
            if not self._ensure_target_page(req, idx):
                return False
            while len(req.draft_pages) <= idx:
                got = self._alloc(1, ns="draft")
                if got is None:
                    return False
                req.draft_pages.extend(got)
        return True

    def _decode_once(self) -> None:
        """One decode pass over the running batch: speculative rounds
        for spec-eligible slots (unless the degradation ladder tripped
        the acceptance fallback), plain single-token decode for the
        rest."""
        if self.engine.spec is not None and not self._spec_fallback:
            self._spec_decode_once()
        else:
            self._plain_decode_once(None)

    def _can_overlap(self, ending: bool = False) -> bool:
        """The rule for one decode call in flight (docs/serving.md "Host
        phases"): the next plain decode call may be dispatched before this
        one is read only when no request waits in the queue, no chunked
        prefill is in progress, the pass is no speculative round, and no
        rider ends with this call by its budget or the context's end
        (``ending``) — a slot about to be refilled wants its read now."""
        return not (
            ending
            or self.queue
            or (self.engine.spec is not None and not self._spec_fallback)
            or any(r is not None and r.status == PREFILLING
                   for r in self.slots)
        )

    def _plain_decode_once(self, only: Optional[set]) -> None:
        """One plain (single-token) decode iteration.  ``only`` limits
        the pass to the given slot indices (the non-speculative side of
        a mixed batch); ``None`` rides every running slot.

        With a call in flight (:meth:`_can_overlap` held when it was
        dispatched) the riders' host view is one call behind: this call
        feeds each of them the token that call holds for it, still on the
        device, at the position past it, and reads that call only after
        its own launch.  A step reads one call: this one is left in
        flight, if only until the next step starts (``land``)."""
        # (a mixed speculative pass, ``only``, never finds one: the rule
        # holds off speculation, and the step read it first)
        flight = self._flight
        held = {} if flight is None else {
            i: int(flight.steps[i]) for i, r in flight.riders
            if self.slots[i] is r
        }
        with self._phase("serve/batch"):
            b = len(self.slots)
            tokens = np.zeros((b,), np.int32)
            lengths = np.zeros((b,), np.int32)
            temps = np.zeros((b,), np.float32)
            streams = np.zeros((b,), np.uint32)
            gens = np.zeros((b,), np.int32)
            carry = np.zeros((b,), np.int32)
            # iterations each slot rides: one, but for a decode block
            block = self.serve.decode_block
            steps = np.zeros((b,), np.int32)
            tables = np.full(
                (b, self.serve.max_pages_per_seq), NULL_PAGE, np.int32
            )
            ending = aborted = False
            for i, req in enumerate(self.slots):
                if req is None or req.status == PREFILLING:
                    # a prefilling slot rides no decode iteration — its
                    # context advances one chunk per step instead
                    continue
                if only is not None and i not in only:
                    continue
                h = held.get(i, 0)
                ahead = self._block_steps(req, h) if block > 1 else 1
                if not self._ensure_growth_page(req, ahead, h):
                    if flight is not None:
                        aborted = True
                        break
                    # pool exhausted mid-decode: shed the youngest running
                    # request (least sunk cost) and retry this one
                    victims = sorted(
                        self.running, key=lambda r: r.submitted_at or 0.0
                    )
                    victim = victims[-1]
                    v_slot = self.slots.index(victim)
                    self.slots[v_slot] = None
                    self._shed_request(victim, SHED_GROWTH_VICTIM)
                    # the victim's row may already be staged for this
                    # iteration — clear it so the decode never touches its
                    # (now freed) pages
                    tokens[v_slot] = 0
                    lengths[v_slot] = 0
                    steps[v_slot] = 0
                    tables[v_slot] = NULL_PAGE
                    if victim is req or not self._ensure_growth_page(
                        req, ahead
                    ):
                        if self.slots[i] is req:
                            self.slots[i] = None
                            self._shed_request(req, SHED_POOL_EXHAUSTED)
                        continue
                # a held token is fed from the device (carry); the host's
                # view of the slot moves past it
                tokens[i] = 0 if h else req.tokens[-1]
                carry[i] = 1 if h else 0
                lengths[i] = req.ctx_len + h + 1  # context incl. the fed token
                temps[i] = req.temperature
                streams[i] = self._stream(req)
                gens[i] = len(req.tokens) + h - 1
                steps[i] = ahead
                tables[i] = self._page_table_row(req)
                ending |= self._ends(req, h + ahead)
        if flight is not None and (aborted or not lengths.any()):
            # no page to grow into past the call in flight, or no rider
            # left to feed: read it, and the next step stages afresh (a
            # growth victim is chosen with that call's tokens on the host)
            self._land_flight()
            return
        if not lengths.any():
            return
        riders = [(int(i), self.slots[i]) for i in np.flatnonzero(lengths)]
        # keep this call in flight past the step, unread, while the rule
        # holds for it; else it is read before the step returns — or, with
        # a call in flight, when the next step starts
        keep = only is None and self._can_overlap(ending)
        lazy = keep or flight is not None
        self._decode_lengths = lengths
        self._riders += len(riders)
        if self._latent:
            # iteration j of a slot reads its context at lengths + j
            its = steps.astype(np.int64) if block > 1 else (
                lengths > 0).astype(np.int64)
            self._count("serve/latent_rows_read", float(
                self._latent_layers * int(
                    (its * lengths + its * (its - 1) // 2).sum())))
        t0 = self.clock()
        try:
            _, out = self.engine.decode(
                tokens, lengths, tables, temps,
                streams=streams, gens=gens,
                **({"steps": steps} if block > 1 else {}),
                **({} if flight is None else {
                    "carry": carry, "read": (flight.out, flight.index)}),
                **({"lazy": True} if lazy else {}),
            )
        except Exception as e:
            # a crashed decode step produced nothing host-side: the call in
            # flight is read first, then every rider keeps its prefix and
            # pages and re-enters through bounded retry while the engine
            # rebuilds under supervision (a read that raised was the fault)
            if self._land_flight():
                self._on_engine_fault(e)
            return
        call = _DecodeCall(out, self.engine.decode_iters, riders, steps, t0,
                           land=not keep)
        if flight is not None:
            self._count("serve/decode_overlapped")
            if not self._land_flight():
                # its riders went to retry with the fault: read at the next
                # step's start, its tokens are discarded
                call.land = True
        if lazy:
            self._flight = call
        else:
            # read inside the call, as it always was
            self._retire_call(call, out, self.engine.last_decode_finite,
                              self.engine.last_moe_counts)

    def _land_flight(self) -> bool:
        """Read the decode call in flight, if any, and retire its tokens;
        a read that raises is an engine fault (False)."""
        call, self._flight = self._flight, None
        if call is None:
            return True
        try:
            out, finite, counts = self.engine.read_decode(
                call.out, call.index)
        except Exception as e:
            self._on_engine_fault(e)
            return False
        self._retire_call(call, out, finite, counts)
        return True

    def _retire_call(self, call: "_DecodeCall", next_tokens, finite,
                     counts) -> None:
        """A plain decode call's tokens, on the host, onto the riders that
        still hold their slots: an EOS or a quarantine the call before it
        revealed, or a growth shed, took a rider out while the call was in
        flight, and its token is discarded."""
        block, b = self.serve.decode_block, len(self.slots)
        # (iterations, slots): one row, but for a decode block
        next_tokens = np.asarray(next_tokens).reshape(block, b)
        with self._phase("serve/retire"):
            # a decode timeout runs from the call's dispatch to its read
            elapsed_ms = 1e3 * (self.clock() - call.t0)
            self._count("serve/decode_steps")
            if self._routed:
                self._fold_moe(counts)
            for i, req in call.riders:
                if self.slots[i] is not req:
                    continue
                if finite is not None and not bool(finite[i]):
                    # poisoned-request quarantine: a non-finite logits row
                    # evicts ONLY the offending slot — its token is
                    # garbage, its KV is suspect — while the rest of the
                    # batch keeps its tokens from this very iteration
                    self.slots[i] = None
                    self._shed_request(req, SHED_POISONED)
                    continue
                timeout_ms = (
                    req.decode_timeout_ms
                    if req.decode_timeout_ms is not None
                    else self.decode_timeout_ms
                )
                if timeout_ms is not None and elapsed_ms > timeout_ms:
                    # a hung iteration (per-request budget): discard this
                    # request's token from the suspect step — the KV append
                    # is positionally idempotent, so the retried decode
                    # rewrites the same slot — and re-admit with the prefix
                    # preserved
                    self._count("serve/decode_timeouts")
                    self.slots[i] = None
                    self._send_to_retry(
                        req, f"decode_timeout:{elapsed_ms:.0f}ms"
                    )
                    continue
                # engine-numbered iteration id: the correlation key linking
                # a request's decode span to the engine batch iterations it
                # rode
                if req.first_decode_iter is None:
                    req.first_decode_iter = call.index
                req.last_decode_iter = call.index
                for tok in next_tokens[: call.steps[i], i]:
                    req.ctx_len += 1
                    req.tokens.append(int(tok))
                    self._tokens_out += 1
                    self._count("serve/tokens_out")
                    if self._finished(req):
                        # (an EOS inside a block: the iterations after it
                        # advanced a slot that is now free)
                        self.slots[i] = None
                        self._retire(req, DONE)
                        self._count("serve/completed")
                        break

    # -- speculative decoding ---------------------------------------------
    def _stream(self, req: Request) -> int:
        """Stable per-request sampling-stream id.  The engine folds it
        into its base key and each emission folds its position index, so
        the sampled token at (request, position) is a pure function of
        request identity — a rollback replay, a spec bonus draw, and
        plain decode all reproduce the exact same stream."""
        if req.stream_seed is not None:
            return req.stream_seed
        return zlib.crc32(str(req.rid).encode()) & 0x7FFFFFFF

    def _spec_decode_once(self) -> None:
        """Partition the running batch: slots with a healthy draft ride
        a speculative round (propose k, verify once, roll back the
        rejected tail); everything else — draft-demoted requests, slots
        whose window cannot be provisioned, streams near the context
        ceiling — rides plain decode.  Mixed batches are the steady
        state, not an edge case."""
        with self._phase("serve/batch"):
            k = self.engine.spec.k
            spec_idx: List[int] = []
            plain_idx: List[int] = []
            for i, req in enumerate(self.slots):
                if req is None or req.status == PREFILLING:
                    continue
                if (
                    req.spec_ok
                    and req.draft_pages
                    and req.ctx_len + 1 + k <= self.serve.max_context
                ):
                    spec_idx.append(i)
                else:
                    plain_idx.append(i)
            for i in list(spec_idx):
                if not self._ensure_spec_span(self.slots[i]):
                    # cannot provision the whole window: demote for THIS
                    # round only — the pool may free up by the next one
                    spec_idx.remove(i)
                    plain_idx.append(i)
        if spec_idx:
            self._spec_round(spec_idx, k)
        if plain_idx:
            self._plain_decode_once(set(plain_idx))

    def _spec_round(self, idx: List[int], k: int) -> None:
        """One propose → verify → accept/rollback round for the given
        slots.  The verify step scans the SAME per-token program body
        plain decode runs, so every accepted token is bit-identical to
        the token plain decode would have produced; the rejected tail's
        KV (target and draft) is truncated afterwards so no stale entry
        outlives the round."""
        with self._phase("serve/batch"):
            b = len(self.slots)
            tokens = np.zeros((b,), np.int32)
            lengths = np.zeros((b,), np.int32)
            temps = np.zeros((b,), np.float32)
            streams = np.zeros((b,), np.uint32)
            gens = np.zeros((b,), np.int32)
            tables = np.full(
                (b, self.serve.max_pages_per_seq), NULL_PAGE, np.int32
            )
            dtables = np.full(
                (b, self.serve.max_pages_per_seq), NULL_PAGE, np.int32
            )
            for i in idx:
                req = self.slots[i]
                tokens[i] = req.tokens[-1]
                lengths[i] = req.ctx_len + 1  # context incl. the fed token
                temps[i] = req.temperature
                streams[i] = self._stream(req)
                gens[i] = len(req.tokens) - 1
                tables[i] = self._page_table_row(req)
                dtables[i, : len(req.draft_pages)] = req.draft_pages
        t0 = self.clock()
        try:
            out, acc, finite = self.engine.spec_step(
                tokens, lengths, tables, dtables, temps, streams, gens
            )
        except chaos.InjectedFault as e:
            if getattr(e, "site", None) == chaos.SERVE_DRAFT:
                # a faulted draft never corrupts a stream: the round
                # was abandoned BEFORE any verify-side KV write, and
                # every rider falls back to plain decode this iteration
                self._count("serve/draft_faults")
                self._plain_decode_once(set(idx))
                return
            self._on_engine_fault(e)
            return
        except Exception as e:
            self._on_engine_fault(e)
            return
        with self._phase("serve/retire"):
            elapsed_ms = 1e3 * (self.clock() - t0)
            self._riders += len(idx)
            self._count("serve/decode_steps")
            self._count("serve/spec_rounds")
            it = getattr(self.engine, "decode_iters", None)
            rb_starts = np.zeros((b,), np.int32)
            rb_counts = np.zeros((b,), np.int32)
            drafted = accepted = emitted = slot_steps = 0
            for i in idx:
                req = self.slots[i]
                slot_steps += 1
                if finite is not None and not bool(finite[i]):
                    # poisoned VERIFY output — the target's own logits are
                    # garbage, same quarantine as a poisoned plain step
                    self.slots[i] = None
                    self._shed_request(req, SHED_POISONED)
                    continue
                timeout_ms = (
                    req.decode_timeout_ms
                    if req.decode_timeout_ms is not None
                    else self.decode_timeout_ms
                )
                if timeout_ms is not None and elapsed_ms > timeout_ms:
                    self._count("serve/decode_timeouts")
                    self.slots[i] = None
                    self._send_to_retry(
                        req, f"decode_timeout:{elapsed_ms:.0f}ms"
                    )
                    continue
                if it is not None:
                    if req.first_decode_iter is None:
                        req.first_decode_iter = it
                    req.last_decode_iter = it
                a = int(acc[i])
                drafted += k
                accepted += a
                start_ctx = req.ctx_len
                n_emit = 0
                for t in out[i, : a + 1]:
                    req.ctx_len += 1
                    req.tokens.append(int(t))
                    n_emit += 1
                    self._tokens_out += 1
                    if self._finished(req):
                        break
                emitted += n_emit
                self._count("serve/tokens_out", n_emit)
                if self._finished(req):
                    self.slots[i] = None
                    self._retire(req, DONE)
                    self._count("serve/completed")
                else:
                    # the round wrote target KV at [start_ctx, start_ctx+k];
                    # everything past the new context is a rejected draft's
                    # residue and is truncated below (slots that retired or
                    # shed keep counts 0 — the rollback masks them to the
                    # null page)
                    stale = start_ctx + k + 1 - req.ctx_len
                    if stale > 0:
                        rb_starts[i] = req.ctx_len
                        rb_counts[i] = stale
            if rb_counts.any():
                self.engine.rollback(rb_starts, rb_counts, tables)
                self.engine.draft_rollback(rb_starts, rb_counts, dtables)
                self._count(
                    "serve/spec_rollbacks", int((rb_counts > 0).sum())
                )
            self._count("serve/spec_drafted", drafted)
            self._count("serve/spec_accepted", accepted)
            if drafted > accepted:
                self._count("serve/spec_rejected", drafted - accepted)
            if self._spec_window is not None:
                self._spec_window.append(
                    (drafted, accepted, emitted, slot_steps)
                )
                if len(self._spec_window) == self._spec_window.maxlen:
                    tot_d = sum(w[0] for w in self._spec_window)
                    tot_a = sum(w[1] for w in self._spec_window)
                    if tot_d and (
                        tot_a / tot_d < self.engine.spec.min_accept_rate
                    ):
                        # degradation ladder: speculation is costing more
                        # than it saves — fall back to plain decode until
                        # an operator resume() re-arms it
                        self._spec_fallback = True
                        self._count("serve/spec_fallbacks")

    # -- metrics ----------------------------------------------------------
    def _phase(self, name: str, **args):
        """A host phase (docs/serving.md "Host phases") on the attached
        recorder, else on the process ring every deployment keeps."""
        return host_recorder(self.spans).phase(name, **args)

    def _count(self, name: str, n: float = 1.0) -> None:
        if self._mstate is not None:
            self.registry.host_update(self._mstate, {name: n})

    def _gauge(self, name: str, value) -> None:
        if self._mstate is not None and value is not None:
            self.registry.host_update(self._mstate, {name: value})

    def _publish_attribution(self) -> None:
        """Percentile gauges over the recent completion window — one
        batched registry update, recomputed only when new completions
        arrived since the last publish."""
        if (
            self._mstate is None
            or not self._comps
            or len(self.completed) == self._published_done
        ):
            return
        self._published_done = len(self.completed)
        attr = ttft_attribution(self._comps)
        updates: Dict[str, float] = {}
        for comp in TTFT_COMPONENTS:
            for tag, value in attr[f"{comp}_ms"].items():
                updates[f"serve/ttft_{comp}_ms_{tag}"] = value
        updates["serve/ttft_queue_wait_fraction"] = attr[
            "queue_wait_fraction"
        ]
        self.registry.host_update(self._mstate, updates)

    def _publish(self) -> None:
        with self._phase("serve/publish"):
            now = self.clock()
            self._window.append((now, self._tokens_out))
            tps = 0.0
            if len(self._window) >= 2:
                (t0, n0), (t1, n1) = self._window[0], self._window[-1]
                if t1 > t0:
                    tps = (n1 - n0) / (t1 - t0)
            self._gauge("serve/queue_depth", len(self.queue))
            self._gauge("serve/batch_fill", self.batch_fill())
            self._gauge("serve/page_occupancy", self.pool.occupancy())
            self._gauge("serve/tokens_per_s", tps)
            if self._decode_lengths is not None:
                if self._ssm_slot_bytes:
                    # every rider's state-space state, read and written
                    self._gauge(
                        "serve/ssm/state_bytes_per_iter",
                        2.0 * self._ssm_slot_bytes
                        * int(np.count_nonzero(self._decode_lengths)),
                    )
                # only a walk that ran: the decode program's attention
                # took the kernel (the jnp path gathers the whole table)
                took = _dispatch.last_paths().get("paged_decode_attention")
                if self._paged and took == "pallas":
                    self._gauge(
                        "serve/decode_walk_live_share",
                        walk_live_share(
                            self._decode_lengths, self.engine.cache["k"],
                            self.serve.max_pages_per_seq,
                        ),
                    )
                self._decode_lengths = None
            if self.prefix is not None:
                self._gauge(
                    "serve/prefix_cached_pages",
                    float(len(self.prefix.cached_pages())),
                )
            if self._stateful:
                held = self.slots_in_use()
                self._gauge("serve/state/slots_in_use", float(held))
                self._gauge(
                    "serve/state/bytes", float(held * self._slot_state_bytes))
            if self._latent:
                self._gauge(
                    "serve/latent/pages_in_use", float(self.pool.in_use))
            if self._spec_window:
                tot_d = sum(w[0] for w in self._spec_window)
                tot_a = sum(w[1] for w in self._spec_window)
                tot_e = sum(w[2] for w in self._spec_window)
                tot_s = sum(w[3] for w in self._spec_window)
                self._gauge(
                    "serve/spec_accept_rate", tot_a / tot_d if tot_d else 0.0
                )
                self._gauge(
                    "serve/spec_tokens_per_step",
                    tot_e / tot_s if tot_s else 0.0,
                )
            self._publish_attribution()
            if self._mstate is not None:
                self.registry.observe(self._step, self._mstate)

    # -- the iteration ----------------------------------------------------
    def step(self) -> None:
        """One continuous-batching iteration: admit (prefill) into free
        slots, then one decode pass over the running batch — or, where a
        decode call in flight may not stay so, only its read."""
        with self._phase("serve/step", step=self._step) as ph:
            prefills = self.engine.prefill_calls
            tokens = self._tokens_out
            retired = len(self.completed) + len(self.shed)
            self._riders = 0
            if self._flight is not None and (
                    self._flight.land or not self._can_overlap()):
                # a rider ends with the call in flight, or the rule no
                # longer holds (a request arrived): the step reads it and
                # returns, so its tokens and its ends go out at once
                self._land_flight()
            else:
                self._admit_and_decode()
            self._step += 1
            self._publish()
            # counts measured where the work happens: the engine's
            # call counter, the decode batches, the token and retire
            # ledgers
            ph.set(
                prefills=self.engine.prefill_calls - prefills,
                riders=self._riders,
                tokens=self._tokens_out - tokens,
                retired=len(self.completed) + len(self.shed) - retired,
            )
        if self._rebuild_pending and not self.pending:
            # idle point reached in a caller-driven step() loop: run
            # the owed rebuild now, off the traffic path (run()/drain()
            # reach the same flush through their own exits)
            self.flush_rebuild()

    def _admit_and_decode(self) -> None:
        """A step's work with no call in flight to read first: admit
        (prefill) into free slots, then one decode pass."""
        # admit until slots or pages run out — each prefill slots in
        # between decode iterations by construction
        while self._admit_one():
            pass
        if self._dispatched:
            self._resolve_prefills()
        if self.queue:
            # admission gave up with requests still queued: they are
            # resource-blocked (no slot / pool cannot cover the head)
            # from here until the next admission attempt — the
            # queue_wait TTFT component.  Only pre-first-token requests
            # accrue it: a retrying request past its first token is in
            # RECOVERY wait, which must not pollute TTFT attribution
            # (the components would stop summing to the measured TTFT).
            now = self.clock()
            for r in self.queue:
                if r.first_token_at is None and r.blocked_since is None:
                    r.blocked_since = now
        if self.prefix is not None and chaos.active(
            chaos.SERVE_PREFIX_EVICT, self._step
        ) is not None:
            # forced full eviction sweep (the ``serve.prefix_evict``
            # chaos drill): every idle cached run is reclaimed at once
            # — borrowed pages MUST survive (refcount > 1 is never
            # evictable) and the ledger must stay exact, proven by the
            # leak check right here
            self._count("serve/prefix_evict_faults")
            freed = self.prefix.evict()
            if freed:
                self._count("serve/prefix_evictions", freed)
            if self.leak_checks:
                self.leak_check()
        self._advance_prefills()
        self._decode_once()

    def run(self, max_steps: int = 10_000) -> None:
        """Drain: step until every submitted request completed or shed.
        An engine rebuild deferred during the run executes at the idle
        exit — off the traffic path."""
        for _ in range(max_steps):
            if not self.pending:
                self.flush_rebuild()
                return
            self.step()
        raise RuntimeError(
            f"scheduler did not drain within {max_steps} iterations"
        )

    def drain(self, max_steps: int = 10_000, *,
              handoff=None) -> Dict[str, object]:
        """Graceful drain for a rolling restart (docs/serving.md
        "Failure semantics"): stop admitting new work, let running
        decodes AND in-flight retrying re-admissions finish, then
        report the drained state with the page pool provably empty.
        The scheduler stays drained: subsequent submits are rejected
        until :meth:`resume` is called.

        ``handoff`` — a ``callable(Request) -> bool`` (e.g. a fleet
        router's re-route hook): each never-admitted queue entry is
        OFFERED to it instead of being shed; on acceptance the request
        leaves this replica as ``shed(rerouted)`` on the ledger and
        continues elsewhere with its prompt and shared retry budget
        intact.  Without a handoff (or when it refuses) the entry is
        shed loudly as ``draining`` — the client retries on another
        replica itself."""
        self.start_drain(handoff=handoff)
        for _ in range(max_steps):
            if not self.pending:
                break
            self.step()
        else:
            raise RuntimeError(
                f"drain did not complete within {max_steps} iterations"
            )
        return self.finish_drain()

    def start_drain(self, *, handoff=None) -> int:
        """Enter the draining state (phase 1 of :meth:`drain`): stop
        admitting new work, hand never-admitted queue entries to
        ``handoff`` (or shed them as ``draining``), keep in-flight
        retrying work.  Returns the re-routed count.  Split out of
        :meth:`drain` so a fleet control plane can drain a replica
        INCREMENTALLY — ticking :meth:`step` itself on a shared fleet
        clock while the other replicas keep serving — instead of
        monopolizing the loop until this replica is empty; call
        :meth:`finish_drain` once :attr:`pending` clears."""
        self.draining = True
        self._drain_handoff = handoff
        self._count("serve/drains")
        self._gauge("serve/draining", 1.0)
        # hand off (or reject) never-admitted work now; retrying
        # requests are in-flight (they hold pages and a prefix) and
        # get to finish here
        kept = [r for r in self.queue if r.status == RETRYING]
        rejected = [r for r in self.queue if r.status != RETRYING]
        self.queue = collections.deque(kept)
        rerouted = 0
        for req in rejected:
            if handoff is not None and self._reroute_request(req, handoff):
                rerouted += 1
            else:
                self._shed_request(req, SHED_DRAINING)
        self._drain_rerouted = rerouted
        return rerouted

    def finish_drain(self) -> Dict[str, object]:
        """Seal a drain (phase 3): settle any owed rebuild, re-prove
        the pool empty, and report — :meth:`drain`'s exit, also called
        directly by a fleet that drove the intervening steps itself."""
        # an incremental drain can still be re-routing through
        # _admit_one up to the last step — count those too
        self._drain_handoff = None
        self.flush_rebuild()  # settle any rebuild owed from the storm
        if self.prefix is not None:
            # a drained replica keeps no cached history: release every
            # cache-owned reference so the pool is PROVABLY empty below
            self.prefix.flush()
        self.leak_check()
        self._publish()
        return {
            "drained": True,
            "completed": len(self.completed),
            "shed": len(self.shed),
            "rerouted": self._drain_rerouted,
            "pool_in_use": self.pool.in_use,
            "engine_rebuilds": self.engine.rebuilds,
            "leak_checks_run": self.leak_checks_run,
        }

    def resume(self) -> None:
        """Leave the drained state (the rolling restart completed):
        submissions are accepted again and the ``serve/draining``
        gauge clears — a resumed replica must not keep reporting
        itself as draining."""
        self.draining = False
        self._gauge("serve/draining", 0.0)
        # re-arm speculation: a fresh deploy may carry a better draft,
        # so the acceptance fallback and its window reset here
        self._spec_fallback = False
        if self._spec_window is not None:
            self._spec_window.clear()
