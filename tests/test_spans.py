"""Span recorder + unified timeline: lifecycle invariants, TTFT
attribution, the Chrome-trace sink, and the span-accounting tooling.

ISSUE 8 acceptance surface: every admitted request ends in exactly one
terminal span, shed reasons match the scheduler's ledger counters, a
planted out-of-order event is rejected loudly, per-request TTFT
components sum to the measured TTFT by construction, and
``tools/timeline.py`` turns a scheduler run's span dump into a
Perfetto-loadable trace plus a passing accounting summary.
"""

import importlib.util
import json
import os
import sys

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.observability import (
    MetricRegistry,
    QueueWaitFractionRule,
    SpanRecorder,
    TimelineSink,
    Watchdog,
    bench_record,
    monotonic_to_epoch,
    serve_rules,
    wall_clock_anchor,
)
from apex_tpu.observability.health import HealthEvent
from apex_tpu.observability.spans import (
    REQ_DECODE,
    REQ_DONE,
    REQ_PREFILL,
    REQ_QUEUED,
    REQ_SHED,
    TRACK_ENGINE,
    TRACK_REQUESTS,
)
from apex_tpu.observability.trace import TraceScheduler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")


def _tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(TOOLS, f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _names(rec):
    counts = {}
    for e in rec.snapshot():
        counts[e["name"]] = counts.get(e["name"], 0) + 1
    return counts


# ---------------------------------------------------------------------------
# anchor
# ---------------------------------------------------------------------------


class TestAnchor:
    def test_anchor_is_captured_once(self):
        a = wall_clock_anchor()
        b = wall_clock_anchor()
        assert a == b
        assert set(a) >= {"monotonic", "epoch", "pid"}
        assert a["pid"] == os.getpid()

    def test_monotonic_to_epoch_offset(self):
        a = wall_clock_anchor()
        # the anchor's own monotonic timestamp maps to its epoch one
        assert monotonic_to_epoch(a["monotonic"]) == pytest.approx(
            a["epoch"]
        )
        assert monotonic_to_epoch(a["monotonic"] + 2.5) == pytest.approx(
            a["epoch"] + 2.5
        )


# ---------------------------------------------------------------------------
# recorder core
# ---------------------------------------------------------------------------


class TestRecorderCore:
    def test_span_and_instant_record(self):
        rec = SpanRecorder(capacity=16)
        rec.span("a", 1.0, 2.0, track="t", lane=7, foo=1)
        rec.instant("b", 3.0, track="t")
        spans = rec.snapshot()
        assert spans[0]["name"] == "a" and spans[0]["lane"] == 7
        assert spans[0]["args"] == {"foo": 1}
        assert spans[1]["name"] == "b" and spans[1]["t"] == 3.0
        assert [e["seq"] for e in spans] == [0, 1]

    def test_backwards_span_rejected(self):
        rec = SpanRecorder(capacity=16)
        with pytest.raises(ValueError, match="ends before it starts"):
            rec.span("a", 2.0, 1.0)

    def test_ring_drops_oldest_and_counts(self):
        rec = SpanRecorder(capacity=4)
        for i in range(10):
            rec.instant(f"e{i}", float(i))
        assert rec.dropped == 6
        assert [e["name"] for e in rec.snapshot()] == [
            "e6", "e7", "e8", "e9",
        ]

    def test_dump_payload(self, tmp_path):
        rec = SpanRecorder(capacity=8, run={"job": "t"})
        rec.span("a", 1.0, 2.0)
        rec.instant("nan", 1.5, value=float("nan"))
        path = rec.dump(reason="unit", path=str(tmp_path / "s.json"))
        data = json.load(open(path))
        assert data["kind"] == "apex_tpu_spans"
        assert data["version"] == 1
        assert set(data["anchor"]) >= {"monotonic", "epoch"}
        assert data["reason"] == "unit"
        assert data["run"] == {"job": "t"}
        assert data["dropped"] == 0
        assert len(data["spans"]) == 2
        # non-finite forensics survive as strings, strict JSON
        assert data["spans"][1]["args"]["value"] == "NaN"

    def test_from_env(self, monkeypatch, tmp_path):
        from apex_tpu.observability.spans import ENV_SPANS

        monkeypatch.delenv(ENV_SPANS, raising=False)
        assert SpanRecorder.from_env() is None
        monkeypatch.setenv(ENV_SPANS, "0")
        assert SpanRecorder.from_env() is None
        monkeypatch.setenv(ENV_SPANS, f"32:{tmp_path}")
        rec = SpanRecorder.from_env()
        assert rec.capacity == 32 and rec.directory == str(tmp_path)


# ---------------------------------------------------------------------------
# request lifecycle state machine
# ---------------------------------------------------------------------------


class TestRequestLifecycle:
    def test_full_chain_spans(self):
        rec = SpanRecorder(capacity=64)
        rec.request_event(5, REQ_QUEUED, 1.0, prompt_tokens=4)
        rec.request_event(5, REQ_PREFILL, 2.0, bucket=8)
        rec.request_event(5, REQ_DECODE, 3.0, ttft_ms=2000.0)
        rec.request_event(5, REQ_DONE, 4.0, tokens=3)
        names = _names(rec)
        assert names == {
            "req/queued": 1, "req/admitted": 1, "req/prefill": 1,
            "req/decode": 1, "req/done": 1,
        }
        assert rec.open_requests == {}
        spans = {e["name"]: e for e in rec.snapshot()}
        # phase spans cover [open, close] and merge open+close args
        q = spans["req/queued"]
        assert (q["t0"], q["t1"]) == (1.0, 2.0)
        assert q["args"] == {"prompt_tokens": 4, "bucket": 8}
        p = spans["req/prefill"]
        assert (p["t0"], p["t1"]) == (2.0, 3.0)
        assert p["args"]["ttft_ms"] == 2000.0
        assert spans["req/done"]["lane"] == 5

    def test_shed_from_queue(self):
        rec = SpanRecorder(capacity=64)
        rec.request_event(1, REQ_QUEUED, 1.0)
        rec.request_event(1, REQ_SHED, 2.0, reason="deadline")
        names = _names(rec)
        assert names == {"req/queued": 1, "req/shed": 1}
        shed = [e for e in rec.snapshot() if e["name"] == "req/shed"][0]
        assert shed["args"]["reason"] == "deadline"
        assert rec.open_requests == {}

    def test_out_of_order_transition_rejected(self):
        rec = SpanRecorder(capacity=64)
        with pytest.raises(ValueError, match="out-of-order request"):
            rec.request_event(1, REQ_DECODE, 1.0)  # decode before queued
        rec.request_event(1, REQ_QUEUED, 1.0)
        with pytest.raises(ValueError, match="out-of-order request"):
            rec.request_event(1, REQ_DECODE, 2.0)  # skip prefill
        rec.request_event(1, REQ_PREFILL, 2.0)
        rec.request_event(1, REQ_DONE, 3.0)
        with pytest.raises(ValueError, match="out-of-order request"):
            rec.request_event(1, REQ_DONE, 4.0)  # second terminal

    def test_backwards_timestamp_rejected(self):
        rec = SpanRecorder(capacity=64)
        rec.request_event(1, REQ_QUEUED, 5.0)
        with pytest.raises(ValueError, match="out-of-order request timestamp"):
            rec.request_event(1, REQ_PREFILL, 4.0)


class TestRecoveryLifecycle:
    """The fault-recovery vocabulary (docs/serving.md "Failure
    semantics"): ``retrying`` transitions, the ``shed(poisoned)``
    terminal, and the illegal recovery paths the validated state
    machine must reject."""

    def test_decode_retry_roundtrip_chain(self):
        from apex_tpu.observability.spans import REQ_RETRYING

        rec = SpanRecorder(capacity=64)
        rec.request_event(9, REQ_QUEUED, 1.0)
        rec.request_event(9, REQ_PREFILL, 2.0)
        rec.request_event(9, REQ_DECODE, 3.0)
        rec.request_event(9, REQ_RETRYING, 4.0, cause="engine:Boom",
                          attempt=1)
        rec.request_event(9, REQ_DECODE, 5.0, resumed=True)
        rec.request_event(9, REQ_DONE, 6.0, tokens=4)
        names = _names(rec)
        assert names["req/retrying"] == 1
        assert names["req/decode"] == 2
        assert names["req/done"] == 1
        retry = [e for e in rec.snapshot()
                 if e["name"] == "req/retrying"][0]
        # the recovery interval carries its cause AND the resume marker
        assert (retry["t0"], retry["t1"]) == (4.0, 5.0)
        assert retry["args"]["cause"] == "engine:Boom"
        assert retry["args"]["resumed"] is True
        assert rec.open_requests == {}

    def test_prefill_retry_reenters_through_prefill(self):
        from apex_tpu.observability.spans import REQ_RETRYING

        rec = SpanRecorder(capacity=64)
        rec.request_event(3, REQ_QUEUED, 1.0)
        rec.request_event(3, REQ_PREFILL, 2.0)
        rec.request_event(3, REQ_RETRYING, 3.0, cause="prefill:Boom")
        rec.request_event(3, REQ_PREFILL, 4.0, attempt=1)
        rec.request_event(3, REQ_DECODE, 5.0, ttft_ms=4000.0)
        rec.request_event(3, REQ_DONE, 6.0)
        assert _names(rec)["req/prefill"] == 2
        assert rec.open_requests == {}

    def test_shed_poisoned_from_decode(self):
        rec = SpanRecorder(capacity=64)
        rec.request_event(4, REQ_QUEUED, 1.0)
        rec.request_event(4, REQ_PREFILL, 2.0)
        rec.request_event(4, REQ_DECODE, 3.0)
        rec.request_event(4, REQ_SHED, 4.0, reason="poisoned")
        shed = [e for e in rec.snapshot() if e["name"] == "req/shed"][0]
        assert shed["args"]["reason"] == "poisoned"
        assert rec.open_requests == {}

    def test_shed_from_retrying_allowed(self):
        from apex_tpu.observability.spans import REQ_RETRYING

        rec = SpanRecorder(capacity=64)
        rec.request_event(5, REQ_QUEUED, 1.0)
        rec.request_event(5, REQ_PREFILL, 2.0)
        rec.request_event(5, REQ_RETRYING, 3.0)
        rec.request_event(5, REQ_SHED, 4.0, reason="retries_exhausted")
        assert rec.open_requests == {}

    def test_retrying_cannot_complete_directly(self):
        """retrying -> done is illegal: completion must go back
        through a decode (or prefill) that actually produced tokens."""
        from apex_tpu.observability.spans import REQ_RETRYING

        rec = SpanRecorder(capacity=64)
        rec.request_event(6, REQ_QUEUED, 1.0)
        rec.request_event(6, REQ_PREFILL, 2.0)
        rec.request_event(6, REQ_RETRYING, 3.0)
        with pytest.raises(ValueError, match="out-of-order request"):
            rec.request_event(6, REQ_DONE, 4.0)

    def test_shed_cannot_be_readmitted(self):
        """shed -> decode without re-admission is illegal: a terminal
        shed is final — recovery means a NEW request id."""
        from apex_tpu.observability.spans import REQ_RETRYING

        rec = SpanRecorder(capacity=64)
        rec.request_event(7, REQ_QUEUED, 1.0)
        rec.request_event(7, REQ_PREFILL, 2.0)
        rec.request_event(7, REQ_SHED, 3.0, reason="poisoned")
        for state in (REQ_DECODE, REQ_RETRYING, REQ_PREFILL):
            with pytest.raises(ValueError, match="out-of-order request"):
                rec.request_event(7, state, 4.0)

    def test_queued_cannot_jump_to_retrying(self):
        """retrying is a FAULT phase: a request that never reached
        prefill has nothing to retry."""
        from apex_tpu.observability.spans import REQ_RETRYING

        rec = SpanRecorder(capacity=64)
        rec.request_event(8, REQ_QUEUED, 1.0)
        with pytest.raises(ValueError, match="out-of-order request"):
            rec.request_event(8, REQ_RETRYING, 2.0)

    def test_routed_hop_chain(self):
        """The fleet re-route chain: queued -> routed (drain handoff)
        -> queued on the destination, then a normal lifecycle.  The
        routed span carries the destination replica and is closed by
        the target's own queued event."""
        from apex_tpu.observability.spans import REQ_ROUTED

        rec = SpanRecorder(capacity=64)
        rec.request_event(9, REQ_ROUTED, 1.0, replica="r0")  # fresh dispatch
        rec.request_event(9, REQ_QUEUED, 1.0)
        rec.request_event(9, REQ_ROUTED, 2.0, replica="r1")  # drain handoff
        rec.request_event(9, REQ_QUEUED, 2.5)
        rec.request_event(9, REQ_PREFILL, 3.0)
        rec.request_event(9, REQ_DECODE, 4.0)
        rec.request_event(9, REQ_DONE, 5.0)
        assert rec.open_requests == {}
        routed = [e for e in rec.snapshot() if e["name"] == "req/routed"]
        assert [s["args"]["replica"] for s in routed] == ["r0", "r1"]

    def test_routed_from_retrying_after_crash_evacuation(self):
        """A crash evacuation moves RUNNING work through retrying
        (charging the shared budget) before the hop — retrying ->
        routed is the legal crash-migration edge."""
        from apex_tpu.observability.spans import REQ_RETRYING, REQ_ROUTED

        rec = SpanRecorder(capacity=64)
        rec.request_event(10, REQ_QUEUED, 1.0)
        rec.request_event(10, REQ_PREFILL, 2.0)
        rec.request_event(10, REQ_DECODE, 3.0)
        rec.request_event(10, REQ_RETRYING, 4.0, cause="replica_crash")
        rec.request_event(10, REQ_ROUTED, 4.5, replica="r2")
        rec.request_event(10, REQ_QUEUED, 5.0)
        assert rec.open_requests == {10: "queued"}

    def test_inflight_phases_cannot_route_directly(self):
        """prefill/decode -> routed is illegal: a migration of
        in-flight work IS a fault recovery and must pass through
        retrying, where the shared retry budget is charged — a free
        hop would let a flapping replica bounce a request forever."""
        from apex_tpu.observability.spans import REQ_ROUTED

        for last in (REQ_PREFILL, REQ_DECODE):
            rec = SpanRecorder(capacity=64)
            rec.request_event(11, REQ_QUEUED, 1.0)
            rec.request_event(11, REQ_PREFILL, 2.0)
            if last == REQ_DECODE:
                rec.request_event(11, REQ_DECODE, 3.0)
            with pytest.raises(ValueError, match="out-of-order request"):
                rec.request_event(11, REQ_ROUTED, 4.0, replica="r1")

    def test_scheduler_records_retry_chain_end_to_end(self):
        """The scheduler's real fault path produces the validated
        chain: decode fault -> retrying span (with cause) ->
        re-admitted decode -> done, and the clamp rung lands as a
        req/clamped instant."""
        import numpy as np

        from apex_tpu.models.gpt import GptConfig, GptModel
        from apex_tpu.resilience import chaos
        from apex_tpu.serve import (
            ContinuousBatchingScheduler,
            InferenceEngine,
            Request,
            ServeConfig,
        )

        cfg = GptConfig(
            vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
            intermediate_size=64, max_seq_len=128, dtype=jnp.float32,
        )
        model = GptModel(cfg)
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((8, 1), jnp.int32)
        )
        eng = InferenceEngine(
            cfg, params,
            ServeConfig(page_size=8, num_pages=32, max_batch=2,
                        max_pages_per_seq=8, verify=False),
        )
        rec = SpanRecorder(capacity=4096)
        sched = ContinuousBatchingScheduler(
            eng, spans=rec,
            clamp_max_new_tokens=3, clamp_occupancy=0.01,
        )
        rs = np.random.RandomState(40)
        with chaos.inject(chaos.Fault(
            chaos.SERVE_DECODE, steps=(1,), mode="raise", max_hits=1,
        )):
            a = sched.submit(Request(
                prompt=[int(t) for t in rs.randint(0, 64, size=6)],
                max_new_tokens=6,
            ))
            b = sched.submit(Request(
                prompt=[int(t) for t in rs.randint(0, 64, size=6)],
                max_new_tokens=6,
            ))
            sched.run()
        assert a.status == "done" and b.status == "done"
        names = _names(rec)
        assert names.get("req/retrying", 0) >= 1
        assert names.get("req/clamped", 0) >= 1  # occupancy rung fired
        assert rec.open_requests == {}
        retry = [e for e in rec.snapshot()
                 if e["name"] == "req/retrying"][0]
        assert retry["args"]["cause"].startswith("engine:")
        assert retry["args"]["attempt"] == 1


# ---------------------------------------------------------------------------
# run_resilient observer bridge + trace window markers
# ---------------------------------------------------------------------------


class TestObserverBridge:
    def test_step_spans_and_replay_mark(self):
        t = [0.0]

        def clock():
            t[0] += 1.0
            return t[0]

        rec = SpanRecorder(capacity=64, clock=clock)
        rec.on_step(0)          # baseline tick only — no span yet
        rec.on_step(1)
        rec.on_rollback(2, 0, skips=2, discarded=1)
        rec.on_step(1)          # replay: rewound counter
        rec.on_checkpoint(1)
        rec.on_resume(5)
        rec.on_retry("save", 2, RuntimeError("boom"))
        rec.on_preempt(6)
        names = _names(rec)
        assert names["train/step"] == 2
        for k in ("train/rollback", "train/checkpoint", "train/resume",
                  "train/retry", "train/preempt"):
            assert names[k] == 1
        steps = [e for e in rec.snapshot() if e["name"] == "train/step"]
        assert "replay" not in (steps[0]["args"])
        assert steps[1]["args"]["replay"] is True
        retry = [e for e in rec.snapshot()
                 if e["name"] == "train/retry"][0]
        assert "RuntimeError: boom" in retry["args"]["error"]

    def test_health_event_instant(self):
        rec = SpanRecorder(capacity=16)
        rec.note_health(HealthEvent(
            "ttft", "critical", 7, 2500.0, 1000.0, "TTFT blown", None,
        ))
        ev = rec.snapshot()[0]
        assert ev["name"] == "health/ttft"
        assert ev["args"]["severity"] == "critical"
        assert ev["args"]["threshold"] == 1000.0

    def test_trace_scheduler_abort_records_partial_window(self, tmp_path):
        """A watchdog re-arm mid-capture closes the window early; its
        partial artifacts still get a span, marked aborted."""
        rec = SpanRecorder(capacity=16)
        sched = TraceScheduler(
            spec=f"1+4:{tmp_path}", spans=rec,
            _start_fn=lambda d: None, _stop_fn=lambda: None,
        )
        sched.on_step(1)          # capture starts
        assert sched.tracing
        sched.arm(5, 1)           # escalation re-arms mid-capture
        windows = [e for e in rec.snapshot()
                   if e["name"] == "trace/window"]
        assert len(windows) == 1
        assert windows[0]["args"]["aborted"] == "rearm"
        # the re-armed window captures and records cleanly
        for step in range(2, 8):
            sched.on_step(step)
        windows = [e for e in rec.snapshot()
                   if e["name"] == "trace/window"]
        assert len(windows) == 2
        assert "aborted" not in windows[1]["args"]
        assert windows[1]["args"]["start_step"] == 5

    def test_trace_scheduler_window_marker(self, tmp_path):
        calls = []
        rec = SpanRecorder(capacity=16)
        sched = TraceScheduler(
            spec=f"2+2:{tmp_path}", spans=rec,
            _start_fn=lambda d: calls.append(("start", d)),
            _stop_fn=lambda: calls.append(("stop",)),
        )
        for step in range(6):
            sched.on_step(step)
        assert [c[0] for c in calls] == ["start", "stop"]
        windows = [e for e in rec.snapshot()
                   if e["name"] == "trace/window"]
        assert len(windows) == 1
        w = windows[0]
        assert w["args"]["start_step"] == 2
        assert w["args"]["end_step"] == 3
        assert w["args"]["log_dir"] == sched.log_dir
        assert w["t1"] >= w["t0"]


# ---------------------------------------------------------------------------
# scheduler-driven lifecycle (the ISSUE 8 invariants)
# ---------------------------------------------------------------------------


def tiny_engine(**serve_kw):
    from apex_tpu.models.gpt import GptConfig, GptModel
    from apex_tpu.serve import InferenceEngine, ServeConfig

    cfg = GptConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        intermediate_size=64, max_seq_len=128, dtype=jnp.float32,
    )
    model = GptModel(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((8, 1), jnp.int32)
    )
    kw = dict(page_size=8, num_pages=32, max_batch=2,
              max_pages_per_seq=8, verify=False)
    kw.update(serve_kw)
    return InferenceEngine(cfg, params, ServeConfig(**kw))


@pytest.fixture(scope="module")
def engine():
    return tiny_engine()


def _run_load(engine, n=4, spans=None, registry=None, max_new=3):
    from apex_tpu.serve import ContinuousBatchingScheduler, Request

    sched = ContinuousBatchingScheduler(
        engine, registry=registry, spans=spans,
    )
    rs = np.random.RandomState(0)
    for _ in range(n):
        sched.submit(Request(
            prompt=[int(t) for t in rs.randint(0, 64, size=6)],
            max_new_tokens=max_new,
        ))
    sched.run()
    return sched


class TestSchedulerSpans:
    def test_every_admitted_request_has_one_terminal(self, engine):
        rec = SpanRecorder(capacity=1024)
        sched = _run_load(engine, n=4, spans=rec)
        engine.spans = None
        assert rec.open_requests == {}
        terms = {}
        for e in rec.snapshot():
            if e["name"] in ("req/done", "req/shed"):
                terms[e["lane"]] = terms.get(e["lane"], 0) + 1
        assert sorted(terms) == sorted(r.rid for r in sched.completed)
        assert all(v == 1 for v in terms.values())

    def test_ttft_components_sum_and_span_args(self, engine):
        rec = SpanRecorder(capacity=1024)
        sched = _run_load(engine, n=4, spans=rec)
        engine.spans = None
        assert len(sched.completed) == 4
        for r in sched.completed:
            c = r.ttft_components()
            total = (
                c["queue_wait_ms"] + c["prefill_ms"] + c["contention_ms"]
            )
            # by construction: contention is the remainder
            assert total == pytest.approx(c["ttft_ms"], abs=1e-6)
        # the req/prefill span carries the full attribution
        prefills = [e for e in rec.snapshot()
                    if e["name"] == "req/prefill"]
        assert len(prefills) == 4
        for p in prefills:
            args = p["args"]
            assert {"ttft_ms", "queue_wait_ms", "prefill_ms",
                    "contention_ms"} <= set(args)

    def test_decode_iter_correlation(self, engine):
        rec = SpanRecorder(capacity=1024)
        sched = _run_load(engine, n=2, spans=rec, max_new=4)
        engine.spans = None
        iters = {
            e["args"]["iter"] for e in rec.snapshot()
            if e["name"] == "engine/decode"
        }
        assert iters, "engine decode spans missing"
        for r in sched.completed:
            assert r.first_decode_iter in iters
            assert r.last_decode_iter in iters
            assert r.first_decode_iter <= r.last_decode_iter
        # the terminal args carry the correlation window
        dones = [e for e in rec.snapshot() if e["name"] == "req/done"]
        by_rid = {e["lane"]: e["args"] for e in dones}
        for r in sched.completed:
            assert by_rid[r.rid]["first_iter"] == r.first_decode_iter
            assert by_rid[r.rid]["last_iter"] == r.last_decode_iter
            assert by_rid[r.rid]["tokens"] == len(r.tokens)

    def test_shed_reasons_match_ledger_counters(self):
        """Deadline + growth-victim sheds: span reasons == Request
        ledger == the split serve/shed_* registry counters."""
        from apex_tpu.serve import ContinuousBatchingScheduler, Request

        class FakeClock:
            def __init__(self):
                self.t = 0.0

            def __call__(self):
                self.t += 1e-4
                return self.t

            def advance(self, dt):
                self.t += dt

        eng = tiny_engine(num_pages=3, max_pages_per_seq=2)
        rec = SpanRecorder(capacity=1024)
        reg = MetricRegistry(fetch_every=1)
        clock = FakeClock()
        sched = ContinuousBatchingScheduler(
            eng, registry=reg, clock=clock, spans=rec,
        )
        rs = np.random.RandomState(9)
        hog = sched.submit(Request(
            prompt=[int(t) for t in rs.randint(0, 64, size=14)],
            max_new_tokens=4,
        ))
        starved = sched.submit(Request(
            prompt=[int(t) for t in rs.randint(0, 64, size=14)],
            max_new_tokens=2, slo_ttft_ms=500.0,
        ))
        sched.step()
        clock.advance(1.0)
        sched.run()
        eng.spans = None
        assert starved.status == "shed"
        assert starved.shed_reason == "deadline"
        assert hog.status == "done"
        sheds = [e for e in rec.snapshot() if e["name"] == "req/shed"]
        assert len(sheds) == 1
        assert sheds[0]["lane"] == starved.rid
        assert sheds[0]["args"]["reason"] == "deadline"
        reg.fetch()
        vals = reg.values()
        assert vals["serve/shed"] == 1.0
        assert vals["serve/shed_deadline"] == 1.0
        assert vals["serve/shed_growth_victim"] == 0.0
        assert vals["serve/shed_pool_exhausted"] == 0.0
        assert vals["serve/shed_oversize"] == 0.0

    def test_growth_victim_reason(self):
        from apex_tpu.serve import ContinuousBatchingScheduler, Request

        eng = tiny_engine(num_pages=4, max_pages_per_seq=3)
        rec = SpanRecorder(capacity=1024)
        reg = MetricRegistry(fetch_every=1)
        sched = ContinuousBatchingScheduler(eng, registry=reg, spans=rec)
        rs = np.random.RandomState(10)
        old = sched.submit(Request(
            prompt=[int(t) for t in rs.randint(0, 64, size=8)],
            max_new_tokens=10,
        ))
        young = sched.submit(Request(
            prompt=[int(t) for t in rs.randint(0, 64, size=8)],
            max_new_tokens=10,
        ))
        hog = sched.submit(Request(
            prompt=[int(t) for t in rs.randint(0, 64, size=8)],
            max_new_tokens=1,
        ))
        sched.run()
        eng.spans = None
        assert old.status == "done" and hog.status == "done"
        assert young.status == "shed"
        assert young.shed_reason == "growth_victim"
        reg.fetch()
        vals = reg.values()
        assert vals["serve/shed"] == 1.0
        assert vals["serve/shed_growth_victim"] == 1.0
        # ledger counters == span record == per-reason sum
        reasons = [e["args"]["reason"] for e in rec.snapshot()
                   if e["name"] == "req/shed"]
        assert reasons == ["growth_victim"]
        assert vals["serve/shed"] == sum(
            vals[f"serve/shed_{r}"] for r in
            ("deadline", "growth_victim", "pool_exhausted", "oversize")
        )

    def test_second_scheduler_takes_over_engine_recorder(self, engine):
        """A later scheduler's recorder replaces the retired one on the
        shared engine — its dump carries the engine spans its
        correlation ids reference."""
        rec_a = SpanRecorder(capacity=1024)
        _run_load(engine, n=1, spans=rec_a, max_new=2)
        rec_b = SpanRecorder(capacity=1024)
        sched_b = _run_load(engine, n=1, spans=rec_b, max_new=2)
        engine.spans = None
        b_iters = {e["args"]["iter"] for e in rec_b.snapshot()
                   if e["name"] == "engine/decode"}
        assert b_iters, "second recorder got no engine spans"
        for r in sched_b.completed:
            assert r.first_decode_iter in b_iters
        # and nothing from B's run leaked into A's retired record
        a_iters = {e["args"]["iter"] for e in rec_a.snapshot()
                   if e["name"] == "engine/decode"}
        assert not (a_iters & b_iters)

    def test_prefill_calls_counted_without_recorder(self):
        eng = tiny_engine()
        pages = eng.pool.alloc(1)
        eng.prefill([1, 2, 3], pages)  # no recorder attached
        assert eng.prefill_calls == 1
        eng.pool.free(pages)

    def test_custom_clock_shared_with_recorder(self):
        """A non-default scheduler clock becomes the recorder's clock:
        one time basis for request AND engine spans."""
        from apex_tpu.serve import ContinuousBatchingScheduler

        eng = tiny_engine()
        rec = SpanRecorder(capacity=64)
        clock_vals = iter(float(i) for i in range(1000))
        clock = lambda: next(clock_vals)  # noqa: E731
        ContinuousBatchingScheduler(eng, clock=clock, spans=rec)
        assert rec.clock is clock
        eng.spans = None

    def test_attribution_percentiles_on_registry(self, engine):
        reg = MetricRegistry(fetch_every=1)
        _run_load(engine, n=4, spans=None, registry=reg)
        reg.fetch()
        vals = reg.values()
        for comp in ("queue_wait", "prefill", "contention"):
            for tag in ("p50", "p95", "p99"):
                assert f"serve/ttft_{comp}_ms_{tag}" in vals
        # prefill really runs, so its p50 must be positive
        assert vals["serve/ttft_prefill_ms_p50"] > 0.0
        assert 0.0 <= vals["serve/ttft_queue_wait_fraction"] <= 1.0


# ---------------------------------------------------------------------------
# host phases: SpanRecorder.phase(), the process ring, the vocabulary
# ---------------------------------------------------------------------------


class _Tick:
    """A clock that advances 1.0 per read."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def _since(ring, seq0):
    return [e for e in ring.snapshot() if e["seq"] >= seq0]


def _tree(entries):
    """(steps in order, children by parent id) of phase entries."""
    phases = [e for e in entries if "id" in e]
    kids = {}
    for e in phases:
        kids.setdefault(e["parent"], []).append(e)
    return [e for e in phases if e["name"] == "serve/step"], kids


class TestPhasePrimitive:
    def test_phase_nests_and_records_id_and_parent(self):
        rec = SpanRecorder(capacity=16, clock=_Tick())
        with rec.phase("serve/step", step=3) as outer:
            with rec.phase("serve/admit", rid=7) as inner:
                inner.set(bucket=8)
            with rec.phase("engine/decode", track=TRACK_ENGINE):
                pass
            outer.set(tokens=2)
        admit, decode, step = rec.snapshot()     # appended as they close
        assert [e["name"] for e in (admit, decode, step)] == [
            "serve/admit", "engine/decode", "serve/step"]
        assert step["parent"] is None
        assert admit["parent"] == decode["parent"] == step["id"]
        assert len({admit["id"], decode["id"], step["id"]}) == 3
        assert admit["args"] == {"rid": 7, "bucket": 8}
        assert step["args"] == {"step": 3, "tokens": 2}
        assert "args" not in decode
        assert step["track"] == "serve/scheduler"
        assert decode["track"] == TRACK_ENGINE
        assert step["t0"] < admit["t0"] < admit["t1"] < step["t1"]
        # the stack is empty again: the next phase is a root
        with rec.phase("serve/step"):
            pass
        assert rec.snapshot()[-1]["parent"] is None

    def test_phase_that_raises_closes_and_reraises(self):
        rec = SpanRecorder(capacity=16, clock=_Tick())
        with pytest.raises(KeyError):
            with rec.phase("serve/step"):
                with rec.phase("engine/prefill", track=TRACK_ENGINE):
                    raise KeyError("boom")
        names = [e["name"] for e in rec.snapshot()]
        assert names == ["engine/prefill", "serve/step"]
        with rec.phase("serve/publish"):
            pass
        assert rec.snapshot()[-1]["parent"] is None

    def test_dropped_phase_leaves_no_entry_but_keeps_the_stack(self):
        rec = SpanRecorder(capacity=16, clock=_Tick())
        with rec.phase("serve/step") as step:
            with rec.phase("serve/admit") as ph:
                ph.drop()
            with rec.phase("serve/batch"):
                pass
        assert [e["name"] for e in rec.snapshot()] == [
            "serve/batch", "serve/step"]
        assert rec.snapshot()[0]["parent"] == step.id

    def test_ring_stays_at_capacity_and_counts_the_dropped(self):
        from apex_tpu.observability.spans import PROCESS_CAPACITY

        rec = SpanRecorder(PROCESS_CAPACITY)
        for i in range(200_000):
            with rec.phase("serve/batch"):
                pass
        assert len(rec.snapshot()) == PROCESS_CAPACITY == 131072
        assert rec.dropped == 200_000 - PROCESS_CAPACITY
        assert rec.snapshot()[-1]["seq"] == 199_999

    def test_threads_share_a_ring_and_keep_their_own_stacks(self):
        """Replicas stepped from several threads: no entry is lost, no
        sequence number repeats, and a phase's parent is the phase open
        around it on ITS thread."""
        import threading

        rec = SpanRecorder(capacity=100_000)
        workers, rounds = 16, 200
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            def work(k):
                for i in range(rounds):
                    with rec.phase("serve/step", worker=k):
                        with rec.phase("serve/batch", worker=k):
                            pass

            threads = [threading.Thread(target=work, args=(k,))
                       for k in range(workers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(old)
        snap = rec.snapshot()
        assert len(snap) == 2 * workers * rounds and rec.dropped == 0
        assert len({e["seq"] for e in snap}) == len(snap)
        assert len({e["id"] for e in snap}) == len(snap)
        by_id = {e["id"]: e for e in snap}
        for e in snap:
            if e["name"] == "serve/batch":
                parent = by_id[e["parent"]]
                assert parent["name"] == "serve/step"
                assert parent["args"]["worker"] == e["args"]["worker"]
            else:
                assert e["parent"] is None

    def test_phase_lands_in_the_profiler_trace_too(self, tmp_path):
        """With a profiler session on, the same call writes the name
        into the trace's host plane (the device trace's clock)."""
        from jax.profiler import ProfileData

        rec = SpanRecorder(capacity=16)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with rec.phase("serve/step"):
                with rec.phase("engine/stage", track=TRACK_ENGINE):
                    jnp.ones((4,)).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        found = []
        for root, _dirs, files in os.walk(tmp_path):
            found += [os.path.join(root, f) for f in files
                      if f.endswith(".xplane.pb")]
        assert found
        spans = {}
        for plane in ProfileData.from_file(found[0]).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name in ("serve/step", "engine/stage"):
                            spans[e.name] = (e.start_ns,
                                             e.start_ns + e.duration_ns)
        assert set(spans) == {"serve/step", "engine/stage"}
        (s0, s1), (c0, c1) = spans["serve/step"], spans["engine/stage"]
        assert s0 <= c0 and c1 <= s1
        # and the ring holds the same two, nested the same way
        stage, step = rec.snapshot()
        assert stage["parent"] == step["id"]


class TestSchedulerPhases:
    """The vocabulary of docs/serving.md "Host phases", on a tiny
    engine."""

    def _stepwise(self, engine, spans=None, n=3, max_new=3, **kw):
        """Run a load one step at a time; per step, what it did as seen
        from outside."""
        from apex_tpu.serve import ContinuousBatchingScheduler, Request

        sched = ContinuousBatchingScheduler(
            engine, registry=None, spans=spans, **kw)
        rs = np.random.RandomState(1)
        reqs = [sched.submit(Request(
            prompt=[int(t) for t in rs.randint(0, 64, size=6)],
            max_new_tokens=max_new)) for _ in range(n)]
        did = []
        while sched.pending:
            before = (engine.prefill_calls, engine.decode_iters,
                      sum(len(r.tokens) for r in reqs),
                      len(sched.completed) + len(sched.shed))
            sched.step()
            after = (engine.prefill_calls, engine.decode_iters,
                     sum(len(r.tokens) for r in reqs),
                     len(sched.completed) + len(sched.shed))
            did.append(tuple(a - b for a, b in zip(after, before)))
        return sched, did

    def test_no_recorder_one_serve_step_per_step_in_the_process_ring(
            self, engine):
        from apex_tpu.observability import process_recorder

        ring = process_recorder()
        seq0 = ring._seq
        sched, did = self._stepwise(engine)
        assert sched.spans is None and engine.spans is None
        steps, kids = _tree(_since(ring, seq0))
        assert len(steps) == len(did) >= 3
        for step, (prefills, decodes, tokens, retired) in zip(steps, did):
            args = step["args"]
            assert args["prefills"] == prefills
            assert args["tokens"] == tokens
            assert args["retired"] == retired
            mine = kids.get(step["id"], [])
            dec = [c for c in mine if c["name"] == "engine/decode"]
            assert len(dec) == decodes
            assert args["riders"] == sum(c["args"]["batch"] for c in dec)
            assert len([c for c in mine
                        if c["name"] == "serve/admit"]) == prefills
            assert [c["name"] for c in mine][-1] == "serve/publish"
        assert [s["args"]["step"] for s in steps] == list(range(len(did)))
        assert sum(s["args"]["tokens"] for s in steps) == 9

    def test_engine_stage_before_every_engine_call(self, engine):
        from apex_tpu.observability import process_recorder

        ring = process_recorder()
        seq0 = ring._seq
        self._stepwise(engine)
        phases = sorted((e for e in _since(ring, seq0) if "id" in e),
                        key=lambda e: e["t0"])
        calls = 0
        for e in phases:
            if e["name"] not in ("engine/prefill", "engine/decode"):
                continue
            calls += 1
            sibs = [p for p in phases if p["parent"] == e["parent"]
                    and p["t1"] <= e["t0"]]
            assert sibs and sibs[-1]["name"] == "engine/stage"
            want = "decode" if e["name"] == "engine/decode" else (
                f"prefill_{e['args']['bucket']}")
            assert sibs[-1]["args"]["program"] == want
        assert calls >= 5
        by_id = {e["id"]: e for e in phases}
        for e in phases:
            if e["name"] == "engine/prefill":
                admit = by_id[e["parent"]]
                assert admit["name"] == "serve/admit"
                assert admit["args"]["bucket"] == e["args"]["bucket"]
                assert admit["args"]["prompt_tokens"] == e["args"]["tokens"]

    def test_self_times_of_a_step_sum_to_its_duration(self, engine):
        """Children lie inside their parent and do not overlap, so each
        phase's self time (duration less its children's) is >= 0 and a
        step's self times add up to its duration."""
        rec = SpanRecorder(capacity=4096)
        self._stepwise(engine, spans=rec)
        engine.spans = None
        steps, kids = _tree(rec.snapshot())
        assert steps
        eps = 1e-9
        for step in steps:
            total, todo = 0.0, [step]
            while todo:
                e = todo.pop()
                mine = sorted(kids.get(e["id"], []), key=lambda c: c["t0"])
                for a, b in zip(mine, mine[1:]):
                    assert a["t1"] <= b["t0"] + eps
                for c in mine:
                    assert e["t0"] - eps <= c["t0"] <= c["t1"] <= e["t1"] + eps
                own = (e["t1"] - e["t0"]) - sum(
                    c["t1"] - c["t0"] for c in mine)
                assert own >= -eps
                total += own
                todo += mine
            assert total == pytest.approx(step["t1"] - step["t0"], abs=1e-6)

    def test_attached_recorder_gets_the_phases_not_the_process_ring(
            self, engine):
        from apex_tpu.observability import process_recorder

        ring = process_recorder()
        seq0 = ring._seq
        rec = SpanRecorder(capacity=4096)
        _sched, did = self._stepwise(engine, spans=rec)
        engine.spans = None
        assert _since(ring, seq0) == []
        names = _names(rec)
        assert names["serve/step"] == len(did)
        assert names["serve/publish"] == len(did)
        assert names["engine/stage"] == (
            names["engine/prefill"] + names["engine/decode"])
        assert names["serve/batch"] == names["serve/retire"] == (
            names["engine/decode"])
        # the request lifecycle is still there, beside the phases
        assert names["req/done"] == 3

    def test_request_lifecycle_never_reaches_the_process_ring(
            self, engine, monkeypatch):
        from apex_tpu.observability import process_recorder

        ring = process_recorder()

        def refuse(*a, **k):
            raise AssertionError("request_event on the process ring")

        monkeypatch.setattr(ring, "request_event", refuse)
        monkeypatch.setattr(ring, "instant", refuse)
        seq0 = ring._seq
        sched, _ = self._stepwise(engine)
        assert len(sched.completed) == 3
        got = _since(ring, seq0)
        assert got and all(e["name"].startswith(("serve/", "engine/"))
                           for e in got)
        assert ring.open_requests == {}

    def test_admission_without_a_prefill_leaves_no_admit_phase(self):
        """Chunked mode parks the request at admission and prefills it
        chunk by chunk: the chunks' stage + prefill phases sit under
        serve/chunks, and no serve/admit is recorded."""
        eng = tiny_engine()
        rec = SpanRecorder(capacity=4096)
        sched, _ = self._stepwise(eng, spans=rec, n=2,
                                  prefill_chunk_tokens=8)
        eng.spans = None
        assert len(sched.completed) == 2
        names = _names(rec)
        assert "serve/admit" not in names
        by_id = {e["id"]: e for e in rec.snapshot() if "id" in e}
        prefills = [e for e in by_id.values()
                    if e["name"] == "engine/prefill"]
        assert prefills and all(e["args"]["chunked"] for e in prefills)
        assert {by_id[e["parent"]]["name"] for e in prefills} == {
            "serve/chunks"}
        assert names["serve/chunks"] <= names["serve/step"]


# ---------------------------------------------------------------------------
# watchdog: queue-wait fraction rule
# ---------------------------------------------------------------------------


class TestQueueWaitFractionRule:
    def _registry(self, **values):
        from apex_tpu.serve import declare_serve_metrics

        reg = MetricRegistry(fetch_every=1)
        declare_serve_metrics(reg)
        state = reg.update(reg.init(), values)
        reg.observe(0, state)
        reg.observe(1, state)
        reg.fetch()
        return reg

    def test_fires_when_admission_starved(self):
        reg = self._registry(**{"serve/ttft_queue_wait_fraction": 0.8})
        wd = Watchdog(
            serve_rules(queue_wait_fraction={"max_fraction": 0.5}),
            registry=reg, check_every=1,
        )
        wd.on_step(1)
        events = [e for e in wd.events
                  if e.rule == "queue_wait_fraction"]
        assert len(events) == 1
        assert "admission starved" in events[0].message

    def test_watchdog_forwards_events_to_span_recorder(self):
        """Watchdog(spans=rec): a firing lands on the health track, so
        the merged timeline shows the alert next to its cause."""
        rec = SpanRecorder(capacity=16)
        reg = self._registry(**{"serve/ttft_queue_wait_fraction": 0.9})
        wd = Watchdog(
            serve_rules(queue_wait_fraction={"max_fraction": 0.5}),
            registry=reg, spans=rec, check_every=1,
        )
        wd.on_step(1)
        health = [e for e in rec.snapshot()
                  if e["name"] == "health/queue_wait_fraction"]
        assert len(health) == 1
        assert health[0]["args"]["severity"] == "warn"

    def test_silent_under_budget_and_in_serve_rules(self):
        reg = self._registry(**{"serve/ttft_queue_wait_fraction": 0.2})
        wd = Watchdog(serve_rules(), registry=reg, check_every=1)
        wd.on_step(1)
        assert [e for e in wd.events
                if e.rule == "queue_wait_fraction"] == []
        assert any(
            isinstance(r, QueueWaitFractionRule)
            for r in serve_rules()
        )


# ---------------------------------------------------------------------------
# TimelineSink (Chrome trace events)
# ---------------------------------------------------------------------------


class TestTimelineSink:
    def test_spans_to_chrome_events(self, tmp_path):
        out = tmp_path / "trace.json"
        anchor = {"monotonic": 100.0, "epoch": 1000.0}
        with TimelineSink(str(out), process_name="test") as sink:
            n = sink.add_spans(
                [
                    {"name": "req/prefill", "track": TRACK_REQUESTS,
                     "lane": 3, "t0": 101.0, "t1": 101.5,
                     "args": {"bucket": 8}},
                    {"name": "req/done", "track": TRACK_REQUESTS,
                     "lane": 3, "t": 102.0},
                    {"name": "engine/decode", "track": TRACK_ENGINE,
                     "t0": 101.5, "t1": 101.6},
                ],
                anchor=anchor,
            )
            assert n == 3
        data = json.load(open(out))
        evs = data["traceEvents"]
        x = [e for e in evs if e["ph"] == "X"]
        i = [e for e in evs if e["ph"] == "i"]
        m = [e for e in evs if e["ph"] == "M"]
        assert len(x) == 2 and len(i) == 1 and m
        prefill = [e for e in x if e["name"] == "req/prefill"][0]
        # monotonic 101.0 -> epoch 1001.0 -> 1.001e9 us
        assert prefill["ts"] == pytest.approx(1001.0 * 1e6)
        assert prefill["dur"] == pytest.approx(0.5 * 1e6)
        assert prefill["args"] == {"bucket": 8}
        # one named thread row per (track, lane)
        names = {e["args"]["name"] for e in m
                 if e["name"] == "thread_name"}
        assert f"{TRACK_REQUESTS} [3]" in names
        assert TRACK_ENGINE in names

    def test_counter_from_bench_record(self, tmp_path):
        out = tmp_path / "trace.json"
        with TimelineSink(str(out)) as sink:
            sink.write(bench_record("serve/ttft_ms", 12.5, "ms"))
            sink.write(bench_record("ignored", "text"))
            sink.write(bench_record("skipped", float("nan")))
        evs = json.load(open(out))["traceEvents"]
        counters = [e for e in evs if e["ph"] == "C"]
        assert len(counters) == 1
        assert counters[0]["name"] == "serve/ttft_ms"
        assert counters[0]["args"]["value"] == 12.5


# ---------------------------------------------------------------------------
# tools/timeline.py accounting (the CI gate)
# ---------------------------------------------------------------------------


class TestTimelineTool:
    def test_clean_run_accounts_and_merges(self, engine, tmp_path):
        timeline = _tool("timeline")
        rec = SpanRecorder(capacity=4096)
        sched = _run_load(engine, n=3, spans=rec)
        engine.spans = None
        spans_path = str(tmp_path / "spans.json")
        rec.dump(reason="test", path=spans_path)
        out = str(tmp_path / "trace.json")
        rc = timeline.main([
            "--spans", spans_path, "--out", out, "--json",
        ])
        assert rc == 0
        trace = json.load(open(out))
        assert trace["traceEvents"], "empty merged trace"
        summary = timeline.account_requests(
            json.load(open(spans_path))["spans"], 0, 1.0
        )
        assert summary["ok"], summary["violations"]
        assert summary["requests"]["total"] == 3
        assert summary["requests"]["admitted"] == 3
        assert summary["requests"]["complete"] == 3
        assert summary["ttft_accounting"]["checked"] == 3
        assert summary["ttft_accounting"]["max_error_ms"] <= 1.0
        assert len(sched.completed) == 3

    def test_incomplete_chain_fails_accounting(self):
        timeline = _tool("timeline")
        # an admitted request with no terminal event
        spans = [
            {"name": "req/queued", "track": "serve/requests", "lane": 1,
             "t0": 0.0, "t1": 1.0},
            {"name": "req/prefill", "track": "serve/requests", "lane": 1,
             "t0": 1.0, "t1": 2.0},
        ]
        summary = timeline.account_requests(spans, 0, 1.0)
        assert not summary["ok"]
        assert any("terminal" in v for v in summary["violations"])

    def test_ttft_sum_mismatch_fails_accounting(self):
        timeline = _tool("timeline")
        spans = [
            {"name": "req/queued", "track": "serve/requests", "lane": 1,
             "t0": 0.0, "t1": 1.0},
            {"name": "req/prefill", "track": "serve/requests", "lane": 1,
             "t0": 1.0, "t1": 2.0,
             "args": {"ttft_ms": 10.0, "queue_wait_ms": 2.0,
                      "prefill_ms": 3.0, "contention_ms": 1.0}},
            {"name": "req/done", "track": "serve/requests", "lane": 1,
             "t": 2.0},
        ]
        summary = timeline.account_requests(spans, 0, 1.0)
        assert not summary["ok"]
        assert any("components sum off" in v
                   for v in summary["violations"])

    def test_dropped_entries_fail_accounting(self):
        timeline = _tool("timeline")
        chain = [
            {"name": "req/queued", "track": "serve/requests", "lane": 1,
             "t0": 0.0, "t1": 1.0},
            {"name": "req/shed", "track": "serve/requests", "lane": 1,
             "t": 1.0, "args": {"reason": "deadline"}},
        ]
        # a wrapped ring invalidates completeness claims about chains...
        summary = timeline.account_requests(chain, 5, 1.0)
        assert not summary["ok"]
        assert any("dropped" in v for v in summary["violations"])
        # ...but a wrapped train-only record claims nothing about
        # chains and stays clean (the long-run steady state)
        assert timeline.account_requests([], 5, 1.0)["ok"]
        # per-source scoping: a wrapped train-only dump (src 0) merged
        # with a complete serve dump (src 1) must not fail src 1's
        # accounting
        merged = [
            {"name": "train/step", "track": "train",
             "t0": 0.0, "t1": 1.0, "_src": 0},
        ] + [dict(e, _src=1) for e in chain]
        summary = timeline.account_requests(merged, {0: 7, 1: 0}, 1.0)
        assert summary["ok"], summary["violations"]
        assert summary["dropped"] == 7
        # the serve dump's OWN wrap still fails it
        summary = timeline.account_requests(merged, {0: 0, 1: 3}, 1.0)
        assert not summary["ok"]
        # a wrapped serve dump whose CHAINS were all evicted (only
        # engine spans survive) is exactly the truncation the gate
        # exists to catch — serve activity + drops = unaccountable
        engine_only = [
            {"name": "engine/decode", "track": "serve/engine",
             "t0": 0.0, "t1": 0.1, "args": {"iter": 1}},
        ]
        summary = timeline.account_requests(engine_only, {0: 500}, 1.0)
        assert not summary["ok"]
        assert any("dropped" in v for v in summary["violations"])

    def test_flight_dump_merges(self, tmp_path):
        timeline = _tool("timeline")
        from apex_tpu.observability import FlightRecorder, MetricRegistry

        reg = MetricRegistry(fetch_every=1)
        reg.gauge("train/loss")
        state = reg.update(reg.init(), {"train/loss": float("nan")})
        reg.observe(0, state)
        reg.observe(1, state)
        reg.fetch()
        rec = FlightRecorder(
            capacity=8, directory=str(tmp_path), registry=reg,
        )
        for s in range(4):
            rec.on_step(s, skipped=(s == 2))
        rec.on_rollback(3, 1, skips=1)
        dump = rec.dump("unit test")
        out = str(tmp_path / "trace.json")
        rc = timeline.main(["--flight", dump, "--out", out])
        assert rc == 0
        evs = json.load(open(out))["traceEvents"]
        steps = [e for e in evs if e.get("name") == "train/step"]
        assert len(steps) == 3  # 4 frames -> 3 intervals
        assert any(e.get("name") == "train/rollback" for e in evs)
        # the NaN loss — the crash evidence — survives as a marker
        # instant (a counter track cannot render non-finites)
        nan_marks = [e for e in evs
                     if e.get("name") == "train/loss = NaN"]
        assert nan_marks and nan_marks[0]["ph"] == "i"
