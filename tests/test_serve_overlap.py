"""One decode call in flight (docs/serving.md "Host phases"): while no
request waits, no chunked prefill runs, the pass is no speculative round and
no rider ends with the call by its budget, the scheduler dispatches decode
call N + 1 — fed each rider's token from call N's output on the device —
before it reads call N.  The served streams are the ones the synchronous
path serves, token for token; ``_can_overlap`` patched to ``False`` is that
path.  CPU, tiny engines.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from apex_tpu.models.gpt import GptConfig, GptModel  # noqa: E402
from apex_tpu.observability import MetricRegistry, SpanRecorder  # noqa: E402
from apex_tpu.resilience import chaos  # noqa: E402
from apex_tpu.serve import (  # noqa: E402
    ContinuousBatchingScheduler, InferenceEngine, Request, ServeConfig,
)
from apex_tpu.serve import spec as spec_lib  # noqa: E402
from apex_tpu.serve.scheduler import PREFILLING  # noqa: E402


@pytest.fixture(scope="module")
def gpt():
    cfg = GptConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        intermediate_size=64, max_seq_len=128, dtype=jnp.float32,
    )
    params = GptModel(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((8, 1), jnp.int32)
    )
    return cfg, params


def gpt_engine(gpt, *, spec=None, **kw):
    cfg, params = gpt
    serve = dict(page_size=8, num_pages=48, max_batch=3, max_pages_per_seq=8,
                 verify=False, sample_seed=5)
    serve.update(kw)
    return InferenceEngine(cfg, params, ServeConfig(**serve), spec=spec)


def hybrid_engine(**kw):
    """Two layers, one of each kind (KDA + dense, MLA + routed): the hybrid
    stack's decode program, here with a decode block."""
    from apex_tpu.models.hybrid import init_params
    from benchmark.drivers import ling_serve

    from tests.test_serve_hybrid import tiny

    pcfg = ling_serve.program_config(tiny(
        num_hidden_layers=2, layer_group_size=2, first_k_dense_replace=1))
    serve = dict(page_size=8, num_pages=65, max_batch=3, max_pages_per_seq=16,
                 prefill_buckets=(32, 64), verify=False)
    serve.update(kw)
    return InferenceEngine(pcfg, init_params(pcfg, seed=11),
                           ServeConfig(**serve))


def requests(n, *, vocab=64, new=(6, 14), temps=(0.0,), seed=3):
    rs = np.random.RandomState(seed)
    return [
        Request(prompt=[int(t) for t in rs.randint(0, vocab, rs.randint(4, 20))],
                max_new_tokens=int(rs.randint(*new)),
                temperature=temps[i % len(temps)], stream_seed=200 + i)
        for i in range(n)
    ]


def trickle(sched, reqs, every=4, max_steps=2000):
    """An open loop under capacity: one request every ``every`` decode
    calls (at once where nothing runs) — the same arrivals on either path,
    whose steps differ (a step that only reads the call in flight)."""
    todo, due = list(reqs), 0
    for _ in range(max_steps):
        if todo and (sched.engine.decode_iters >= due or not sched.pending):
            sched.submit(todo.pop(0))
            due = sched.engine.decode_iters + every
        if not todo and not sched.pending:
            return
        sched.step()
    raise RuntimeError("the trickle did not drain")


def sync_path(monkeypatch):
    monkeypatch.setattr(ContinuousBatchingScheduler, "_can_overlap",
                        lambda self, ending=False: False)


def watch(sched):
    """Each decode call's kind and what the scheduler saw at its dispatch."""
    eng, seen = sched.engine, []
    real = eng.decode

    def decode(*a, **k):
        seen.append(dict(
            lazy=bool(k.get("lazy")), overlapped=k.get("carry") is not None,
            queue=len(sched.queue),
            prefilling=any(r is not None and r.status == PREFILLING
                           for r in sched.slots)))
        return real(*a, **k)

    eng.decode = decode
    return seen


def counter(reg, name):
    reg.fetch()
    return reg.values()[name]


# ---------------------------------------------------------------------------
# the served streams are the synchronous path's
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", ["gpt_greedy", "gpt_sampled", "hybrid_block"])
def test_overlap_serves_the_sync_paths_streams(gpt, monkeypatch, case):
    def serve():
        if case == "hybrid_block":
            eng = hybrid_engine(decode_block=4)
            reqs = requests(5, vocab=96, new=(6, 20), temps=(0.0, 0.8))
        else:
            eng = gpt_engine(gpt)
            reqs = requests(6, temps=(0.0,) if case == "gpt_greedy"
                            else (0.9, 0.0, 1.3))
        reg = MetricRegistry(fetch_every=1)
        sched = ContinuousBatchingScheduler(eng, registry=reg)
        trickle(sched, reqs)
        assert all(r.status == "done" for r in reqs)
        assert all(len(r.tokens) == r.max_new_tokens for r in reqs)
        assert eng.pool.in_use == 0 and not eng.unread_decodes
        return ([r.tokens for r in reqs], eng.decode_iters,
                counter(reg, "serve/decode_overlapped"))

    overlapped, calls, n_over = serve()
    with monkeypatch.context() as m:
        sync_path(m)
        plain, sync_calls, n_sync = serve()
    assert overlapped == plain
    assert calls == sync_calls          # no EOS: not one call more
    assert n_sync == 0 and n_over > 0


# ---------------------------------------------------------------------------
# the rule, from what the scheduler observes
# ---------------------------------------------------------------------------


def test_rule_engages_on_an_open_loop_trickle(gpt):
    sched = ContinuousBatchingScheduler(gpt_engine(gpt), registry=None)
    seen = watch(sched)
    trickle(sched, requests(6, new=(10, 16)), every=6)
    over = sum(c["overlapped"] for c in seen)
    # every call but the ones beside an arrival and a request's last
    assert over >= 0.5 * len(seen), seen


def test_rule_holds_off_while_a_request_waits(gpt):
    sched = ContinuousBatchingScheduler(gpt_engine(gpt, max_batch=2),
                                        registry=None)
    seen = watch(sched)
    for r in requests(6, new=(8, 12)):
        sched.submit(r)
    sched.run()
    assert len(sched.completed) == 6
    waited = [c for c in seen if c["queue"]]
    assert waited and not any(c["lazy"] or c["overlapped"] for c in waited)
    # once the queue emptied, the last requests' calls overlapped
    assert any(c["overlapped"] for c in seen)


def test_rule_holds_off_when_a_rider_ends_with_the_call(gpt):
    """Two tokens a request: the first decode call ends every rider by its
    budget, so it is read before the step returns (a slot about to be
    refilled wants its read now)."""
    sched = ContinuousBatchingScheduler(gpt_engine(gpt), registry=None)
    seen = watch(sched)
    trickle(sched, requests(4, new=(2, 3)), every=1)
    assert seen and not any(c["lazy"] or c["overlapped"] for c in seen)


def test_rule_holds_off_under_speculation(gpt):
    sp = spec_lib.SpecConfig(draft_params=None, k=2, mode="greedy")
    reg = MetricRegistry(fetch_every=1)
    sched = ContinuousBatchingScheduler(gpt_engine(gpt, spec=sp),
                                        registry=reg)
    trickle(sched, requests(3, new=(8, 12)))
    assert len(sched.completed) == 3
    assert counter(reg, "serve/spec_rounds") > 0
    assert counter(reg, "serve/decode_overlapped") == 0


def test_rule_holds_off_during_chunked_prefill(gpt):
    sched = ContinuousBatchingScheduler(
        gpt_engine(gpt), registry=None, prefill_chunk_tokens=8)
    seen = watch(sched)
    rs = np.random.RandomState(4)
    long = [Request(prompt=[int(t) for t in rs.randint(0, 64, 40)],
                    max_new_tokens=12) for _ in range(3)]
    trickle(sched, long, every=3)
    assert len(sched.completed) == 3
    chunking = [c for c in seen if c["prefilling"]]
    assert chunking and not any(c["lazy"] or c["overlapped"]
                                for c in chunking)
    assert any(c["overlapped"] for c in seen)


# ---------------------------------------------------------------------------
# the streams' semantics under overlap
# ---------------------------------------------------------------------------


def test_eos_is_acted_on_one_call_late_and_the_extra_token_dropped(
        gpt, monkeypatch):
    def serve(eos):
        eng = gpt_engine(gpt)
        sched = ContinuousBatchingScheduler(eng, registry=None)
        req = sched.submit(Request(prompt=list(range(3, 12)),
                                   max_new_tokens=12, eos_token=eos))
        sched.run()
        sched.leak_check()
        assert req.status == "done" and eng.pool.in_use == 0
        assert not eng.unread_decodes
        return req.tokens, eng.decode_iters

    with monkeypatch.context() as m:
        sync_path(m)
        stream, _ = serve(None)
    eos = next(t for i, t in enumerate(stream) if i >= 2
               and t not in stream[:i])
    cut = stream[: stream.index(eos) + 1]
    with monkeypatch.context() as m:
        sync_path(m)
        plain, sync_calls = serve(eos)
    got, calls = serve(eos)
    assert got == plain == cut
    # the call after the EOS went out before the EOS was read: its token
    # was dropped, and its K/V write landed on a page the request still
    # held at that call's dispatch
    assert calls == sync_calls + 1


def test_decode_poison_sheds_only_the_poisoned_request(gpt):
    reg = MetricRegistry(fetch_every=1)
    sched = ContinuousBatchingScheduler(gpt_engine(gpt, max_batch=2),
                                        registry=reg)
    victim, bystander = requests(2, new=(10, 11))
    sched.submit(victim)
    sched.submit(bystander)
    with chaos.inject(chaos.Fault(
        chaos.SERVE_DECODE, steps=(2,), mode="nan", max_hits=1,
    )):
        sched.run()
    assert counter(reg, "serve/decode_overlapped") > 0
    assert victim.status == "shed" and victim.shed_reason == "poisoned"
    assert bystander.status == "done" and len(bystander.tokens) == 10
    assert sched.pool.in_use == 0
    assert counter(reg, "serve/shed") == 1.0


def test_drain_resolves_the_call_in_flight(gpt):
    eng = gpt_engine(gpt)
    sched = ContinuousBatchingScheduler(eng, registry=None)
    reqs = [sched.submit(r) for r in requests(2, new=(8, 12))]
    while sched._flight is None:
        sched.step()
    assert sched.pending
    report = sched.drain()
    assert report["drained"] and report["pool_in_use"] == 0
    assert not sched.pending and sched._flight is None
    assert not eng.unread_decodes
    assert all(r.status == "done" and len(r.tokens) == r.max_new_tokens
               for r in reqs)


def test_decode_fault_lands_the_call_in_flight_then_retries(
        gpt, monkeypatch):
    """A raise at the launch of the call after the one in flight: that call
    is read first, then every rider retries with its prefix — the streams
    are the fault-free ones."""
    def serve(faults):
        eng = gpt_engine(gpt)
        sched = ContinuousBatchingScheduler(eng, registry=None)
        reqs = [sched.submit(r) for r in requests(2, new=(8, 9))]
        with chaos.inject(*faults):
            sched.run()
        assert sched.pool.in_use == 0 and not eng.unread_decodes
        return [r.tokens for r in reqs], eng.rebuilds

    clean, _ = serve(())
    faulted, rebuilds = serve((chaos.Fault(
        chaos.SERVE_DECODE, steps=(3,), mode="raise", max_hits=1),))
    assert faulted == clean and rebuilds == 1


def _by_parent(phases):
    kids = {}
    for e in phases:
        kids.setdefault(e["parent"], []).append(e)
    return {k: sorted(v, key=lambda e: e["t0"]) for k, v in kids.items()}


LAUNCH = [("engine/dispatch", {"program": "decode"})]
READS = [("engine/read", {"program": "decode", "what": "tokens"}),
         ("engine/read", {"program": "decode", "what": "finite"})]


def test_each_decode_phase_launches_reads_or_both_as_its_call_went(gpt):
    """Under an ``engine/decode`` that launched (it carries ``overlapped``):
    the launch then the two reads of the call itself where it was read at
    once, or of the call before it where ``overlapped`` (read behind the
    launch); the launch alone where the call was left in flight with none
    before it.  A phase with no launch (and no flag) reads one call left in
    flight, in a later step that launches nothing.  Every call is read
    once, and a step reads one call at most, so no call's tokens wait for
    the program after it."""
    rec = SpanRecorder(capacity=8192)
    sched = ContinuousBatchingScheduler(gpt_engine(gpt), registry=None,
                                        spans=rec)
    seen = watch(sched)
    trickle(sched, requests(5, new=(8, 14)))
    phases = [e for e in rec.snapshot() if "id" in e]
    kids = _by_parent(phases)

    def got(e):
        return [(c["name"], c["args"]) for c in kids.get(e["id"], [])]

    decode = sorted((e for e in phases if e["name"] == "engine/decode"),
                    key=lambda e: e["t0"])
    launched = [e for e in decode if "overlapped" in e["args"]]
    assert len(launched) == len(seen) == sched.engine.decode_iters
    launch_of, read_in = {}, {}
    for e, c in zip(launched, seen):
        call = e["args"]["iter"]
        assert e["args"]["overlapped"] is c["overlapped"]
        assert c["lazy"] or not c["overlapped"]
        assert got(e) == (LAUNCH if c["lazy"] and not c["overlapped"]
                          else LAUNCH + READS)
        launch_of[call] = e
        if c["overlapped"]:
            # the call before it, left in flight by the step before
            assert call - 1 in launch_of and call - 1 not in read_in
            assert launch_of[call - 1]["parent"] != e["parent"]
            read_in[call - 1] = e
        elif not c["lazy"]:
            read_in[call] = e
    for e in decode:
        if "overlapped" in e["args"]:
            continue
        assert got(e) == READS
        call = e["args"]["iter"]
        assert call not in read_in
        read_in[call] = e
        assert e["parent"] != launch_of[call]["parent"]
        assert e["t0"] > launch_of[call]["t1"]
        assert not [x for x in launched if x["parent"] == e["parent"]]
    assert sorted(read_in) == sorted(launch_of)
    assert sum(c["overlapped"] for c in seen) > 0
    for step in (e for e in phases if e["name"] == "serve/step"):
        reads = [c for c in kids.get(step["id"], [])
                 if c["name"] == "engine/decode"
                 and ("engine/read", READS[0][1]) in got(c)]
        assert len(reads) <= 1


def test_routed_counts_booked_at_each_dispatch_are_the_sync_paths(
        monkeypatch):
    """A routed model's MoE counts, asked of the engine right after each
    decode call returns (as a caller that books them per call does): a call
    left in flight is read for them, so each call is booked its own counts
    — the synchronous path's, call for call — and the scheduler folds the
    same totals."""
    def serve():
        eng = hybrid_engine(decode_block=4)
        reg = MetricRegistry(fetch_every=1)
        sched = ContinuousBatchingScheduler(eng, registry=reg)
        booked, real = [], eng.decode

        def decode(*a, **k):
            out = real(*a, **k)
            booked.append(tuple(int(v) for v in eng.last_moe_counts))
            return out

        eng.decode = decode
        reqs = requests(5, vocab=96, new=(6, 20), temps=(0.0, 0.8))
        trickle(sched, reqs)
        assert all(r.status == "done" for r in reqs)
        assert not eng.unread_decodes
        return (booked, [r.tokens for r in reqs],
                counter(reg, "serve/moe/routed_local_tokens"),
                counter(reg, "serve/moe/experts_touched"),
                counter(reg, "serve/decode_overlapped"))

    *overlapped, n_over = serve()
    with monkeypatch.context() as m:
        sync_path(m)
        *plain, n_sync = serve()
    assert overlapped == plain
    assert n_over > 0 and n_sync == 0


def test_overlapped_counter_counts_what_the_spans_say(gpt):
    rec = SpanRecorder(capacity=8192)
    reg = MetricRegistry(fetch_every=1)
    sched = ContinuousBatchingScheduler(gpt_engine(gpt), registry=reg,
                                        spans=rec)
    trickle(sched, requests(5, new=(8, 14)))
    spans = [e for e in rec.snapshot() if e["name"] == "engine/decode"]
    flagged = [e for e in spans if "overlapped" in e.get("args", {})]
    n = counter(reg, "serve/decode_overlapped")
    assert n > 0
    assert n == sum(e["args"]["overlapped"] for e in flagged)
    # every launch carries the flag; a read on its own carries none
    assert len(flagged) == sched.engine.decode_iters
