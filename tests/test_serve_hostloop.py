"""The serving host loop launches nothing on the device but the step
programs (ISSUE 32): scheduler counters are Python numbers, sampling
keys are folded inside the compiled steps, and a call's host arrays go
over in one transfer (one packed vector).  CPU, counts only.

The recorded counters and token streams below were read from the
PARENT commit (19f0938, eager host-side folds and device-side
counters) running the same fixed scripts: the keys are threefry on
integers, so every sampled token must be bit-identical.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.gpt import GptConfig, GptModel
from apex_tpu.observability import MetricRegistry
from apex_tpu.serve import (
    ContinuousBatchingScheduler,
    InferenceEngine,
    Request,
    ServeConfig,
)
from apex_tpu.serve import spec as spec_lib

SAMPLE_SEED = 11

#: (prompt length, max_new_tokens, temperature) — the fixed script
SCRIPT = [(5, 4, 0.0), (11, 6, 0.9), (3, 2, 0.0), (17, 5, 1.3), (8, 1, 0.7)]

#: every counter the parent's registry read after the script
PARENT_COUNTERS = {
    "serve/admitted": 5.0, "serve/completed": 5.0,
    "serve/decode_steps": 8.0, "serve/prefills": 5.0,
    "serve/tokens_out": 18.0,
}
#: the parent's token streams: plain scheduler, chunked prefill
#: (``prefill_chunk_tokens=8``), speculative ``k=2`` temperature mode
PARENT_STREAMS = {
    "plain": [[56, 56, 56, 56], [39, 50, 55, 26, 5, 52], [46, 46],
              [42, 47, 24, 28, 31], [36]],
    "chunked": [[56, 56, 56, 56], [22, 14, 55, 26, 5, 52], [46, 46],
                [6, 47, 24, 18, 31], [49]],
    "spec": [[56, 56, 56, 56], [39, 58, 38, 26, 43, 18], [46, 46],
             [42, 38, 42, 18, 30], [36]],
}
#: the parent's engine alone: a temperature-1.1 prefill of nine tokens,
#: then five ``decode`` calls WITHOUT ``streams`` (the legacy key chain)
PARENT_LEGACY_STREAM = [52, 24, 52, 49, 38, 48]


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


def make_engine(gpt, *, spec=None, registry=None, **serve_kw):
    cfg, params = gpt
    kw = dict(
        page_size=8, num_pages=32, max_batch=2, max_pages_per_seq=8,
        verify=False, sample_seed=SAMPLE_SEED,
    )
    kw.update(serve_kw)
    return InferenceEngine(
        cfg, params, ServeConfig(**kw), spec=spec, registry=registry
    )


def script_requests():
    rs = np.random.RandomState(5)
    return [
        Request(
            prompt=[int(t) for t in rs.randint(0, 64, size=n)],
            max_new_tokens=m, temperature=t, stream_seed=100 + i,
        )
        for i, (n, m, t) in enumerate(SCRIPT)
    ]


# ---------------------------------------------------------------------------
# (a) the scheduler's metric state is host numbers
# ---------------------------------------------------------------------------


class TestHostCounters:
    def test_registry_host_fold_by_kind(self):
        reg = MetricRegistry(fetch_every=1)
        reg.counter("c")
        reg.gauge("g")
        reg.minimum("lo")
        reg.maximum("hi")
        state = reg.host_init()
        assert state == {
            "c": 0.0, "g": 0.0, "lo": float("inf"), "hi": float("-inf"),
        }
        for v in (3, 1.5, True):
            reg.host_update(state, {"c": v, "g": v, "lo": v, "hi": v})
        assert state == {"c": 5.5, "g": 1.0, "lo": 1.0, "hi": 3.0}
        assert all(type(v) is float for v in state.values())
        # the same folds as the in-jit update
        dev = reg.init()
        for v in (3, 1.5, True):
            dev = reg.update(dev, {"c": v, "g": v, "lo": v, "hi": v})
        assert {k: float(v) for k, v in dev.items()} == state
        with pytest.raises(KeyError, match="not declared"):
            reg.host_update(state, {"typo": 1})
        # observe copies: a later fold must not reach the stash
        reg.observe(0, state)
        reg.host_update(state, {"c": 1})
        assert reg.fetch()["c"] == 5.5

    def test_step_leaves_python_numbers_and_the_parents_counters(self, gpt):
        reg = MetricRegistry(fetch_every=1)
        sched = ContinuousBatchingScheduler(make_engine(gpt), registry=reg)
        for r in script_requests():
            sched.submit(r)
        saw_steady = False
        while sched.queue or sched.running:
            done, admitted = len(sched.completed), sched.engine.prefill_calls
            riders_before = len(sched.running)
            sched.step()
            # every value of the metric state is a Python number: no
            # device array, no numpy scalar, after any kind of step
            assert all(
                type(v) in (float, int) for v in sched._mstate.values()
            ), {k: type(v) for k, v in sched._mstate.items()}
            saw_steady |= (
                riders_before > 0
                and sched.engine.prefill_calls > admitted
                and len(sched.completed) > done
            )
        # the script holds a step with live riders, an admission and a
        # completion all at once
        assert saw_steady
        reg.fetch()
        vals = reg.values()
        counters = {k: v for k, v in vals.items() if reg.kind(k) == "counter"}
        expected = dict.fromkeys(counters, 0.0)
        expected.update(PARENT_COUNTERS)
        # the one counter the parent had not: two of the eight decode calls
        # went out with the call before them unread, once the queue was
        # empty (docs/serving.md "Host phases")
        expected["serve/decode_overlapped"] = 2.0
        assert counters == expected
        assert vals["serve/ttft_ms"] > 0.0
        assert vals["serve/ttft_prefill_ms_p95"] > 0.0

    @pytest.mark.parametrize("path", ["pallas", "jnp"])
    def test_walk_live_share_is_folded_from_the_steps_lengths(
        self, gpt, monkeypatch, path
    ):
        """`serve/decode_walk_live_share`: live pages over the pages the
        decode kernel's walk copies in, from the lengths of the step's
        decode call — a Python float, like every other value, reckoned
        by the kernel's own helper, and published only when the decode
        program's attention took the kernel: the jnp path walks nothing."""
        from apex_tpu.ops import _dispatch
        from apex_tpu.ops.paged_attention import walk_live_share

        reg = MetricRegistry(fetch_every=1)
        sched = ContinuousBatchingScheduler(make_engine(gpt), registry=reg)
        pool = sched.engine.cache["k"]
        width = sched.serve.max_pages_per_seq
        assert pool.shape[3] == 8 and width == 8  # one step of 8 pages
        monkeypatch.setattr(
            _dispatch, "last_paths", lambda: {"paged_decode_attention": path})
        calls = []
        decode = sched.engine.decode
        sched.engine.decode = lambda tokens, lengths, *a, **kw: (
            calls.append(np.array(lengths)) or decode(
                tokens, lengths, *a, **kw))
        for r in script_requests():
            sched.submit(r)
        seen = set()
        while sched.queue or sched.running:
            n = len(calls)
            sched.step()
            if len(calls) > n:
                got = sched._mstate["serve/decode_walk_live_share"]
                if path == "pallas":
                    want = walk_live_share(calls[-1], pool, width)
                    assert type(got) is float and got == want
                    assert 1.0 / 8 <= got <= 1.0
                seen.add(got)
        # it moved with the riders' lengths, or was never published
        assert len(seen) > 1 if path == "pallas" else seen == {0.0}

    def test_fleet_ledger_is_host_numbers(self, gpt):
        import time

        from apex_tpu.fleetctl import EngineReplica, Fleet

        def factory(name):
            eng = make_engine(gpt, registry=MetricRegistry(fetch_every=1))
            return EngineReplica(name, eng, clock=time.monotonic)

        fleet = Fleet(factory, replicas=1)
        fleet.submit(Request(prompt=[1, 2, 3], max_new_tokens=2))
        for _ in range(6):
            fleet.step()
        assert all(type(v) is float for v in fleet._mstate.values())
        vals = fleet.registry.fetch()
        assert vals["fleet/submitted"] == 1.0
        assert vals["fleet/routed"] == 1.0
        assert vals["fleet/replicas_live"] == 1.0


# ---------------------------------------------------------------------------
# (b) the host path folds no key and stages with one transfer
# ---------------------------------------------------------------------------


class _HostPathGuard:
    """Once the engine is built: ``jax.random.fold_in`` raises, no
    ``jax.device_put`` may be called, every compiled program must be
    handed exactly ONE host array (its packed argument vector — the
    call's one transfer), and any other host-to-device contact (a
    ``jnp.asarray`` of a scalar, an eager op on a numpy operand) is
    disallowed by JAX's own transfer guard."""

    def __init__(self, monkeypatch, eng):
        self.calls = 0
        real_put = jax.device_put
        guard = self

        class OneHostArg:
            def __init__(self, compiled):
                self.compiled = compiled

            def __call__(self, *args):
                host = [
                    x for x in jax.tree_util.tree_leaves(args)
                    if not isinstance(x, jax.Array)
                ]
                assert len(host) == 1, [type(x) for x in host]
                assert type(host[0]) is np.ndarray
                assert host[0].dtype == np.int32 and host[0].ndim == 1
                guard.calls += 1
                # hand the vector over explicitly, so the guard below
                # can disallow every IMPLICIT transfer around the call
                return self.compiled(*(
                    real_put(a) if a is host[0] else a for a in args
                ))

        for key, compiled in eng._programs.items():
            eng._programs[key] = OneHostArg(compiled)

        def no_put(*_a, **_k):
            raise AssertionError("the host path called jax.device_put")

        def no_fold(*_a, **_k):
            raise AssertionError("the host path folded a sampling key")

        monkeypatch.setattr(jax, "device_put", no_put)
        monkeypatch.setattr(jax.random, "fold_in", no_fold)

    def one_transfer(self, call, programs=1):
        before = self.calls
        with jax.transfer_guard_host_to_device("disallow"):
            out = call()
        if callable(programs):
            programs = programs()
        assert self.calls - before == programs, self.calls - before
        return out


def _decode_args(eng, pages, ctx, token):
    b, mp = eng.serve.max_batch, eng.serve.max_pages_per_seq
    tokens = np.zeros((b,), np.int32)
    lengths = np.zeros((b,), np.int32)
    table = np.zeros((b, mp), np.int32)
    tokens[0], lengths[0] = token, ctx + 1
    table[0, : len(pages)] = pages
    return tokens, lengths, table


class TestHostPathLaunchesNothing:
    @pytest.mark.parametrize(
        "call", ["prefill", "decode_streams", "decode_legacy",
                 "chunk_prefill", "fork_page"],
    )
    def test_engine_call_folds_nothing_and_stages_once(
        self, gpt, monkeypatch, call
    ):
        eng = make_engine(gpt).build(chunked=True)
        pages = eng.pool.alloc(3)
        prompt = list(range(1, 10))
        eng.prefill(prompt, pages[:2], temperature=0.8)
        guard = _HostPathGuard(monkeypatch, eng)
        temps = np.array([0.8, 0.0], np.float32)
        if call == "prefill":
            _, tok = guard.one_transfer(
                lambda: eng.prefill(prompt, pages[:2], temperature=0.8)
            )
        elif call == "decode_streams":
            args = _decode_args(eng, pages, len(prompt), 7)
            _, toks = guard.one_transfer(lambda: eng.decode(
                *args, temps, streams=np.array([3, 0], np.uint32),
                gens=np.array([1, 0], np.int32),
            ))
            tok = toks[0]
        elif call == "decode_legacy":
            args = _decode_args(eng, pages, len(prompt), 7)
            _, toks = guard.one_transfer(lambda: eng.decode(*args, temps))
            tok = toks[0]
        elif call == "chunk_prefill":
            _, tok = guard.one_transfer(lambda: eng.chunk_prefill(
                list(range(20, 28)), 8, pages, pages[1:2],
                temperature=0.8,
            ))
        else:
            guard.one_transfer(lambda: eng.fork_page(pages[0], pages[2]))
            tok = 0
        assert 0 <= int(tok) < eng.cfg.vocab_size
        # no executable refused a call, and none was compiled again
        assert eng.retraces == 0
        assert set(eng.compile_counts.values()) == {1}

    def test_speculative_round_folds_nothing_and_stages_once(
        self, gpt, monkeypatch
    ):
        sp = spec_lib.SpecConfig(draft_params=None, k=2, mode="temperature")
        eng = make_engine(gpt, spec=sp).build()
        pages, dpages = eng.pool.alloc(3), eng.pool.alloc(3)
        prompt = list(range(1, 10))
        eng.prefill(prompt, pages[:2], temperature=0.8)
        eng.draft_prefill(prompt, dpages[:2])
        guard = _HostPathGuard(monkeypatch, eng)
        tokens, lengths, table = _decode_args(eng, pages, len(prompt), 7)
        dtable = np.zeros_like(table)
        dtable[0, :3] = dpages
        # a round is two programs (draft, verify), a vector each
        out, acc, finite = guard.one_transfer(lambda: eng.spec_step(
            tokens, lengths, table, dtable,
            np.array([0.8, 0.0], np.float32),
            np.array([3, 0], np.uint32), np.array([1, 0], np.int32),
        ), programs=2)
        assert out.shape == (2, 3) and 0 <= acc[0] <= 2 and finite[0]
        starts = np.array([len(prompt) + 1, 0], np.int32)
        counts = np.array([1, 0], np.int32)
        guard.one_transfer(lambda: eng.rollback(starts, counts, table))
        guard.one_transfer(
            lambda: eng.draft_rollback(starts, counts, dtable)
        )
        guard.one_transfer(lambda: eng.draft_prefill(prompt, dpages[:2]))
        assert eng.retraces == 0

    def test_scheduler_step_makes_no_other_device_contact(
        self, gpt, monkeypatch
    ):
        """A whole ``sched.step()`` — admission, decode, retire,
        publish — puts nothing on the device but each engine call's
        one vector, and runs no eager fold."""
        reg = MetricRegistry(fetch_every=1)
        eng = make_engine(gpt, registry=reg).build()
        sched = ContinuousBatchingScheduler(eng, registry=reg)
        for r in script_requests():
            sched.submit(r)
        guard = _HostPathGuard(monkeypatch, eng)
        while sched.queue or sched.running:
            calls = eng.prefill_calls + eng.decode_iters
            guard.one_transfer(
                sched.step,
                programs=lambda: eng.prefill_calls + eng.decode_iters - calls,
            )
        assert [r.tokens for r in sched.completed] and not sched.shed


# ---------------------------------------------------------------------------
# retraces are counted where the executable refuses a call (ISSUE 39)
# ---------------------------------------------------------------------------


class TestRetraceCount:
    def test_steady_run_counts_none(self, gpt):
        eng = make_engine(gpt).build()
        sched = ContinuousBatchingScheduler(eng, registry=None)
        for r in script_requests():
            sched.submit(r)
        while sched.queue or sched.running:
            sched.step()
        assert len(sched.completed) == len(SCRIPT) and not sched.shed
        assert eng.retraces == 0
        assert set(eng.compile_counts.values()) == {1}

    @pytest.mark.parametrize("call", ["decode", "prefill"])
    def test_refused_vector_counts_one_and_raises(
        self, gpt, monkeypatch, call
    ):
        """A host vector one word short: the executable raises
        ``TypeError`` before it runs, the engine counts it once and lets
        it go on; nothing was donated, so the next call is served."""
        eng = make_engine(gpt).build()
        pages = eng.pool.alloc(2)
        prompt = list(range(1, 10))
        args = _decode_args(eng, pages, len(prompt), 7)
        layout = eng._host_args(call, None if call == "decode" else 16)
        real_pack = layout.pack
        monkeypatch.setattr(layout, "pack", lambda *v: real_pack(*v)[:-1])
        with pytest.raises(TypeError):
            if call == "decode":
                eng.decode(*args)
            else:
                eng.prefill(prompt, pages)
        assert eng.retraces == 1
        monkeypatch.setattr(layout, "pack", real_pack)
        eng.prefill(prompt, pages)
        eng.decode(*args)
        assert eng.retraces == 1
        assert set(eng.compile_counts.values()) == {1}

    def test_speculative_round_phases(self, gpt):
        """The round's draft program dispatches while the verify call is
        staged; the verify call dispatches and reads its tokens, its
        acceptance counts and its screen under ``engine/decode``."""
        from apex_tpu.observability import SpanRecorder

        sp = spec_lib.SpecConfig(draft_params=None, k=2, mode="temperature")
        eng = make_engine(gpt, spec=sp).build()
        eng.spans = rec = SpanRecorder(capacity=256)
        pages, dpages = eng.pool.alloc(3), eng.pool.alloc(3)
        prompt = list(range(1, 10))
        eng.prefill(prompt, pages[:2])
        eng.draft_prefill(prompt, dpages[:2])
        tokens, lengths, table = _decode_args(eng, pages, len(prompt), 7)
        dtable = np.zeros_like(table)
        dtable[0, :3] = dpages
        eng.spec_step(tokens, lengths, table, dtable, None,
                      np.array([3, 0], np.uint32), np.array([1, 0], np.int32))
        spans = [e for e in rec.snapshot() if "id" in e]
        kids = {}
        for e in spans:
            kids.setdefault(e["parent"], []).append(
                (e["name"], e.get("args")))
        by_name = {e["name"]: e for e in spans}
        stage = [e for e in spans if e["name"] == "engine/stage"][-1]
        assert stage["args"] == {"program": "spec"}
        assert kids[stage["id"]] == [
            ("engine/dispatch", {"program": "draft_decode"})]
        assert kids[by_name["engine/decode"]["id"]] == [
            ("engine/dispatch", {"program": "verify"}),
            ("engine/read", {"program": "verify", "what": "tokens"}),
            ("engine/read", {"program": "verify", "what": "accepted"}),
            ("engine/read", {"program": "verify", "what": "finite"}),
        ]
        assert eng.retraces == 0


# ---------------------------------------------------------------------------
# (c) the keys, and therefore the sampled tokens, did not change
# ---------------------------------------------------------------------------


class TestKeysUnchanged:
    def test_lax_fold_in_equals_jax_random_fold_in(self):
        """``model.fold_in`` (threefry written in lax primitives, cheap
        to lower) gives ``jax.random.fold_in``'s key bit for bit: one
        base key or a key batch, uint32 and int32 data, the edges of
        both ranges, a scalar."""
        from apex_tpu.serve import model as model_lib

        base = jax.random.PRNGKey(SAMPLE_SEED)
        vals = [0, 1, 7, 104, 255, 1024, 2**31 - 1, 2**31, 2**32 - 1]
        data = np.array(vals, np.uint32)
        fold = jax.jit(model_lib.fold_in)
        one = np.asarray(fold(base, data))
        keys = np.asarray(fold(base, data[::-1].copy()))
        each = np.asarray(fold(keys, data))
        signed = np.asarray(fold(base, data.view(np.int32)))
        for i, v in enumerate(vals):
            want = np.asarray(jax.random.fold_in(base, v))
            np.testing.assert_array_equal(one[i], want)
            np.testing.assert_array_equal(signed[i], want)
            np.testing.assert_array_equal(
                each[i], np.asarray(jax.random.fold_in(keys[i], v))
            )
        np.testing.assert_array_equal(
            np.asarray(fold(base, np.int32(5))),
            np.asarray(jax.random.fold_in(base, 5)),
        )

    def test_in_program_keys_equal_the_eager_folds(self):
        from apex_tpu.serve import model as model_lib

        base = jax.random.PRNGKey(SAMPLE_SEED)
        streams = np.array([0, 1, 104, 0x7FFFFFFF, 2**32 - 1], np.uint32)
        gens = np.array([0, 7, 255, 1024, 2**31 - 1], np.int32)
        got = np.asarray(jax.jit(model_lib.slot_keys)(base, streams, gens))
        stream_keys = np.asarray(
            jax.jit(model_lib.stream_keys)(base, streams)
        )
        for i, (s, g) in enumerate(zip(streams, gens)):
            sk = jax.random.fold_in(base, int(s))
            np.testing.assert_array_equal(stream_keys[i], np.asarray(sk))
            np.testing.assert_array_equal(
                got[i], np.asarray(jax.random.fold_in(sk, int(g)))
            )

    def test_prefill_program_folds_the_call_index(self, gpt):
        """``serve_prefill_*`` samples under ``fold_in(base, call)``:
        its in-step token equals sampling the returned logits eagerly
        under that key, for a handful of call indices."""
        from apex_tpu.serve import model as model_lib

        eng = make_engine(gpt)
        pages = eng.pool.alloc(2)
        prompt = list(range(3, 12))
        for _ in range(4):
            call = eng.prefill_calls
            logits, tok = eng.prefill(prompt, pages, temperature=1.5)
            key = jax.random.fold_in(jax.random.PRNGKey(SAMPLE_SEED), call)
            want = model_lib.sample_tokens(
                jnp.asarray(logits)[None], jnp.asarray([1.5]), key
            )
            assert tok == int(want[0])

    @pytest.mark.parametrize("path", ["plain", "chunked", "spec"])
    def test_sampled_streams_equal_the_parents(self, gpt, path):
        spec = None
        kw = {}
        if path == "spec":
            spec = spec_lib.SpecConfig(
                draft_params=None, k=2, mode="temperature"
            )
        if path == "chunked":
            kw["prefill_chunk_tokens"] = 8
        sched = ContinuousBatchingScheduler(
            make_engine(gpt, spec=spec), registry=None, **kw
        )
        reqs = [sched.submit(r) for r in script_requests()]
        sched.run()
        assert [list(r.tokens) for r in reqs] == PARENT_STREAMS[path]

    def test_legacy_decode_stream_equals_the_parents(self, gpt):
        eng = make_engine(gpt)
        pages = eng.pool.alloc(2)
        rs = np.random.RandomState(9)
        prompt = [int(t) for t in rs.randint(0, 64, size=9)]
        _, first = eng.prefill(prompt, pages, temperature=1.1)
        toks = [first]
        temps = np.array([1.1, 0.0], np.float32)
        for i in range(5):
            args = _decode_args(eng, pages, len(prompt) + i, toks[-1])
            _, nxt = eng.decode(*args, temps)
            toks.append(int(nxt[0]))
        assert toks == PARENT_LEGACY_STREAM
