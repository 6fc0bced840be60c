"""Serving subsystem — paged cache, AOT engine, continuous batching.

Covers the ISSUE 7 acceptance surface: page-pool alloc/free/exhaustion
+ shedding, continuous-batching admission order and mid-stream
admission (batch fill above the single-request baseline), prefill and
decode numerics against the UNPAGED ``GptModel.apply`` reference at f32
and int8-KV, the ``analysis.check`` zero-ERROR pin on both AOT step
programs, and the serving watchdog rules.  The decode-attention kernel
parity tests live beside the flash-attention tests
(``tests/test_attention.py::TestPagedDecodeAttention``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.gpt import GptConfig, GptModel, _tied_vocab_logits
from apex_tpu.serve import (
    ContinuousBatchingScheduler,
    InferenceEngine,
    NULL_PAGE,
    PagePool,
    Request,
    ServeConfig,
)
from apex_tpu.serve import cache as cache_lib
from apex_tpu.serve import model as serve_model

#: pinned serving-numerics envelopes on last-position logits vs the
#: unpaged f32 reference (tools/serve_bench.py pins the same numbers)
TOL_F32 = 2e-4
TOL_INT8_KV = 5e-2


def tiny_cfg(**kw):
    base = dict(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        intermediate_size=64, max_seq_len=128, dtype=jnp.float32,
    )
    base.update(kw)
    return GptConfig(**base)


@pytest.fixture(scope="module")
def gpt():
    cfg = tiny_cfg()
    model = GptModel(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((8, 1), jnp.int32)
    )
    return cfg, model, params


def make_engine(gpt, **serve_kw):
    cfg, _, params = gpt
    kw = dict(
        page_size=8, num_pages=32, max_batch=2, max_pages_per_seq=8,
        verify=False,
    )
    kw.update(serve_kw)
    return InferenceEngine(cfg, params, ServeConfig(**kw))


def ref_logits(model, params, token_ids):
    """Unpaged reference: full forward, all positions' logits."""
    ids = jnp.asarray(np.asarray(token_ids, np.int32)[:, None])
    h = model.apply(params, ids)
    return np.asarray(
        _tied_vocab_logits(params, model, h, sp_gathered=False)[:, 0]
    )


# ---------------------------------------------------------------------------
# page pool
# ---------------------------------------------------------------------------


class TestPagePool:
    def test_alloc_free_roundtrip(self):
        pool = PagePool(num_pages=8, page_size=4)
        assert pool.usable == 7 and pool.available == 7
        got = pool.alloc(3)
        assert len(got) == 3 and NULL_PAGE not in got
        assert pool.in_use == 3
        pool.free(got)
        assert pool.available == 7 and pool.occupancy() == 0.0

    def test_alloc_is_all_or_nothing(self):
        pool = PagePool(num_pages=4, page_size=4)
        assert pool.alloc(5) is None
        # the failed alloc must not leak pages
        assert pool.available == 3
        assert len(pool.alloc(3)) == 3
        assert pool.alloc(1) is None

    def test_double_free_and_bad_ids_raise(self):
        pool = PagePool(num_pages=8, page_size=4)
        got = pool.alloc(2)
        pool.free(got)
        with pytest.raises(ValueError, match="double free"):
            pool.free([got[0]])
        with pytest.raises(ValueError):
            pool.free([NULL_PAGE])

    def test_pages_for(self):
        pool = PagePool(num_pages=8, page_size=4)
        assert pool.pages_for(0) == 0
        assert pool.pages_for(1) == 1
        assert pool.pages_for(4) == 1
        assert pool.pages_for(5) == 2


# ---------------------------------------------------------------------------
# cache device helpers
# ---------------------------------------------------------------------------


def _read_history(kv, layer, table, heads, head_dim=None):
    """One layer's K history ``(B, H, T, D)`` f32 through ``table``."""
    from apex_tpu.ops.paged_attention import gather_history

    return np.asarray(gather_history(
        kv["k"], kv.get("k_scale"), layer, jnp.asarray(table), heads,
        head_dim,
    ))


class TestCacheWrites:
    """The pool helpers: whole pages at ``[layer, page_ids]``, in the
    lane-dense layout ``(L, P, H/G, page, W)``."""

    @pytest.mark.parametrize("h,d,g,w", [
        (2, 128, 1, 128), (4, 64, 2, 128), (4, 32, 4, 128),
        # heads that do not pair up, or no divisor of a tile: one head a
        # row, the row padded to whole 128-lane tiles
        (3, 64, 1, 128), (25, 64, 1, 128), (4, 80, 1, 128),
        (4, 96, 1, 128), (2, 192, 1, 256), (2, 256, 1, 256),
    ])
    def test_pool_shape_follows_heads_and_head_dim(self, h, d, g, w):
        kv = cache_lib.init_kv_pages(2, 5, h, 4, d, dtype=jnp.float32)
        assert kv["k"].shape == kv["v"].shape == (2, 5, h // g, 4, w)
        q = cache_lib.init_kv_pages(2, 5, h, 4, d, kv_wire="int8")
        assert q["k"].dtype == jnp.int8
        # a token a row, a head a lane, whole tiles
        assert q["k_scale"].shape == (2, 5, 1, 4, 128)

    @pytest.mark.parametrize("h,d", [(2, 128), (4, 64), (3, 64), (2, 80)])
    def test_prompt_pages_roundtrip(self, h, d):
        """pack -> write at [layer, page_ids] -> read back in table
        order; the other layer and the other pages stay untouched."""
        rs = np.random.RandomState(0)
        s, page = 16, 4
        k = jnp.asarray(rs.randn(s, h, d), jnp.float32)
        kv = cache_lib.init_kv_pages(2, 10, h, page, d, dtype=jnp.float32)
        ids = jnp.asarray([3, 5, 2, 7], jnp.int32)
        out = cache_lib.write_prompt_kv(kv, 1, ids, k, -k)
        got = _read_history(out, 1, np.asarray(ids)[None], h, d)[0]
        np.testing.assert_array_equal(
            got, np.asarray(jnp.transpose(k, (1, 0, 2)))
        )
        # a row's padding lanes stay zero
        assert not np.asarray(out["k"][..., d * (h // kv["k"].shape[2]):]).any()
        np.testing.assert_array_equal(
            np.asarray(out["v"]), -np.asarray(out["k"])
        )
        assert not np.asarray(out["k"][0]).any()
        untouched = [p for p in range(10) if p not in (3, 5, 2, 7)]
        assert not np.asarray(out["k"][1, untouched]).any()

    def test_prompt_tail_lands_in_null_page(self):
        """Null entries of ``page_ids`` dump their page (a padded tail,
        a borrowed page's re-run) into page 0 and nowhere else."""
        rs = np.random.RandomState(7)
        k = jnp.asarray(rs.randn(8, 4, 64), jnp.float32)
        kv = cache_lib.init_kv_pages(1, 6, 4, 4, 64, dtype=jnp.float32)
        out = cache_lib.write_prompt_kv(
            kv, 0, jnp.asarray([4, NULL_PAGE], jnp.int32), k, k
        )
        got = _read_history(out, 0, np.asarray([[4]]), 4)[0]
        np.testing.assert_array_equal(
            got, np.asarray(jnp.transpose(k[:4], (1, 0, 2)))
        )
        assert not np.asarray(out["k"][0, [1, 2, 3, 5]]).any()

    @pytest.mark.parametrize("h,d", [(2, 128), (4, 64), (4, 32)])
    def test_append_token_roundtrip(self, h, d):
        """The read-modify-write append puts each row at its (page,
        slot) and keeps every other row of the touched pages."""
        rs = np.random.RandomState(1)
        page = 4
        kv = cache_lib.init_kv_pages(2, 6, h, page, d, dtype=jnp.float32)
        kv = {n: jnp.asarray(rs.randn(*a.shape), a.dtype)
              for n, a in kv.items()}
        rows = jnp.asarray(rs.randn(3, h, d), jnp.float32)
        pids = jnp.asarray([1, 4, 2], jnp.int32)
        slots = jnp.asarray([0, 3, 1], jnp.int32)
        out = cache_lib.append_token_kv(kv, 1, pids, slots, rows, 2 * rows)
        before = _read_history(kv, 1, np.arange(6)[None], h)[0]
        after = _read_history(out, 1, np.arange(6)[None], h)[0]
        want = before.copy()
        for b in range(3):
            want[:, int(pids[b]) * page + int(slots[b])] = rows[b]
        np.testing.assert_array_equal(after, want)
        np.testing.assert_array_equal(
            np.asarray(out["k"][0]), np.asarray(kv["k"][0])
        )
        got_v = np.asarray(out["v"][1]) - np.asarray(kv["v"][1])
        assert np.count_nonzero(got_v) == 3 * h * d

    def test_idle_slots_share_the_null_page(self):
        """Idle slots all append into page 0 (duplicate scatter
        indices): the live slot's row still lands, and only the null
        page takes the garbage."""
        rs = np.random.RandomState(2)
        h, d, page = 4, 64, 4
        kv = cache_lib.init_kv_pages(1, 5, h, page, d, dtype=jnp.float32)
        rows = jnp.asarray(rs.randn(4, h, d), jnp.float32)
        pids = jnp.asarray([NULL_PAGE, 3, NULL_PAGE, NULL_PAGE], jnp.int32)
        slots = jnp.asarray([0, 2, 0, 0], jnp.int32)
        out = cache_lib.append_token_kv(kv, 0, pids, slots, rows, rows)
        got = _read_history(out, 0, np.asarray([[3]]), h)[0]
        np.testing.assert_array_equal(got[:, 2], np.asarray(rows[1]))
        assert not np.asarray(out["k"][0, [1, 2, 4]]).any()
        assert np.count_nonzero(got) == h * d

    def test_int8_planes_write_and_append(self):
        """Under the int8 wire the same helpers carry codes and scale
        planes: what is read back is the codec's rounding of the rows."""
        rs = np.random.RandomState(3)
        h, d, page = 4, 64, 4
        kv = cache_lib.init_kv_pages(2, 6, h, page, d, kv_wire="int8")
        k = jnp.asarray(rs.randn(8, h, d) * 3.0, jnp.float32)
        out = cache_lib.write_prompt_kv(
            kv, 1, jnp.asarray([2, 5], jnp.int32), k, k
        )
        row = jnp.asarray(rs.randn(1, h, d), jnp.float32)
        out = cache_lib.append_token_kv(
            out, 1, jnp.asarray([5], jnp.int32),
            jnp.asarray([1], jnp.int32), row, row,
        )
        want = np.asarray(jnp.transpose(k, (1, 0, 2))).copy()
        want[:, page + 1] = np.asarray(row[0])
        got = _read_history(out, 1, np.asarray([[2, 5]]), h)[0]
        step = np.abs(want).max(axis=-1, keepdims=True) / 127.0
        assert (np.abs(got - want) <= step + 1e-6).all()
        np.testing.assert_array_equal(
            np.asarray(out["v"]), np.asarray(out["k"])
        )

    def test_int8_encode_roundtrip(self):
        rs = np.random.RandomState(2)
        x = jnp.asarray(rs.randn(4, 2, 8) * 3.0, jnp.float32)
        codes, scale = cache_lib.encode_kv(x)
        assert codes.dtype == jnp.int8 and scale.shape == (4, 2)
        back = codes.astype(jnp.float32) * scale[..., None]
        assert float(jnp.abs(back - x).max()) <= float(
            jnp.abs(x).max()
        ) / 127.0 + 1e-6


# ---------------------------------------------------------------------------
# engine numerics vs the unpaged reference
# ---------------------------------------------------------------------------


class TestEngineNumerics:
    def test_prefill_matches_unpaged_reference(self, gpt):
        cfg, model, params = gpt
        eng = make_engine(gpt)
        rs = np.random.RandomState(3)
        prompt = [int(t) for t in rs.randint(0, cfg.vocab_size, size=21)]
        pages = eng.pool.alloc(eng.pool.pages_for(len(prompt)))
        logits, tok = eng.prefill(prompt, pages)
        ref = ref_logits(model, params, prompt)[-1]
        assert np.abs(logits - ref).max() <= 1e-5
        assert tok == int(np.argmax(ref))

    @pytest.mark.parametrize("kv_wire,tol", [
        ("f32", TOL_F32), ("int8", TOL_INT8_KV),
    ])
    def test_decode_matches_unpaged_reference(self, gpt, kv_wire, tol):
        """Greedy continuation through the paged decode step stays
        within the pinned envelope of the growing full forward — and
        at f32 the generated TOKENS are identical."""
        cfg, model, params = gpt
        eng = make_engine(gpt, kv_wire=kv_wire)
        rs = np.random.RandomState(4)
        prompt = [int(t) for t in rs.randint(0, cfg.vocab_size, size=13)]
        pages = eng.pool.alloc(eng.pool.pages_for(len(prompt)))
        _, tok = eng.prefill(prompt, pages)
        cur = list(prompt)
        ctx = len(prompt)
        table = np.zeros((2, 8), np.int32)
        for _ in range(5):
            if ctx // 8 >= len(pages):
                pages += eng.pool.alloc(1)
            table[0, : len(pages)] = pages
            logits, nxt = eng.decode(
                np.array([tok, 0]), np.array([ctx + 1, 0]), table
            )
            cur.append(tok)
            ref = ref_logits(model, params, cur)[-1]
            assert np.abs(logits[0] - ref).max() <= tol, kv_wire
            if kv_wire == "f32":
                assert int(nxt[0]) == int(np.argmax(ref))
            ctx += 1
            tok = int(nxt[0])

    @pytest.mark.parametrize("kv_wire,tol", [
        ("f32", TOL_F32), ("int8", TOL_INT8_KV),
    ])
    def test_decode_kernel_walks_rows_padded_to_whole_tiles(
        self, gpt, kv_wire, tol
    ):
        """Two heads of 16 lanes do not fill a lane row: the pool's rows
        are one head padded to a whole 128-lane tile, written by the
        prefill and the appends, and the decode program's attention is
        the page-walk kernel over them (forced here; interpreted) — the
        same envelope against the growing full forward."""
        from apex_tpu.ops import _dispatch

        cfg, model, params = gpt
        eng = make_engine(gpt, kv_wire=kv_wire)
        assert eng.cache["k"].shape[2:] == (2, 8, 128)
        rs = np.random.RandomState(6)
        prompt = [int(t) for t in rs.randint(0, cfg.vocab_size, size=13)]
        pages = eng.pool.alloc(eng.pool.pages_for(len(prompt)))
        _, tok = eng.prefill(prompt, pages)
        cur, ctx = list(prompt), len(prompt)
        table = np.zeros((2, 8), np.int32)
        _dispatch.set_use_pallas(True)
        try:
            for _ in range(4):
                if ctx // 8 >= len(pages):
                    pages += eng.pool.alloc(1)
                table[0, : len(pages)] = pages
                logits, nxt = eng.decode(
                    np.array([tok, 0]), np.array([ctx + 1, 0]), table
                )
                cur.append(tok)
                ref = ref_logits(model, params, cur)[-1]
                assert np.abs(logits[0] - ref).max() <= tol, kv_wire
                ctx += 1
                tok = int(nxt[0])
        finally:
            _dispatch.set_use_pallas(None)
        assert _dispatch.last_paths()["paged_decode_attention"] == "pallas"
        # the rows' padding lanes were never written
        assert not np.asarray(eng.cache["k"][..., 16:]).any()

    def test_weight_wire_int8_stays_close(self, gpt):
        cfg, model, params = gpt
        eng = make_engine(gpt, weight_wire="int8")
        rs = np.random.RandomState(5)
        prompt = [int(t) for t in rs.randint(0, cfg.vocab_size, size=9)]
        pages = eng.pool.alloc(eng.pool.pages_for(len(prompt)))
        logits, _ = eng.prefill(prompt, pages)
        ref = ref_logits(model, params, prompt)[-1]
        # int8 weights: codec noise only, scaled by logit magnitude
        assert np.abs(logits - ref).max() <= 0.15 * max(
            1.0, np.abs(ref).max()
        )

    def test_packed_weight_roundtrip(self, gpt):
        _, _, params = gpt
        q = serve_model.quantize_params(params)
        back = serve_model.dequantize_params(q)
        for a, b in zip(
            jax.tree_util.tree_leaves(params),
            jax.tree_util.tree_leaves(back),
        ):
            assert a.shape == b.shape and a.dtype == b.dtype
            scale = max(1e-6, float(jnp.abs(a).max()))
            # blockwise int8: worst-case error is one quantization step
            assert float(jnp.abs(a - b).max()) <= scale / 127.0 + 1e-6


# ---------------------------------------------------------------------------
# AOT + analysis pins
# ---------------------------------------------------------------------------


class TestEngineBuild:
    def test_analysis_zero_errors_on_both_steps(self, gpt):
        """The ISSUE 7 acceptance pin: analysis.check runs over the
        AOT prefill AND decode programs at build and reports zero
        ERRORs (transfer-free + donation-aliased), for both KV
        wires."""
        for wire in ("f32", "int8"):
            eng = make_engine(gpt, kv_wire=wire, verify=True)
            eng.build(buckets=(16,))
            assert set(eng.reports) == {"prefill_16", "decode"}
            for name, report in eng.reports.items():
                assert report.errors() == [], (wire, name, report.render())
                assert "transfer" in report.rules_run
                assert "donation" in report.rules_run

    def test_lint_surface_is_clean(self, gpt):
        report = make_engine(gpt).lint()
        assert report.errors() == [], report.render()
        assert report.target == "serve"
        # the ISSUE 9 artifact sections ride the serve lint too
        blob = report.to_json()
        assert blob["peak_hbm_bytes"] > 0
        assert set(blob["peak_hbm_by_program"]) == {
            "serve/prefill_8", "serve/decode"}

    def test_hbm_budget_gate_fails_build(self, gpt):
        """The ISSUE 9 serve satellite: a pool that never fit is a
        BUILD error (memory-budget), not a step-0 OOM; a generous
        budget builds and publishes the peak gauge."""
        from apex_tpu.observability.metrics import board

        eng = make_engine(gpt, verify=True, hbm_budget_bytes=1 << 10)
        with pytest.raises(RuntimeError, match="memory-budget"):
            eng.build(buckets=(16,))

        board.clear()
        ok = make_engine(gpt, verify=True, hbm_budget_bytes=64 << 20)
        ok.build(buckets=(16,))
        peak = board.get("serve/peak_hbm_bytes")
        assert peak and 0 < peak <= 64 << 20
        # the KV page pool (static shape) is part of the budgeted peak
        pool_bytes = sum(
            np.asarray(leaf).nbytes
            for leaf in jax.tree_util.tree_leaves(ok.cache)
        )
        assert peak >= pool_bytes
        board.clear()

    def test_aot_compiles_once_no_retrace(self, gpt):
        """Steady-state serving never recompiles: many prefill/decode
        calls leave exactly one compile per program and zero sentinel
        retraces."""
        eng = make_engine(gpt)
        rs = np.random.RandomState(6)
        table = np.zeros((2, 8), np.int32)
        for i in range(4):
            prompt = [int(t) for t in rs.randint(0, 64, size=5 + i)]
            pages = eng.pool.alloc(1)
            _, tok = eng.prefill(prompt, pages)
            table[0, :1] = pages
            eng.decode(
                np.array([tok, 0]),
                np.array([len(prompt) + 1, 0]), table,
            )
            eng.pool.free(pages)
        assert eng.compile_counts == {"prefill_8": 1, "decode": 1}
        assert eng.retraces == 0

    def test_config_validation(self, gpt):
        cfg, _, params = gpt
        with pytest.raises(ValueError, match="max_seq_len"):
            InferenceEngine(
                cfg, params,
                ServeConfig(page_size=8, num_pages=128,
                            max_pages_per_seq=64),
            )
        with pytest.raises(ValueError, match="cannot hold even one"):
            ServeConfig(page_size=8, num_pages=4, max_pages_per_seq=8)
        with pytest.raises(ValueError, match="sequence_parallel"):
            serve_model.validate_config(
                tiny_cfg(sequence_parallel=True)
            )
        with pytest.raises(ValueError, match="kv_wire"):
            ServeConfig(kv_wire="fp8")

    def test_prompt_over_max_context_rejected(self, gpt):
        eng = make_engine(gpt, max_pages_per_seq=2)
        with pytest.raises(ValueError, match="exceeds the max context"):
            eng.bucket_for(17)


# ---------------------------------------------------------------------------
# continuous batching scheduler
# ---------------------------------------------------------------------------


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1e-4  # every read advances a hair (monotonic)
        return self.t

    def advance(self, dt):
        self.t += dt


class TestScheduler:
    def _prompt(self, rs, n):
        return [int(t) for t in rs.randint(0, 64, size=n)]

    def test_fifo_admission_and_drain(self, gpt):
        eng = make_engine(gpt)
        sched = ContinuousBatchingScheduler(eng)
        rs = np.random.RandomState(7)
        reqs = [
            sched.submit(Request(prompt=self._prompt(rs, 6),
                                 max_new_tokens=3))
            for _ in range(4)
        ]
        sched.run()
        assert [r.rid for r in sched.completed] == [r.rid for r in reqs]
        assert all(len(r.tokens) == 3 for r in sched.completed)
        assert all(r.ttft_ms is not None for r in sched.completed)
        assert eng.pool.in_use == 0  # every page returned

    def test_mid_stream_admission_raises_batch_fill(self, gpt):
        """A request submitted while another is mid-decode joins the
        RUNNING batch (continuous batching), pushing batch fill above
        the single-request baseline."""
        eng = make_engine(gpt)
        sched = ContinuousBatchingScheduler(eng)
        rs = np.random.RandomState(8)
        first = sched.submit(Request(prompt=self._prompt(rs, 6),
                                     max_new_tokens=12))
        fills = []
        sched.step()  # admit + first decode of request 1, alone
        baseline = sched.batch_fill()
        assert baseline == 0.5  # 1 of 2 slots
        second = sched.submit(Request(prompt=self._prompt(rs, 6),
                                      max_new_tokens=4))
        while sched.pending:
            sched.step()
            fills.append(sched.batch_fill())
        assert max(fills) == 1.0  # both ran TOGETHER mid-stream
        assert second.status == "done" and first.status == "done"
        # the short request finished while the long one kept running
        assert second.done_at < first.done_at

    def test_pool_exhaustion_sheds_past_deadline(self, gpt):
        """Admission backpressure: with the pool pinned by a running
        request, a queued request waits — and is shed (not silently
        starved) once its TTFT SLO deadline passes."""
        eng = make_engine(gpt, num_pages=3, max_pages_per_seq=2)
        clock = FakeClock()
        sched = ContinuousBatchingScheduler(eng, clock=clock)
        rs = np.random.RandomState(9)
        # the hog still holds the whole pool when the starved request's
        # deadline is judged (it keeps decoding past step 1)
        hog = sched.submit(Request(prompt=self._prompt(rs, 14),
                                   max_new_tokens=4))
        starved = sched.submit(Request(prompt=self._prompt(rs, 14),
                                       max_new_tokens=2,
                                       slo_ttft_ms=500.0))
        sched.step()  # hog admitted (2 pages = whole pool), starved waits
        assert hog.status in ("running", "done")
        assert starved.status == "queued"
        clock.advance(1.0)  # blow the 500ms deadline
        sched.run()
        assert starved.status == "shed"
        assert starved.shed_reason == "deadline"  # the split-counter pin
        assert hog.status == "done"
        assert eng.pool.in_use == 0

    def test_growth_page_exhaustion_sheds_youngest(self, gpt):
        """Mid-decode pool exhaustion sheds the YOUNGEST running
        request so older ones keep making progress."""
        eng = make_engine(gpt, num_pages=4, max_pages_per_seq=3)
        clock = FakeClock()
        sched = ContinuousBatchingScheduler(eng, clock=clock)
        rs = np.random.RandomState(10)
        # both need a growth page mid-generation: 8-token prompts fill
        # one page exactly, decode crosses into a second page
        old = sched.submit(Request(prompt=self._prompt(rs, 8),
                                   max_new_tokens=10))
        young = sched.submit(Request(prompt=self._prompt(rs, 8),
                                     max_new_tokens=10))
        # a third hogs the remaining page so growth must fail
        hog = sched.submit(Request(prompt=self._prompt(rs, 8),
                                   max_new_tokens=1))
        sched.run()
        assert old.status == "done" and len(old.tokens) == 10
        assert young.status == "shed"
        assert young.shed_reason == "growth_victim"
        assert hog.status == "done"
        assert eng.pool.in_use == 0

    def test_oversize_prompt_is_shed(self, gpt):
        eng = make_engine(gpt, max_pages_per_seq=2)  # 16-token context
        sched = ContinuousBatchingScheduler(eng)
        rs = np.random.RandomState(11)
        too_big = sched.submit(Request(prompt=self._prompt(rs, 20)))
        ok = sched.submit(Request(prompt=self._prompt(rs, 6),
                                  max_new_tokens=2))
        sched.run()
        assert too_big.status == "shed"
        assert too_big.shed_reason == "oversize"
        assert ok.status == "done"

    def test_metrics_flow_through_registry(self, gpt):
        from apex_tpu.observability import MetricRegistry

        eng = make_engine(gpt)
        reg = MetricRegistry(fetch_every=1)
        sched = ContinuousBatchingScheduler(eng, registry=reg)
        rs = np.random.RandomState(12)
        for _ in range(3):
            sched.submit(Request(prompt=self._prompt(rs, 6),
                                 max_new_tokens=2))
        sched.run()
        reg.fetch()
        vals = reg.values()
        assert vals["serve/completed"] == 3.0
        assert vals["serve/admitted"] == 3.0
        assert vals["serve/shed"] == 0.0
        assert vals["serve/tokens_out"] == 6.0
        assert vals["serve/ttft_ms"] > 0.0
        assert vals["serve/tokens_per_s"] >= 0.0
        # the shed breakdown sums to the total (here: all zero)
        from apex_tpu.serve import SHED_REASONS, TTFT_COMPONENTS

        assert vals["serve/shed"] == sum(
            vals[f"serve/shed_{r}"] for r in SHED_REASONS
        )
        # TTFT attribution percentiles ride the same registry, and the
        # components sum to the TTFT gauge on every completed request
        for comp in TTFT_COMPONENTS:
            for tag in ("p50", "p95", "p99"):
                assert f"serve/ttft_{comp}_ms_{tag}" in vals
        assert vals["serve/ttft_prefill_ms_p50"] > 0.0
        for r in sched.completed:
            c = r.ttft_components()
            assert (
                c["queue_wait_ms"] + c["prefill_ms"] + c["contention_ms"]
            ) == pytest.approx(c["ttft_ms"], abs=1e-6)


# ---------------------------------------------------------------------------
# serving watchdog rules
# ---------------------------------------------------------------------------


class TestServeHealthRules:
    def _registry(self, **values):
        from apex_tpu.observability import MetricRegistry
        from apex_tpu.serve import declare_serve_metrics

        reg = MetricRegistry(fetch_every=1)
        declare_serve_metrics(reg)
        state = reg.update(reg.init(), values)
        reg.observe(0, state)
        reg.observe(1, state)
        reg.fetch()
        return reg

    def test_ttft_rule_fires_and_escalates(self):
        from apex_tpu.observability import TTFTRule, Watchdog, serve_rules

        reg = self._registry(**{"serve/ttft_ms": 2500.0})
        wd = Watchdog(
            serve_rules(ttft={"deadline_ms": 1000.0}),
            registry=reg, check_every=1,
        )
        wd.on_step(1)
        events = [e for e in wd.events if e.rule == "ttft"]
        assert len(events) == 1
        assert events[0].severity == "critical"  # > 2x deadline
        # under the deadline: silent
        rule = TTFTRule(deadline_ms=5000.0)
        reg2 = self._registry(**{"serve/ttft_ms": 100.0})
        wd2 = Watchdog([rule], registry=reg2, check_every=1)
        wd2.on_step(1)
        assert wd2.events == []

    def test_queue_depth_rule(self):
        from apex_tpu.observability import Watchdog, serve_rules

        reg = self._registry(**{"serve/queue_depth": 40.0})
        wd = Watchdog(
            serve_rules(queue_depth={"max_depth": 16}),
            registry=reg, check_every=1,
        )
        wd.on_step(1)
        events = [e for e in wd.events if e.rule == "queue_depth"]
        assert len(events) == 1 and events[0].severity == "warn"

    def test_serve_rules_rejects_unknown(self):
        from apex_tpu.observability import serve_rules

        with pytest.raises(ValueError, match="unknown serve health"):
            serve_rules(mfu_floor={})


class TestPagePoolLeakCheck:
    def test_exact_ownership_passes(self):
        pool = PagePool(num_pages=8, page_size=4)
        a = pool.alloc(2)
        b = pool.alloc(3)
        pool.leak_check([a, b])
        pool.free(b)
        pool.leak_check([a])
        pool.free(a)
        pool.leak_check([])

    def test_leaked_page_named(self):
        pool = PagePool(num_pages=8, page_size=4)
        a = pool.alloc(2)
        with pytest.raises(ValueError, match=rf"leaked.*{a[1]}"):
            pool.leak_check([[a[0]]])

    def test_foreign_page_named(self):
        pool = PagePool(num_pages=8, page_size=4)
        a = pool.alloc(1)
        free_page = 7 if a[0] != 7 else 6
        with pytest.raises(ValueError, match="foreign"):
            pool.leak_check([a, [free_page]])

    def test_double_owned_page_named(self):
        pool = PagePool(num_pages=8, page_size=4)
        a = pool.alloc(2)
        with pytest.raises(ValueError, match="more than one request"):
            pool.leak_check([a, [a[0]]])


# ---------------------------------------------------------------------------
# serving resilience: retries, quarantine, timeouts, ladder, drain
# (docs/serving.md "Failure semantics & degradation ladder")
# ---------------------------------------------------------------------------


def _registry():
    from apex_tpu.observability import MetricRegistry

    return MetricRegistry(fetch_every=1)


def _vals(reg):
    reg.fetch()
    return reg.values()


class TestServeResilience:
    def _prompt(self, rs, n):
        return [int(t) for t in rs.randint(0, 64, size=n)]

    def test_decode_fault_retries_preserve_prefix(self, gpt):
        """A crashed decode iteration sends every rider through
        bounded re-admission with pages and prefix retained — the
        resumed f32 token stream is BIT-IDENTICAL to an unfaulted
        run's (the scheduler-level half of the rebuild-determinism
        satellite)."""
        from apex_tpu.resilience import chaos

        rs = np.random.RandomState(20)
        prompts = [self._prompt(rs, 6) for _ in range(2)]

        def run(faults):
            eng = make_engine(gpt)
            sched = ContinuousBatchingScheduler(eng)
            with chaos.inject(*faults):
                reqs = [
                    sched.submit(Request(prompt=list(p), max_new_tokens=6))
                    for p in prompts
                ]
                sched.run()
            return eng, sched, [r.tokens for r in reqs]

        _, _, clean = run(())
        eng, sched, faulted = run(
            (chaos.Fault(chaos.SERVE_DECODE, steps=(2,), mode="raise",
                         max_hits=1),)
        )
        assert faulted == clean  # prefix preserved, resume exact
        assert eng.rebuilds == 1  # deferred rebuild flushed at idle
        assert all(r.status == "done" for r in sched.completed)
        assert sched.pool.in_use == 0
        assert sched.leak_checks_run > 0

    def test_persistent_decode_fault_exhausts_rebuild_limit(self, gpt):
        from apex_tpu.resilience import chaos

        eng = make_engine(gpt)
        sched = ContinuousBatchingScheduler(eng, rebuild_limit=1)
        rs = np.random.RandomState(21)
        with chaos.inject(chaos.Fault(
            chaos.SERVE_DECODE, steps=tuple(range(64)), mode="raise",
        )):
            sched.submit(Request(prompt=self._prompt(rs, 6),
                                 max_new_tokens=4))
            with pytest.raises(RuntimeError, match="rebuild_limit"):
                sched.run()

    def test_prefill_fault_retried_then_shed_when_persistent(self, gpt):
        from apex_tpu.resilience import chaos

        rs = np.random.RandomState(22)
        # transient: one fault, heals on retry
        eng = make_engine(gpt)
        sched = ContinuousBatchingScheduler(eng)
        with chaos.inject(chaos.Fault(
            chaos.SERVE_PREFILL, steps=(0,), mode="raise", max_hits=1,
        )):
            req = sched.submit(Request(prompt=self._prompt(rs, 6),
                                       max_new_tokens=2))
            sched.run()
        assert req.status == "done" and req.retries == 1
        assert len(req.tokens) == 2
        # persistent: the re-admission budget bounds the loop
        eng2 = make_engine(gpt)
        reg = _registry()
        sched2 = ContinuousBatchingScheduler(
            eng2, registry=reg, max_retries=2,
        )
        with chaos.inject(chaos.Fault(
            chaos.SERVE_PREFILL, steps=tuple(range(16)), mode="raise",
        )):
            req2 = sched2.submit(Request(prompt=self._prompt(rs, 6)))
            sched2.run()
        assert req2.status == "shed"
        assert req2.shed_reason == "retries_exhausted"
        assert req2.retries == 2
        assert sched2.pool.in_use == 0  # retained pages freed at shed
        vals = _vals(reg)
        assert vals["serve/shed_retries_exhausted"] == 1.0
        assert vals["serve/retries"] == 2.0
        assert vals["serve/engine_faults"] == 3.0  # initial + 2 retries

    def test_poisoned_decode_evicts_only_offending_slot(self, gpt):
        """Non-finite logits quarantine THE slot, never the batch: the
        co-resident request keeps the tokens of that very iteration."""
        from apex_tpu.resilience import chaos

        eng = make_engine(gpt)
        reg = _registry()
        sched = ContinuousBatchingScheduler(eng, registry=reg)
        rs = np.random.RandomState(23)
        victim = sched.submit(Request(prompt=self._prompt(rs, 6),
                                      max_new_tokens=8))
        bystander = sched.submit(Request(prompt=self._prompt(rs, 6),
                                         max_new_tokens=8))
        with chaos.inject(chaos.Fault(
            chaos.SERVE_DECODE, steps=(1,), mode="nan", max_hits=1,
        )):
            sched.run()
        assert victim.status == "shed"
        assert victim.shed_reason == "poisoned"
        assert bystander.status == "done"
        assert len(bystander.tokens) == 8
        assert sched.pool.in_use == 0
        vals = _vals(reg)
        assert vals["serve/shed_poisoned"] == 1.0
        assert vals["serve/shed"] == 1.0

    def test_poisoned_prefill_quarantined_at_first_token(self, gpt):
        from apex_tpu.resilience import chaos

        eng = make_engine(gpt)
        sched = ContinuousBatchingScheduler(eng)
        rs = np.random.RandomState(24)
        with chaos.inject(chaos.Fault(
            chaos.SERVE_PREFILL, steps=(0,), mode="nan", max_hits=1,
        )):
            req = sched.submit(Request(prompt=self._prompt(rs, 6)))
            sched.run()
        assert req.status == "shed" and req.shed_reason == "poisoned"
        assert req.tokens == []  # the poisoned first token is not kept
        assert sched.pool.in_use == 0

    def test_decode_timeout_is_per_request(self, gpt):
        """A chaos stall makes one iteration slow; ONLY the request
        carrying a decode timeout discards that iteration and goes
        through retry — its co-rider keeps the token."""
        from apex_tpu.resilience import chaos

        eng = make_engine(gpt)
        reg = _registry()
        sched = ContinuousBatchingScheduler(eng, registry=reg,
                                            max_retries=8)
        rs = np.random.RandomState(25)
        timed = sched.submit(Request(prompt=self._prompt(rs, 6),
                                     max_new_tokens=4,
                                     decode_timeout_ms=20.0))
        free = sched.submit(Request(prompt=self._prompt(rs, 6),
                                    max_new_tokens=4))
        with chaos.inject(chaos.Fault(
            chaos.SERVE_DECODE, steps=(1,), mode="stall", max_hits=1,
        )):
            sched.run()
        assert timed.status == "done" and timed.retries >= 1
        assert free.status == "done" and free.retries == 0
        assert len(timed.tokens) == 4 and len(free.tokens) == 4
        assert _vals(reg)["serve/decode_timeouts"] >= 1.0

    def test_admission_fault_is_transient(self, gpt):
        from apex_tpu.resilience import chaos

        eng = make_engine(gpt)
        reg = _registry()
        sched = ContinuousBatchingScheduler(eng, registry=reg)
        rs = np.random.RandomState(26)
        with chaos.inject(chaos.Fault(
            chaos.SERVE_ADMISSION, steps=(0, 1), mode="raise",
        )):
            req = sched.submit(Request(prompt=self._prompt(rs, 6),
                                       max_new_tokens=2))
            sched.run()
        assert req.status == "done"
        assert _vals(reg)["serve/admission_faults"] == 2.0

    def test_kv_alloc_fault_degrades_gracefully(self, gpt):
        from apex_tpu.resilience import chaos

        eng = make_engine(gpt)
        reg = _registry()
        sched = ContinuousBatchingScheduler(eng, registry=reg)
        rs = np.random.RandomState(27)
        with chaos.inject(chaos.Fault(
            chaos.SERVE_KV_ALLOC, steps=(0,), mode="fail", max_hits=1,
        )):
            req = sched.submit(Request(prompt=self._prompt(rs, 6),
                                       max_new_tokens=2))
            sched.run()
        assert req.status == "done"  # waited one iteration, then ran
        assert _vals(reg)["serve/kv_alloc_faults"] == 1.0

    def test_queue_cap_fast_rejects_excess(self, gpt):
        eng = make_engine(gpt)
        reg = _registry()
        sched = ContinuousBatchingScheduler(eng, registry=reg,
                                            max_queue_depth=2)
        rs = np.random.RandomState(28)
        reqs = [
            sched.submit(Request(prompt=self._prompt(rs, 6),
                                 max_new_tokens=2))
            for _ in range(5)
        ]
        rejected = [r for r in reqs if r.shed_reason == "queue_full"]
        assert len(rejected) == 3  # exactly the over-cap excess
        assert all(r.done_at is not None for r in rejected)
        sched.run()
        assert [r.status for r in reqs[:2]] == ["done", "done"]
        vals = _vals(reg)
        assert vals["serve/shed_queue_full"] == 3.0
        assert vals["serve/shed"] == 3.0

    def test_clamp_rung_bounds_token_budget(self, gpt):
        eng = make_engine(gpt, num_pages=9, max_pages_per_seq=4)
        reg = _registry()
        sched = ContinuousBatchingScheduler(
            eng, registry=reg,
            clamp_max_new_tokens=2, clamp_occupancy=0.25,
        )
        rs = np.random.RandomState(29)
        first = sched.submit(Request(prompt=self._prompt(rs, 16),
                                     max_new_tokens=10))
        second = sched.submit(Request(prompt=self._prompt(rs, 16),
                                      max_new_tokens=10))
        sched.run()
        # occupancy crossed the threshold once the first was resident
        assert first.status == "done" and second.status == "done"
        assert second.clamped_from == 10
        assert second.max_new_tokens == 2 and len(second.tokens) == 2
        assert _vals(reg)["serve/clamped"] >= 1.0

    def test_drain_finishes_running_and_sheds_queued(self, gpt):
        eng = make_engine(gpt)  # max_batch=2
        reg = _registry()
        sched = ContinuousBatchingScheduler(eng, registry=reg)
        rs = np.random.RandomState(30)
        reqs = [
            sched.submit(Request(prompt=self._prompt(rs, 6),
                                 max_new_tokens=6))
            for _ in range(4)
        ]
        sched.step()  # two admitted, two still queued
        report = sched.drain()
        assert report["drained"] and report["pool_in_use"] == 0
        assert [r.status for r in reqs[:2]] == ["done", "done"]
        assert all(r.shed_reason == "draining" for r in reqs[2:])
        vals = _vals(reg)
        assert vals["serve/drains"] == 1.0
        assert vals["serve/shed_draining"] == 2.0
        # a drained scheduler rejects new work loudly
        late = sched.submit(Request(prompt=self._prompt(rs, 6)))
        assert late.status == "shed" and late.shed_reason == "draining"

    def test_step_loop_flushes_deferred_rebuild_at_idle(self, gpt):
        """A caller-driven step() loop (the documented drive pattern)
        must still execute the deferred rebuild once the scheduler
        goes idle — not only run()/drain()."""
        from apex_tpu.resilience import chaos

        eng = make_engine(gpt)
        sched = ContinuousBatchingScheduler(eng)
        rs = np.random.RandomState(34)
        with chaos.inject(chaos.Fault(
            chaos.SERVE_DECODE, steps=(1,), mode="raise", max_hits=1,
        )):
            req = sched.submit(Request(prompt=self._prompt(rs, 6),
                                       max_new_tokens=4))
            while sched.pending:
                sched.step()
        assert req.status == "done"
        assert eng.rebuilds == 1  # flushed by step(), off the traffic path

    def test_resume_clears_drained_state_and_gauge(self, gpt):
        eng = make_engine(gpt)
        reg = _registry()
        sched = ContinuousBatchingScheduler(eng, registry=reg)
        rs = np.random.RandomState(35)
        sched.drain()
        rejected = sched.submit(Request(prompt=self._prompt(rs, 6)))
        assert rejected.shed_reason == "draining"
        sched.resume()
        accepted = sched.submit(Request(prompt=self._prompt(rs, 6),
                                        max_new_tokens=2))
        sched.run()
        assert accepted.status == "done"
        assert _vals(reg)["serve/draining"] == 0.0

    def test_drain_handoff_reroutes_instead_of_shedding(self, gpt):
        """The fleet hook (docs/serving.md "Fleet operations"): with a
        ``handoff``, drain hands never-admitted work out instead of
        shedding it — ledgered as the DISTINCT ``rerouted`` reason
        (still summing into ``serve/shed``), but NOT terminal: no shed
        span, no ``sched.shed`` entry, the request continues
        elsewhere."""
        eng = make_engine(gpt)  # max_batch=2
        reg = _registry()
        sched = ContinuousBatchingScheduler(eng, registry=reg)
        rs = np.random.RandomState(36)
        reqs = [
            sched.submit(Request(prompt=self._prompt(rs, 6),
                                 max_new_tokens=6))
            for _ in range(4)
        ]
        sched.step()  # two admitted, two still queued
        handed = []

        def handoff(r):
            handed.append(r)
            return True

        report = sched.drain(handoff=handoff)
        assert report["drained"] and report["pool_in_use"] == 0
        assert report["rerouted"] == 2
        assert [r.status for r in reqs[:2]] == ["done", "done"]
        assert handed == reqs[2:]
        # re-routed requests are NOT terminal on this replica
        assert all(r.status == "queued" for r in handed)
        assert all(r.shed_reason is None for r in handed)
        assert all(not r.pages for r in handed)  # pages replica-local
        assert sched.shed == []
        vals = _vals(reg)
        assert vals["serve/shed_rerouted"] == 2.0
        assert vals["serve/shed"] == 2.0  # breakdown still sums
        assert vals["serve/shed_draining"] == 0.0

    def test_incremental_drain_start_finish_split(self, gpt):
        """A fleet control plane drains a replica INCREMENTALLY:
        ``start_drain`` now, caller-driven ``step`` ticks, then
        ``finish_drain`` seals with the pool re-proven empty."""
        eng = make_engine(gpt)
        sched = ContinuousBatchingScheduler(eng)
        rs = np.random.RandomState(37)
        reqs = [
            sched.submit(Request(prompt=self._prompt(rs, 6),
                                 max_new_tokens=6))
            for _ in range(2)
        ]
        sched.step()
        rerouted = sched.start_drain(handoff=lambda r: True)
        assert sched.draining and rerouted == 0  # both were admitted
        steps = 0
        while sched.pending:
            sched.step()
            steps += 1
        assert steps > 0  # the drain really spanned ticks
        report = sched.finish_drain()
        assert report["drained"] and report["pool_in_use"] == 0
        assert all(r.status == "done" for r in reqs)

    def test_shed_breakdown_still_sums_with_new_reasons(self, gpt):
        from apex_tpu.observability.ometrics import metric_name
        from apex_tpu.serve import SHED_REASONS

        assert {"poisoned", "queue_full", "retries_exhausted",
                "draining", "rerouted"} < set(SHED_REASONS)
        # the per-reason ledger counters must stay injective on the
        # OpenMetrics export: two reasons mapping to one exposition
        # family would silently merge on every fleet aggregation
        exported = [metric_name(f"serve/shed_{r}") for r in SHED_REASONS]
        assert len(set(exported)) == len(SHED_REASONS)


class TestEngineRecovery:
    def test_rebuild_decode_is_bit_identical(self, gpt):
        """Satellite: a restored engine's decode over RETAINED KV
        pages is bit-identical to the uninterrupted run — same pin
        style as goodput's resume-loss-drift check (drift must be 0.0,
        not small)."""
        cfg, _, _ = gpt
        rs = np.random.RandomState(31)
        prompt = [int(t) for t in rs.randint(0, cfg.vocab_size, size=9)]

        def decode_stream(rebuild_at):
            eng = make_engine(gpt)
            pages = eng.pool.alloc(eng.pool.pages_for(len(prompt)))
            _, tok = eng.prefill(prompt, pages)
            ctx = len(prompt)
            table = np.zeros((2, 8), np.int32)
            out_tokens, out_logits = [], []
            for step in range(6):
                if step == rebuild_at:
                    eng.rebuild()
                if ctx // 8 >= len(pages):
                    pages += eng.pool.alloc(1)
                table[0, : len(pages)] = pages
                logits, nxt = eng.decode(
                    np.array([tok, 0]), np.array([ctx + 1, 0]), table
                )
                out_tokens.append(int(nxt[0]))
                out_logits.append(np.asarray(logits[0]))
                ctx += 1
                tok = int(nxt[0])
            return eng, out_tokens, out_logits

        _, clean_toks, clean_logits = decode_stream(rebuild_at=None)
        eng, toks, logits = decode_stream(rebuild_at=3)
        assert eng.rebuilds == 1
        assert eng.compile_counts["decode"] == 2  # honest recompile
        assert toks == clean_toks
        for a, b in zip(logits, clean_logits):
            np.testing.assert_array_equal(a, b)  # bit-identical

    def test_full_rebuild_drops_prefill_buckets_lazily(self, gpt):
        eng = make_engine(gpt).build(buckets=(8,))
        assert eng.compile_counts == {"prefill_8": 1, "decode": 1}
        eng.rebuild(full=True)
        assert eng.compile_counts["decode"] == 2
        # prefill recompiles lazily on next use
        rs = np.random.RandomState(32)
        pages = eng.pool.alloc(1)
        eng.prefill([int(t) for t in rs.randint(0, 64, size=5)], pages)
        assert eng.compile_counts["prefill_8"] == 2
        eng.pool.free(pages)

    def test_finite_screens_default_clean(self, gpt):
        eng = make_engine(gpt)
        rs = np.random.RandomState(33)
        pages = eng.pool.alloc(1)
        eng.prefill([int(t) for t in rs.randint(0, 64, size=5)], pages)
        assert eng.last_prefill_finite is True
        table = np.zeros((2, 8), np.int32)
        table[0, :1] = pages
        eng.decode(np.array([1, 0]), np.array([6, 0]), table)
        assert eng.last_decode_finite is not None
        assert bool(eng.last_decode_finite.all())
        eng.pool.free(pages)


class TestBf16Serving:
    def test_bf16_engine_runs_and_is_sane(self):
        """The default training dtype (bf16) serves: greedy decode
        runs, logits stay finite, and the argmax token agrees with the
        bf16 reference forward most of the time (exact-match is not
        guaranteed at bf16 — the paged path rounds at different
        points)."""
        cfg = tiny_cfg(dtype=jnp.bfloat16)
        model = GptModel(cfg)
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((8, 1), jnp.int32)
        )
        eng = InferenceEngine(
            cfg, params,
            ServeConfig(page_size=8, num_pages=16, max_batch=2,
                        max_pages_per_seq=4, verify=False),
        )
        rs = np.random.RandomState(13)
        prompt = [int(t) for t in rs.randint(0, cfg.vocab_size, size=9)]
        pages = eng.pool.alloc(2)
        logits, tok = eng.prefill(prompt, pages)
        assert np.isfinite(logits).all()
        table = np.zeros((2, 4), np.int32)
        table[0, :2] = pages
        lg, nxt = eng.decode(
            np.array([tok, 0]), np.array([len(prompt) + 1, 0]), table
        )
        assert np.isfinite(lg[0]).all()
        assert 0 <= int(nxt[0]) < cfg.vocab_size


# ---------------------------------------------------------------------------
# prefix cache: refcounted pool, content-addressed runs, COW, chunked prefill
# (docs/serving.md "Prefix caching & chunked prefill")
# ---------------------------------------------------------------------------


class TestPagePoolRefcounts:
    def test_share_free_roundtrip(self):
        pool = PagePool(num_pages=8, page_size=4)
        got = pool.alloc(2)
        pool.share(got)
        assert pool.refcount(got[0]) == 2
        pool.free(got)  # one reference down: pages stay allocated
        assert pool.in_use == 2
        assert pool.refcount(got[0]) == 1
        pool.free(got)  # last holder lets go: back on the free list
        assert pool.in_use == 0
        assert pool.refcount(got[0]) == 0

    def test_share_unallocated_raises(self):
        pool = PagePool(num_pages=8, page_size=4)
        with pytest.raises(ValueError, match="unallocated"):
            pool.share([3])
        with pytest.raises(ValueError):
            pool.share([NULL_PAGE])

    def test_double_free_still_loud_after_shares(self):
        pool = PagePool(num_pages=8, page_size=4)
        got = pool.alloc(1)
        pool.share(got)
        pool.free(got)
        pool.free(got)
        with pytest.raises(ValueError, match="double free"):
            pool.free(got)

    def test_leak_check_cached_arm(self):
        pool = PagePool(num_pages=8, page_size=4)
        mine = pool.alloc(2)
        cached = pool.alloc(1)
        pool.share(cached)  # the cache's own hold on a borrowed run
        pool.leak_check([mine, cached], cached=cached)
        pool.free(cached)  # the borrower retires
        pool.leak_check([mine], cached=cached)
        # the cache's reference unaccounted -> leaked, loudly
        with pytest.raises(ValueError, match="leaked"):
            pool.leak_check([mine])
        # a claim above the reference count is still double-ownership
        with pytest.raises(ValueError, match="more than one request"):
            pool.leak_check([mine, cached, cached], cached=cached)


class TestPrefixCache:
    def test_prefix_keys_chain_and_tail_commitment(self):
        a = cache_lib.prefix_keys([1, 2, 3, 4, 5, 6], 4)
        b = cache_lib.prefix_keys([1, 2, 3, 4, 9, 9], 4)
        assert [end for _, end in a] == [4, 6]
        assert a[0][0] == b[0][0]  # shared first page, same key
        assert a[1][0] != b[1][0]  # diverging tail
        # a partial-tail key embeds the WHOLE prompt: extending the
        # prompt changes the second key even with the same 6 tokens
        c = cache_lib.prefix_keys([1, 2, 3, 4, 5, 6, 7, 8], 4)
        assert c[0][0] == a[0][0]
        assert c[1][0] != a[1][0]

    def test_commit_match_borrow(self):
        pool = PagePool(num_pages=16, page_size=4)
        cache = cache_lib.PrefixCache(pool)
        prompt = list(range(10))  # 2 full pages + a partial tail
        pages = pool.alloc(3)
        assert cache.commit(prompt, pages) == 3
        assert cache.commits == 1
        # full hit: every page, INCLUDING the partial tail
        hit, tokens = cache.match(prompt)
        assert hit == pages and tokens == 10
        cache.borrow(hit)
        assert pool.refcount(pages[0]) == 3  # owner + cache + borrower
        # shared-prefix hit: full pages only — the foreign partial
        # tail's key embeds tokens this prompt does not have
        hit2, tok2 = cache.match(list(range(8)) + [63, 62, 61])
        assert hit2 == pages[:2] and tok2 == 8
        assert cache.hits == 2 and cache.misses == 0
        assert cache.hit_tokens == 18

    def test_match_miss_and_nontouching_peek(self):
        pool = PagePool(num_pages=16, page_size=4)
        cache = cache_lib.PrefixCache(pool)
        assert cache.match([1, 2, 3]) == ([], 0)
        assert cache.misses == 1
        pages = pool.alloc(1)
        cache.commit([1, 2, 3, 4], pages)
        tick = cache._tick
        assert cache.peek_tokens([1, 2, 3, 4]) == 4
        assert cache.peek_tokens([9, 9]) == 0
        assert cache._tick == tick  # the router probe never touches LRU

    def test_commit_existing_key_keeps_incumbent(self):
        pool = PagePool(num_pages=16, page_size=4)
        cache = cache_lib.PrefixCache(pool)
        prompt = [1, 2, 3, 4, 5, 6, 7, 8]
        a = pool.alloc(2)
        assert cache.commit(prompt, a) == 2
        b = pool.alloc(2)
        # a racing cold prefill of the same prompt: incumbent wins,
        # nothing double-publishes, the loser's pages stay the loser's
        assert cache.commit(prompt, b) == 0
        hit, tokens = cache.match(prompt)
        assert hit == a and tokens == 8
        pool.free(a)
        pool.free(b)
        cache.flush()
        assert pool.in_use == 0

    def test_evict_lru_leaf_first_and_borrowed_pinned(self):
        pool = PagePool(num_pages=16, page_size=4)
        cache = cache_lib.PrefixCache(pool)
        a = pool.alloc(2)
        cache.commit([1, 2, 3, 4, 5, 6, 7, 8], a)
        pool.free(a)  # only the cache holds run A now
        b = pool.alloc(1)
        cache.commit([9, 9, 9, 9], b)
        pool.free(b)
        # A is LRU; its leaf (tail) page goes first — never the parent
        # out from under a cached child
        assert cache.evict(need=1) == 1
        assert cache.peek_tokens([1, 2, 3, 4, 5, 6, 7, 8]) == 4
        # a borrowed run is NEVER evicted, even by a full sweep
        hit, _ = cache.match([9, 9, 9, 9])
        cache.borrow(hit)
        assert cache.evict() == 1  # only A's remaining page was free
        assert cache.peek_tokens([9, 9, 9, 9]) == 4
        pool.free(hit)
        cache.flush()
        assert pool.in_use == 0

    def test_flush_releases_cache_holds_only(self):
        pool = PagePool(num_pages=16, page_size=4)
        cache = cache_lib.PrefixCache(pool)
        pages = pool.alloc(1)
        cache.commit([1, 2, 3, 4], pages)
        assert cache.flush() == 1
        assert pool.refcount(pages[0]) == 1  # the owner's ref survives
        pool.free(pages)
        assert pool.in_use == 0


class TestFusedSampling:
    def test_greedy_rows_are_argmax(self):
        rng = jax.random.PRNGKey(0)
        logits = jax.random.normal(jax.random.PRNGKey(1), (8, 64))
        out = serve_model.sample_tokens(logits, np.zeros(8), rng)
        assert (np.asarray(out) == np.argmax(logits, axis=-1)).all()

    def test_temperature_draws_differ_and_are_deterministic(self):
        logits = jax.random.normal(jax.random.PRNGKey(1), (32, 64))
        temps = np.full(32, 5.0)
        a = serve_model.sample_tokens(logits, temps, jax.random.PRNGKey(2))
        b = serve_model.sample_tokens(logits, temps, jax.random.PRNGKey(2))
        c = serve_model.sample_tokens(logits, temps, jax.random.PRNGKey(3))
        assert (np.asarray(a) == np.asarray(b)).all()  # same key, same draw
        assert (np.asarray(a) != np.asarray(c)).any()  # new key, new draw
        # hot draws leave the argmax at least somewhere over 32 rows
        assert (np.asarray(a) != np.argmax(logits, axis=-1)).any()

    def test_top_k_bounds_the_support(self):
        logits = jax.random.normal(jax.random.PRNGKey(4), (64, 32))
        temps = np.full(64, 10.0)  # hot enough to wander without a mask
        out = np.asarray(serve_model.sample_tokens(
            logits, temps, jax.random.PRNGKey(5), top_k=4
        ))
        top4 = np.argsort(np.asarray(logits), axis=-1)[:, -4:]
        assert all(out[i] in top4[i] for i in range(64))

    def test_mixed_batch_keeps_greedy_rows_exact(self):
        logits = jax.random.normal(jax.random.PRNGKey(6), (8, 64))
        temps = np.array([0.0, 1.0] * 4)
        out = np.asarray(serve_model.sample_tokens(
            logits, temps, jax.random.PRNGKey(7)
        ))
        greedy = np.argmax(np.asarray(logits), axis=-1)
        assert (out[temps == 0.0] == greedy[temps == 0.0]).all()


class TestPrefixScheduler:
    def _prompt(self, rs, n):
        return [int(t) for t in rs.randint(0, 64, size=n)]

    def test_hit_skips_prefill_and_streams_bit_identical(self, gpt):
        eng = make_engine(gpt)
        reg = _registry()
        sched = ContinuousBatchingScheduler(eng, registry=reg,
                                            prefix_cache=True)
        rs = np.random.RandomState(50)
        prompt = self._prompt(rs, 19)  # 2 full pages + a partial tail
        cold = sched.submit(Request(prompt=list(prompt), max_new_tokens=4))
        sched.run()
        calls_after_cold = eng.prefill_calls
        warm = sched.submit(Request(prompt=list(prompt), max_new_tokens=4))
        sched.run()
        # the full prompt (partial tail included) matched, and the hit
        # paid exactly ONE tail chunk instead of a full prefill
        assert warm.cache_hit_tokens == 19
        assert eng.prefill_calls == calls_after_cold + 1
        assert warm.tokens == cold.tokens  # decode is bit-identical
        vals = _vals(reg)
        assert vals["serve/prefix_hits"] == 1.0
        assert vals["serve/prefix_misses"] == 1.0
        assert vals["serve/prefix_hit_tokens"] == 19.0
        assert vals["serve/prefix_commits"] > 0.0
        # the 4-way TTFT attribution: the hit carries a cached_prefill
        # share and the components still sum exactly
        c = warm.ttft_components()
        assert c["cached_prefill_ms"] > 0.0
        assert (
            c["queue_wait_ms"] + c["cached_prefill_ms"]
            + c["prefill_ms"] + c["contention_ms"]
        ) == pytest.approx(c["ttft_ms"], abs=1e-6)
        report = sched.drain()  # flushes the cache, re-proves the pool
        assert report["pool_in_use"] == 0
        assert sched.leak_checks_run > 0

    def test_cow_fork_diverges_without_corrupting_cache(self, gpt):
        """The committer keeps decoding into its own tail page AFTER
        committing it (refcount 2 -> the append forks); a later hit
        borrows the pristine cached run and must see the prompt's KV,
        not the committer's appended tokens."""
        eng = make_engine(gpt)
        reg = _registry()
        sched = ContinuousBatchingScheduler(eng, registry=reg,
                                            prefix_cache=True)
        rs = np.random.RandomState(51)
        prompt = self._prompt(rs, 12)  # partial tail: 4 of 8 slots live
        cold = sched.submit(Request(prompt=list(prompt), max_new_tokens=6))
        sched.run()
        warm = sched.submit(Request(prompt=list(prompt), max_new_tokens=6))
        sched.run()
        warm2 = sched.submit(Request(prompt=list(prompt), max_new_tokens=6))
        sched.run()
        assert warm.cache_hit_tokens == 12
        assert warm.tokens == cold.tokens
        assert warm2.tokens == cold.tokens  # the cached copy never drifted
        assert _vals(reg)["serve/prefix_forks"] >= 3.0  # one per append
        report = sched.drain()
        assert report["pool_in_use"] == 0

    def test_cow_fork_int8_tail(self, gpt):
        """Same fork-then-diverge pin on the int8 KV wire: the fork
        must copy codes AND scale planes."""
        eng = make_engine(gpt, kv_wire="int8")
        sched = ContinuousBatchingScheduler(eng, prefix_cache=True)
        rs = np.random.RandomState(52)
        prompt = self._prompt(rs, 12)
        cold = sched.submit(Request(prompt=list(prompt), max_new_tokens=6))
        sched.run()
        warm = sched.submit(Request(prompt=list(prompt), max_new_tokens=6))
        sched.run()
        assert warm.cache_hit_tokens == 12
        assert warm.tokens == cold.tokens
        report = sched.drain()
        assert report["pool_in_use"] == 0

    def test_eviction_under_pressure_admits_new_work(self, gpt):
        eng = make_engine(gpt, num_pages=5, max_pages_per_seq=4)
        reg = _registry()
        sched = ContinuousBatchingScheduler(eng, registry=reg,
                                            prefix_cache=True)
        rs = np.random.RandomState(53)
        a = sched.submit(Request(prompt=self._prompt(rs, 16),
                                 max_new_tokens=2))
        sched.run()
        assert a.status == "done"
        # run A retired but its 2 pages stay cached; B's admission +
        # growth need the pool back — idle cached pages are reclaimed
        b = sched.submit(Request(prompt=self._prompt(rs, 16),
                                 max_new_tokens=2))
        sched.run()
        assert b.status == "done"
        assert _vals(reg)["serve/prefix_evictions"] >= 1.0
        report = sched.drain()
        assert report["pool_in_use"] == 0

    def test_prefix_evict_drill_spares_borrowed_pages(self, gpt):
        """The ``serve.prefix_evict`` chaos site: a forced full sweep
        mid-traffic reclaims every idle cached run — but a hit's
        borrowed pages survive (refcount > 1 is never evictable) and
        the ledger stays exact under the in-drill leak check."""
        from apex_tpu.resilience import chaos

        eng = make_engine(gpt)
        reg = _registry()
        sched = ContinuousBatchingScheduler(eng, registry=reg,
                                            prefix_cache=True)
        rs = np.random.RandomState(54)
        prompt = self._prompt(rs, 19)
        cold = sched.submit(Request(prompt=list(prompt), max_new_tokens=4))
        sched.run()
        with chaos.inject(chaos.Fault(
            chaos.SERVE_PREFIX_EVICT, steps=tuple(range(64)),
            mode="force", max_hits=1,
        )):
            warm = sched.submit(Request(prompt=list(prompt),
                                        max_new_tokens=4))
            sched.run()
        assert warm.status == "done"
        assert warm.tokens == cold.tokens  # borrowed pages survived
        vals = _vals(reg)
        assert vals["serve/prefix_evict_faults"] == 1.0
        assert sched.leak_checks_run > 0
        report = sched.drain()
        assert report["pool_in_use"] == 0

    def test_shed_borrower_decrements_never_frees_shared(self, gpt):
        """The shed/retry refcount pin (planted fault): a cache-hit
        request whose prefill faults persistently is shed with
        ``retries_exhausted`` — its page release must DECREMENT the
        shared references, not return cached pages to the free list.
        The cache's run survives intact: a later hit still matches the
        full prompt and decodes bit-identical to the cold run."""
        from apex_tpu.resilience import chaos

        eng = make_engine(gpt)
        reg = _registry()
        sched = ContinuousBatchingScheduler(eng, registry=reg,
                                            prefix_cache=True,
                                            max_retries=1)
        rs = np.random.RandomState(55)
        prompt = self._prompt(rs, 19)
        cold = sched.submit(Request(prompt=list(prompt), max_new_tokens=4))
        sched.run()
        cached_before = sorted(sched.prefix.cached_pages())
        with chaos.inject(chaos.Fault(
            chaos.SERVE_PREFILL, steps=tuple(range(64)), mode="raise",
        )):
            doomed = sched.submit(Request(prompt=list(prompt),
                                          max_new_tokens=4))
            sched.run()
        assert doomed.status == "shed"
        assert doomed.shed_reason == "retries_exhausted"
        # the cached run is untouched by the borrower's demise
        assert sorted(sched.prefix.cached_pages()) == cached_before
        sched.leak_check()  # exact ledger, cache holds included
        warm = sched.submit(Request(prompt=list(prompt), max_new_tokens=4))
        sched.run()
        assert warm.status == "done"
        assert warm.cache_hit_tokens == 19
        assert warm.tokens == cold.tokens
        report = sched.drain()
        assert report["pool_in_use"] == 0

    def test_chunked_prefill_matches_monolithic_numerics(self, gpt):
        """Cache OFF, chunking ON: the chunked first token equals the
        monolithic engine's on the same prompt (greedy, f32)."""
        cfg, model, params = gpt
        rs = np.random.RandomState(56)
        prompt = self._prompt(rs, 22)
        eng_mono = make_engine(gpt)
        mono = ContinuousBatchingScheduler(eng_mono)
        a = mono.submit(Request(prompt=list(prompt), max_new_tokens=5))
        mono.run()
        eng_chunk = make_engine(gpt)
        chunked = ContinuousBatchingScheduler(eng_chunk,
                                              prefill_chunk_tokens=8)
        b = chunked.submit(Request(prompt=list(prompt), max_new_tokens=5))
        chunked.run()
        assert a.status == "done" and b.status == "done"
        assert b.tokens[0] == a.tokens[0]  # argmax agrees at f32 tol
        assert b.tokens == a.tokens
        assert eng_chunk.pool.in_use == 0

    def test_chunk_grain_must_be_page_multiple(self, gpt):
        eng = make_engine(gpt)  # page_size=8
        with pytest.raises(ValueError, match="page"):
            ContinuousBatchingScheduler(eng, prefill_chunk_tokens=12)
        with pytest.raises(ValueError):
            ContinuousBatchingScheduler(eng, prefill_chunk_tokens=0)

    def test_cache_off_components_stay_three_way(self, gpt):
        """Without the cache the new component is EXACTLY 0.0 — the
        pre-existing 3-way attribution contract is unchanged."""
        eng = make_engine(gpt)
        sched = ContinuousBatchingScheduler(eng)
        rs = np.random.RandomState(57)
        req = sched.submit(Request(prompt=self._prompt(rs, 6),
                                   max_new_tokens=2))
        sched.run()
        c = req.ttft_components()
        assert c["cached_prefill_ms"] == 0.0


# ---------------------------------------------------------------------------
# speculative decoding (draft propose, one-step verify, PagePool rollback)
# ---------------------------------------------------------------------------


def make_spec_engine(gpt, k=4, spec_kw=None, **serve_kw):
    """A speculative engine; default self-draft (the target proposes
    for itself — 100% greedy acceptance, the tokens/step upper bound)."""
    from apex_tpu.serve import SpecConfig

    cfg, _, params = gpt
    kw = dict(
        page_size=8, num_pages=32, max_batch=2, max_pages_per_seq=8,
        verify=False,
    )
    kw.update(serve_kw)
    spec = SpecConfig(draft_params=None, k=k, **(spec_kw or {}))
    return InferenceEngine(cfg, params, ServeConfig(**kw), spec=spec)


class TestSpeculativeDecoding:
    def _prompt(self, rs, n):
        return [int(t) for t in rs.randint(0, 64, size=n)]

    def _run(self, sched, prompts, max_new=8, **req_kw):
        reqs = [
            sched.submit(Request(prompt=list(p), max_new_tokens=max_new,
                                 rid=f"r{i}", **req_kw))
            for i, p in enumerate(prompts)
        ]
        sched.run()
        assert all(r.status == "done" for r in reqs), [
            (r.status, r.shed_reason) for r in reqs
        ]
        return reqs

    def test_greedy_spec_bit_identical_f32(self, gpt):
        """The acceptance gate: self-draft greedy spec at k=4 emits the
        EXACT token stream plain decode emits, and accepts everything
        (tokens/decode-step = k+1 >> the 1.5 floor)."""
        rs = np.random.RandomState(60)
        prompts = [self._prompt(rs, 6), self._prompt(rs, 11)]
        plain = ContinuousBatchingScheduler(make_engine(gpt))
        base = self._run(plain, prompts)
        reg = _registry()
        sched = ContinuousBatchingScheduler(make_spec_engine(gpt),
                                            registry=reg)
        spec = self._run(sched, prompts)
        for a, b in zip(base, spec):
            assert b.tokens == a.tokens
        vals = _vals(reg)
        assert vals["serve/spec_drafted"] > 0
        assert vals["serve/spec_accepted"] == vals["serve/spec_drafted"]
        assert vals["serve/spec_accept_rate"] == 1.0
        assert vals["serve/spec_tokens_per_step"] >= 1.5
        # spec rounds ARE decode steps: far fewer than tokens emitted
        assert vals["serve/decode_steps"] < vals["serve/tokens_out"] - 2
        assert sched.engine.pool.in_use == 0
        sched.leak_check()

    def test_greedy_spec_bit_identical_int8_kv(self, gpt):
        """Same gate on the int8 KV wire: draft and verify quantize
        through the same codec as plain decode, so greedy acceptance
        still matches argmax-for-argmax."""
        rs = np.random.RandomState(61)
        prompts = [self._prompt(rs, 9), self._prompt(rs, 14)]
        plain = ContinuousBatchingScheduler(make_engine(gpt, kv_wire="int8"))
        base = self._run(plain, prompts)
        sched = ContinuousBatchingScheduler(
            make_spec_engine(gpt, kv_wire="int8")
        )
        spec = self._run(sched, prompts)
        for a, b in zip(base, spec):
            assert b.tokens == a.tokens
        assert sched.engine.pool.in_use == 0

    def test_spec_bit_identical_under_cow_fork(self, gpt):
        """A spec round may roll back KV on the request's tail page —
        which a prefix-cache hit BORROWS.  The scheduler must COW-fork
        the whole speculative window before the round, so the warm
        stream matches the cold one and the cached copy never drifts."""
        rs = np.random.RandomState(62)
        prompt = self._prompt(rs, 12)  # partial tail: 4 of 8 slots live
        plain = ContinuousBatchingScheduler(make_engine(gpt))
        base = self._run(plain, [prompt], max_new=6)
        reg = _registry()
        sched = ContinuousBatchingScheduler(make_spec_engine(gpt),
                                            registry=reg,
                                            prefix_cache=True)
        cold = self._run(sched, [prompt], max_new=6)
        warm = sched.submit(Request(prompt=list(prompt), max_new_tokens=6,
                                    rid="warm"))
        sched.run()
        assert warm.status == "done"
        assert warm.cache_hit_tokens == 12
        assert cold[0].tokens == base[0].tokens
        assert warm.tokens == base[0].tokens
        assert _vals(reg)["serve/prefix_forks"] >= 2.0  # cold + warm tails
        warm2 = sched.submit(Request(prompt=list(prompt), max_new_tokens=6,
                                     rid="warm2"))
        sched.run()
        assert warm2.tokens == base[0].tokens  # cached copy never drifted
        report = sched.drain()
        assert report["pool_in_use"] == 0

    def test_draft_pages_never_enter_prefix_cache(self, gpt):
        """The namespace screen: leak_check refuses a draft-namespace
        page claimed by the prefix cache, and a spec+cache run never
        trips it (draft pages are scheduler-owned only)."""
        pool = PagePool(num_pages=8, page_size=4)
        draft = pool.alloc(1, ns="draft")
        assert pool.namespace(draft[0]) == "draft"
        with pytest.raises(ValueError, match="draft-namespace"):
            pool.leak_check([], cached=draft)
        pool.free(draft)
        # kv-namespace pages cache fine
        kv = pool.alloc(1)
        pool.leak_check([], cached=kv)

    def test_temperature_rollback_replay_bit_identical(self, gpt):
        """The per-slot rng regression pin: sampled tokens are a pure
        function of (stream, position), so re-decoding a position after
        a planted rollback replays the SAME token — no global counter
        leaks into the stream."""
        eng = make_spec_engine(gpt, k=4)
        prompt = list(np.random.RandomState(63).randint(0, 64, size=9))
        pages = eng.pool.alloc(eng.pool.pages_for(len(prompt)))
        _, first = eng.prefill(prompt, pages)
        table = np.zeros((2, 8), np.int32)
        table[0, : len(pages)] = pages
        args = (
            np.array([first, 0], np.int32),
            np.array([len(prompt) + 1, 0], np.int32),
            table,
            np.array([0.8, 0.0], np.float32),
        )
        kw = dict(streams=np.array([1234, 0], np.uint32),
                  gens=np.array([1, 0], np.int32))
        _, t1 = eng.decode(*args, **kw)
        # plant the rollback: truncate the KV row the decode just wrote
        eng.rollback(np.array([len(prompt), 0], np.int32),
                     np.array([1, 0], np.int32), table)
        _, t2 = eng.decode(*args, **kw)
        assert int(t1[0]) == int(t2[0])
        eng.pool.free(pages)

    def test_temperature_k0_matches_plain_stream(self, gpt):
        """Satellite pin: with k=0 the spec path is plain decode routed
        through the verify program — a temperature stream with an
        explicit stream_seed must be bit-identical to the non-spec
        scheduler's."""
        rs = np.random.RandomState(64)
        prompts = [self._prompt(rs, 7), self._prompt(rs, 10)]
        plain = ContinuousBatchingScheduler(make_engine(gpt))
        base = self._run(plain, prompts, temperature=0.7, stream_seed=99)
        sched = ContinuousBatchingScheduler(make_spec_engine(gpt, k=0))
        spec = self._run(sched, prompts, temperature=0.7, stream_seed=99)
        for a, b in zip(base, spec):
            assert b.tokens == a.tokens
        assert sched.engine.pool.in_use == 0

    def test_rejection_sampling_preserves_target_distribution(self):
        """Chi-square on the rejection sampler: proposals drawn from a
        MISMATCHED draft distribution q, accepted/resampled against the
        target p — the emitted first token must be distributed exactly
        as p.  Seeded, CPU, critical value hardcoded (df=7, a=0.001)."""
        from apex_tpu.serve import spec as spec_lib

        V, N, k = 8, 4096, 1
        rs = np.random.RandomState(0)
        p_logits = (rs.randn(V) * 1.5).astype(np.float32)
        q_logits = (rs.randn(V) * 1.5).astype(np.float32)
        p = np.exp(p_logits - p_logits.max())
        p /= p.sum()
        q = np.exp(q_logits - q_logits.max())
        q /= q.sum()
        # the consistency the theorem needs: d ~ q
        d = rs.choice(V, size=(N, k), p=q).astype(np.int32)
        out, n_acc = spec_lib.speculative_verify(
            jnp.broadcast_to(jnp.asarray(p_logits), (k + 1, N, V)),
            jnp.asarray(d),
            jnp.broadcast_to(jnp.asarray(q, jnp.float32), (k, N, V)),
            jnp.ones((N,), jnp.float32),
            jax.vmap(jax.random.fold_in, (None, 0))(
                jax.random.PRNGKey(42), jnp.arange(N, dtype=jnp.uint32)
            ),
            jnp.zeros((N,), jnp.int32),
        )
        counts = np.bincount(np.asarray(out[:, 0]), minlength=V)
        expected = p * N
        chi2 = float(((counts - expected) ** 2 / expected).sum())
        assert chi2 < 24.322, (chi2, counts.tolist(), expected.tolist())
        # and SOME of both outcomes occurred — the test saw real
        # accepts and real rejections, not a degenerate path
        acc = np.asarray(n_acc)
        assert 0 < acc.sum() < N * k

    def test_draft_fault_storm_stream_intact_and_leak_clean(self, gpt):
        """The serve.draft chaos gate: a raise storm makes every spec
        round fall back to plain decode and a nan storm poisons the
        proposals — in BOTH cases the emitted stream stays bit-identical
        to plain decode and the page ledger stays exact."""
        from apex_tpu.resilience import chaos

        rs = np.random.RandomState(65)
        prompts = [self._prompt(rs, 6), self._prompt(rs, 11)]
        plain = ContinuousBatchingScheduler(make_engine(gpt))
        base = self._run(plain, prompts)
        for mode in ("raise", "nan"):
            reg = _registry()
            sched = ContinuousBatchingScheduler(make_spec_engine(gpt),
                                                registry=reg)
            with chaos.inject(chaos.Fault(
                chaos.SERVE_DRAFT, steps=(0, 1, 2), mode=mode
            )):
                reqs = self._run(sched, prompts)
            for a, b in zip(base, reqs):
                assert b.tokens == a.tokens, mode
            vals = _vals(reg)
            if mode == "raise":
                assert vals["serve/draft_faults"] >= 1.0
            else:
                # poisoned proposals are REJECTED, never emitted
                assert vals["serve/spec_rejected"] >= 1.0
            assert sched.engine.pool.in_use == 0
            sched.leak_check()

    def test_acceptance_collapse_falls_back_to_plain(self, gpt):
        """The degradation ladder: a hopeless draft (acceptance under
        min_accept_rate over the window) trips the sticky fallback —
        later rounds ride plain decode, resume() re-arms."""
        import dataclasses as dc

        from apex_tpu.serve import SpecConfig, draft_from_params

        cfg, _, params = gpt
        spec = SpecConfig(
            draft_params=draft_from_params(params, 1),
            k=4,
            draft_cfg=dc.replace(cfg, num_layers=1),
            min_accept_rate=0.95,
            window=2,
        )
        eng = InferenceEngine(cfg, params, ServeConfig(
            page_size=8, num_pages=32, max_batch=2, max_pages_per_seq=8,
            verify=False,
        ), spec=spec)
        reg = _registry()
        sched = ContinuousBatchingScheduler(eng, registry=reg)
        rs = np.random.RandomState(66)
        prompts = [self._prompt(rs, 8), self._prompt(rs, 8)]
        plain = ContinuousBatchingScheduler(make_engine(gpt))
        base = self._run(plain, prompts, max_new=12)
        reqs = self._run(sched, prompts, max_new=12)
        for a, b in zip(base, reqs):
            assert b.tokens == a.tokens  # fallback or not: same stream
        vals = _vals(reg)
        assert vals["serve/spec_fallbacks"] >= 1.0
        assert sched._spec_fallback
        sched.resume()
        assert not sched._spec_fallback
        assert eng.pool.in_use == 0

    def test_spec_acceptance_watchdog_rule(self, gpt):
        """SpecAcceptanceRule pages when the published acceptance gauge
        sinks under its floor — and stays silent when speculation never
        ran."""
        from apex_tpu.observability import (
            MetricRegistry, SpecAcceptanceRule, Watchdog,
        )
        from apex_tpu.serve import declare_serve_metrics

        reg = MetricRegistry(fetch_every=1)
        declare_serve_metrics(reg)
        state = reg.update(reg.init(), {
            "serve/spec_rounds": 8.0,
            "serve/spec_accept_rate": 0.2,
        })
        reg.observe(0, state)
        reg.observe(1, state)
        reg.fetch()
        wd = Watchdog([SpecAcceptanceRule(min_rate=0.5)], registry=reg,
                      check_every=1)
        wd.on_step(1)
        events = [e for e in wd.events if e.rule == "spec_acceptance"]
        assert len(events) == 1
        # silent when spec never ran (rate gauge 0.0, rounds 0)
        reg2 = MetricRegistry(fetch_every=1)
        declare_serve_metrics(reg2)
        state2 = reg2.update(reg2.init(), {})
        reg2.observe(0, state2)
        reg2.observe(1, state2)
        reg2.fetch()
        wd2 = Watchdog([SpecAcceptanceRule(min_rate=0.5)], registry=reg2,
                       check_every=1)
        wd2.on_step(1)
        assert wd2.events == []


# ---------------------------------------------------------------------------
# one block, three dataflows; one program table (ISSUE 33)
# ---------------------------------------------------------------------------


def _head_and_tail(cfg, params, tokens, positions):
    """Plain-jnp logits of the model WITHOUT its layers: embedding,
    learned positions, final LayerNorm, tied logits — what a step body
    must compute when the block returns its input."""
    tree = params["params"]
    emb = tree["word_embeddings"]["weight"]
    x = emb[jnp.asarray(tokens)]
    if not cfg.rotary:
        x = x + tree["position_embeddings"][jnp.asarray(positions)]
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    h = (x - mean) / jnp.sqrt(var + cfg.layer_norm_eps)
    h = h * tree["ln_f"]["scale"] + tree["ln_f"]["bias"]
    return np.asarray(h @ emb.T)


def _run_step_body(body, cfg, params):
    """Drive one of the five step bodies on the tiny configuration;
    returns ``(got, want)``: what the body computed from its logits and
    the same from :func:`_head_and_tail` at the rows' tokens and
    positions."""
    from apex_tpu.serve import spec as spec_lib

    ps, b, k = 8, 2, 2
    kv = cache_lib.init_kv_pages(
        cfg.num_layers, 16, cfg.num_heads, ps,
        cfg.hidden_size // cfg.num_heads, dtype=cfg.dtype,
    )
    rs = np.random.RandomState(33)
    if body in ("prefill", "chunk_prefill"):
        n, offset = 11, 16 if body == "chunk_prefill" else 0
        tokens = np.zeros((16, 1), np.int32)
        tokens[:n, 0] = rs.randint(0, cfg.vocab_size, size=n)
        if body == "prefill":
            logits, _, _, _ = serve_model.prefill_body(
                cfg, params, kv, jnp.asarray(tokens), jnp.int32(n),
                jnp.asarray([1, 2], jnp.int32), page_size=ps,
            )
        else:
            logits, _, _, _ = serve_model.chunk_prefill_body(
                cfg, params, kv, jnp.asarray(tokens), jnp.int32(n),
                jnp.int32(offset), jnp.asarray([3, 4], jnp.int32),
                jnp.asarray([1, 2, 3, 4, 0, 0, 0, 0], jnp.int32),
                page_size=ps,
            )
        want = _head_and_tail(
            cfg, params, tokens[n - 1, 0], offset + n - 1
        )
        return np.asarray(logits), want
    tokens = rs.randint(0, cfg.vocab_size, size=b).astype(np.int32)
    lengths = np.array([5, 12], np.int32)
    tables = np.array([[1, 2, 0, 0], [3, 4, 5, 0]], np.int32)
    if body == "decode":
        logits, _, _, _ = serve_model.decode_body(
            cfg, params, kv, jnp.asarray(tokens), jnp.asarray(lengths),
            jnp.asarray(tables), page_size=ps,
        )
        return np.asarray(logits), _head_and_tail(
            cfg, params, tokens, lengths - 1
        )
    keys = jnp.asarray(rs.randint(0, 2**31, size=(b, 2)), jnp.uint32)
    gens = jnp.zeros((b,), jnp.int32)
    if body == "draft":
        # temperature 1: the proposals' distributions ARE softmax(logits)
        drafts, probs, _, _ = spec_lib.draft_body(
            cfg, params, kv, jnp.asarray(tokens), jnp.asarray(lengths),
            jnp.asarray(tables), jnp.ones((b,), jnp.float32), keys, gens,
            k=k, page_size=ps,
        )
        cols = np.concatenate([tokens[:, None], np.asarray(drafts)], 1)
        want = np.stack([
            jax.nn.softmax(_head_and_tail(
                cfg, params, cols[:, j], lengths - 1 + j
            ), axis=-1)
            for j in range(k)
        ])
        return np.asarray(probs), want
    assert body == "verify"
    drafts = rs.randint(0, cfg.vocab_size, size=(b, k)).astype(np.int32)
    # greedy: the emitted columns are the argmax of each position's logits
    out, _, _, _ = spec_lib.verify_body(
        cfg, params, kv, jnp.asarray(tokens), jnp.asarray(drafts),
        jnp.asarray(lengths), jnp.asarray(tables),
        jnp.zeros((b,), jnp.float32),
        jnp.zeros((k, b, cfg.vocab_size), jnp.float32), keys, gens,
        page_size=ps,
    )
    cols = np.concatenate([tokens[:, None], drafts], 1)
    want = np.stack([
        _head_and_tail(cfg, params, cols[:, j], lengths - 1 + j).argmax(-1)
        for j in range(k + 1)
    ], axis=1)
    return np.asarray(out), want


class TestOneBlock:
    """Every step body applies a layer through ``serve.model._block``
    and no other way."""

    @pytest.mark.parametrize("rotary", [True, False])
    @pytest.mark.parametrize(
        "body", ["prefill", "chunk_prefill", "decode", "draft", "verify"]
    )
    def test_every_body_goes_through_the_one_block(
        self, monkeypatch, body, rotary
    ):
        cfg = tiny_cfg(rotary=rotary)
        params = GptModel(cfg).init(
            jax.random.PRNGKey(1), jnp.zeros((8, 1), jnp.int32)
        )
        real, calls = serve_model._block, []

        def counting(*args):
            calls.append(args)
            return real(*args)

        monkeypatch.setattr(serve_model, "_block", counting)
        got, want = _run_step_body(body, cfg, params)
        # lax.scan traces the layer once: one call a layer loop
        assert len(calls) == 1
        assert not np.allclose(got, want, atol=1e-3)  # the layers matter

        monkeypatch.setattr(
            serve_model, "_block",
            lambda cfg, lp, x, kv, layer, attend: (x, kv),
        )
        got, want = _run_step_body(body, cfg, params)
        if body == "verify":
            np.testing.assert_array_equal(got, want)
        else:
            np.testing.assert_allclose(got, want, atol=2e-5)


#: what ``build(chunked=True)`` on a speculative engine compiles, in
#: order — the keys of ``compile_counts`` (the benchmark's
#: ``fault_counters`` compare them), the sentinel names, and (behind
#: ``jit_serve_``) the module names a device trace shows
PROGRAMS_AT_PR32 = [
    "prefill_8", "chunk_prefill_8", "draft_prefill_8",
    "prefill_16", "chunk_prefill_16", "draft_prefill_16",
    "fork_page", "decode", "draft_decode", "verify", "rollback",
    "draft_rollback",
]


class TestProgramTable:
    @pytest.mark.parametrize("full", [False, True])
    def test_build_and_rebuild_keep_todays_programs(self, gpt, full):
        import re

        eng = make_spec_engine(gpt, k=2, prefill_buckets=(8, 16))
        eng.build(chunked=True)
        assert list(eng.compile_counts) == PROGRAMS_AT_PR32
        assert set(eng.compile_counts.values()) == {1}
        assert sorted(
            kind if bucket is None else f"{kind}_{bucket}"
            for kind, bucket in eng._programs
        ) == sorted(PROGRAMS_AT_PR32)
        assert eng.retraces == 0
        assert {
            re.search(r"HloModule (\S+?),", exe.as_text()).group(1)
            for exe in eng._programs.values()
        } == {f"jit_serve_{name}" for name in PROGRAMS_AT_PR32}

        before = dict(eng._programs)
        eng.rebuild(full=full)
        rebuilt = {"decode", "draft_decode", "verify"}
        dropped = {
            name for name in PROGRAMS_AT_PR32
            if "prefill" in name
        } if full else set()
        for (kind, bucket), exe in before.items():
            name = kind if bucket is None else f"{kind}_{bucket}"
            assert eng.compile_counts[name] == 1 + (name in rebuilt)
            if name in dropped:
                assert (kind, bucket) not in eng._programs
            else:
                assert (kind, bucket) in eng._programs
                # a recompiled program is a NEW executable, the rest
                # are the ones build() made
                same = eng._programs[kind, bucket] is exe
                assert same == (name not in rebuilt)
        # a dropped bucket recompiles on next use
        eng._program("prefill", 8)
        assert eng.compile_counts["prefill_8"] == 1 + full

    def test_plain_engine_has_no_speculative_or_chunked_program(self, gpt):
        eng = make_engine(gpt, prefill_buckets=(8,)).build()
        assert list(eng.compile_counts) == ["prefill_8", "decode"]
        eng.rebuild()
        assert eng.compile_counts == {"prefill_8": 1, "decode": 2}
