"""Pallas kernel static analyzer (apex_tpu.analysis.kernels, ISSUE 10).

Each pass gets a planted-defect fixture asserting the EXACT rule id,
plus a clean-kernel zero-findings fixture; the VMEM model is validated
against captured real ``pallas_call`` arguments (the interpret-mode
call path) across >6 tile configs; the FLOP model is validated against
the dots actually traced into the kernel jaxprs; and the prune/ranking
acceptance runs against the recorded v5e sweep fixture
(tests/data/attn_sweep_r05.json): >=30% of the default grid
eliminated, every cell within 5% of the measured best retained.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import analysis
from apex_tpu.analysis import kernels as ka
from apex_tpu.ops.pallas import decode_attention as da
from apex_tpu.ops.pallas import flash_attention as fa
from apex_tpu.ops.pallas import layer_norm as ln
from apex_tpu.ops.pallas import tune_cache
from apex_tpu.ops.pallas.introspect import (
    BlockArg,
    KernelSpec,
    buffer_bytes,
    dtype_width,
)

DATA = os.path.join(os.path.dirname(__file__), "data")
V5E = "TPU v5 lite"


def fwd_specs(bh, sq, sk, d, **kw):
    kw.setdefault("modes", ("fwd",))
    return fa.kernel_specs(bh, sq, sk, d, **kw)


# ---------------------------------------------------------------------------
# VMEM model vs the real pallas_call (the +-10% acceptance)
# ---------------------------------------------------------------------------


class TestVmemModel:
    # 7 (block_q, block_k) configs at the flash fwd kernel — the
    # acceptance criterion asks for >= 6
    CONFIGS = [
        (128, 128), (128, 256), (256, 128), (256, 256),
        (512, 256), (256, 512), (512, 512),
    ]

    def _captured_bytes(self, monkeypatch, bq, bk, sq=512, d=64, bh=2):
        """Trace the REAL flash_fwd (the interpret-mode call path) with
        a spying pallas_call and rebuild its block+scratch bytes from
        the captured arguments."""
        captured = {}
        real = fa.pl.pallas_call

        def spy(kernel, **kw):
            captured.update(kw)
            return real(kernel, **kw)

        monkeypatch.setattr(fa.pl, "pallas_call", spy)
        q = jnp.zeros((bh, sq, d), jnp.bfloat16)
        jax.eval_shape(
            lambda q, k, v: fa.flash_fwd(
                q, k, v, None, scale=1.0, causal=True,
                block_q=bq, block_k=bk,
            ),
            q, q, q,
        )
        assert captured, "pallas_call was never traced"
        in_dtypes = ["bfloat16"] * 3
        blocks = 0
        for spec, dt in zip(captured["in_specs"], in_dtypes):
            blocks += int(np.prod(spec.block_shape)) * dtype_width(dt)
        for spec, sd in zip(captured["out_specs"], captured["out_shape"]):
            blocks += (
                int(np.prod(spec.block_shape))
                * dtype_width(np.dtype(sd.dtype).name)
            )
        scratch = sum(
            int(np.prod(ref.shape)) * dtype_width(np.dtype(ref.dtype).name)
            for ref in captured["scratch_shapes"]
        )
        return 2 * blocks + scratch

    @pytest.mark.parametrize("bq,bk", CONFIGS)
    def test_model_within_10pct_of_captured_call(self, monkeypatch, bq, bk):
        ref = self._captured_bytes(monkeypatch, bq, bk)
        (spec,) = fwd_specs(2, 512, 512, 64, block_q=bq, block_k=bk)
        fp = ka.vmem_footprint(spec)
        model = fp["block_bytes"] + fp["scratch_bytes"]
        assert abs(model - ref) <= 0.10 * ref, (model, ref, bq, bk)

    def test_footprint_terms(self):
        (spec,) = fwd_specs(2, 512, 512, 64, block_q=256, block_k=256)
        fp = ka.vmem_footprint(spec)
        # q/k/v bf16 blocks + o bf16 + lse f32, double-buffered
        blk = 2 * (3 * 256 * 64 * 2 + 256 * 64 * 2 + 256 * 128 * 4)
        assert fp["block_bytes"] == blk
        # acc (256,64) + m/l (256,128) f32
        assert fp["scratch_bytes"] == (256 * 64 + 2 * 256 * 128) * 4
        # one (bq, bk) f32 score value at fwd steady state
        assert fp["intermediate_bytes"] == 256 * 256 * 4
        assert fp["total_bytes"] == sum(
            fp[k] for k in
            ("block_bytes", "scratch_bytes", "intermediate_bytes")
        )

    def test_oversized_block_is_vmem_overflow(self):
        # a (4096, 4096) f32 score tile is 64 MiB — dead on arrival
        specs = fwd_specs(
            2, 4096, 4096, 128, block_q=4096, block_k=4096,
        )
        report = ka.analyze(specs, device_kind=V5E)
        assert "kernel-vmem-overflow" in {
            f.rule for f in report.errors()
        }

    def test_beyond_edge_probe_stays_feasible(self):
        # docs/flash-roofline.md: a (1024, 2048) fwd score tile (8 MiB)
        # is "comfortably inside v5e's budget" — the ROADMAP's
        # 2048-wide probe must NOT be vmem-pruned; (2048, 2048)'s
        # 16 MiB score tile alone busts the budget and must be
        specs = fwd_specs(
            8, 16384, 16384, 128, block_q=1024, block_k=2048,
        )
        assert ka.analyze(specs, device_kind=V5E).errors() == []
        specs = fwd_specs(
            8, 16384, 16384, 128, block_q=2048, block_k=2048,
        )
        assert ka.analyze(specs, device_kind=V5E).by_rule(
            "kernel-vmem-overflow"
        )

    def test_budget_override(self):
        (spec,) = fwd_specs(2, 512, 512, 64, block_q=256, block_k=256)
        assert ka.analyze(
            [spec], device_kind=V5E, vmem_budget=1 << 30
        ).ok()
        over = ka.analyze([spec], device_kind=V5E, vmem_budget=1 << 16)
        assert over.by_rule("kernel-vmem-overflow")


# ---------------------------------------------------------------------------
# FLOP model vs the dots actually traced into the kernels
# ---------------------------------------------------------------------------


def _dot_flops(eqn):
    (cl, cr), (bl, br) = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    k = int(np.prod([lhs[i] for i in cl])) if cl else 1
    b = int(np.prod([lhs[i] for i in bl])) if bl else 1
    m = int(np.prod(
        [s for i, s in enumerate(lhs) if i not in cl and i not in bl]
    ))
    n = int(np.prod(
        [s for i, s in enumerate(rhs) if i not in cr and i not in br]
    ))
    return 2.0 * b * m * n * k


def _pallas_kernel_dot_flops(jaxpr):
    """name -> per-cell dot FLOPs of every pallas_call in a jaxpr."""
    out = []
    for eqn in analysis.iter_eqns(jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        kernel_jaxpr = eqn.params["jaxpr"]
        flops = sum(
            _dot_flops(e) for e in analysis.iter_eqns(kernel_jaxpr)
            if e.primitive.name == "dot_general"
        )
        out.append(flops)
    return out


class TestFlopModel:
    def test_fwd_flops_match_traced_dots(self):
        q = jnp.zeros((2, 512, 64), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(
            lambda q, k, v: fa.flash_fwd(
                q, k, v, None, scale=1.0, causal=True,
                block_q=256, block_k=128,
            )
        )(q, q, q)
        (traced,) = _pallas_kernel_dot_flops(jaxpr)
        (spec,) = fwd_specs(2, 512, 512, 64, block_q=256, block_k=128)
        assert abs(spec.flops_per_cell - traced) <= 0.10 * traced

    def test_bwd_flops_match_traced_dots(self):
        q = jnp.zeros((2, 512, 64), jnp.bfloat16)
        o = jnp.zeros_like(q)
        lse = jnp.zeros((2, 512, 128), jnp.float32)
        jaxpr = jax.make_jaxpr(
            lambda q, k, v, o, lse: fa.flash_bwd(
                q, k, v, o, lse, o, None, scale=1.0, causal=True,
                block_q=256, block_k=256,
            )
        )(q, q, q, o, lse)
        dkdv_traced, dq_traced = _pallas_kernel_dot_flops(jaxpr)
        dkdv, dq = fa.kernel_specs(
            2, 512, 512, 64, block_q=256, block_k=256,
            modes=("dkdv", "dq"),
        )
        assert abs(dkdv.flops_per_cell - dkdv_traced) <= 0.10 * dkdv_traced
        assert abs(dq.flops_per_cell - dq_traced) <= 0.10 * dq_traced


# ---------------------------------------------------------------------------
# Tiling-alignment lint
# ---------------------------------------------------------------------------


class TestTilingPass:
    def test_96_wide_block_is_tile_misaligned(self):
        # 1536 % 96 == 0, so only the MXU 128-alignment rule can (and
        # must) catch it — the satellite's planted defect
        specs = fwd_specs(
            2, 1536, 1536, 128, causal=False, block_q=96, block_k=96,
        )
        report = ka.analyze(specs, device_kind=V5E)
        assert "kernel-tile-misaligned" in report.rule_ids()

    def test_ragged_tail_is_tile_misaligned_error(self):
        # 100 neither divides 512 nor is sublane-aligned for bf16
        specs = fwd_specs(
            2, 512, 512, 64, causal=False, block_q=100, block_k=128,
        )
        report = ka.analyze(specs, device_kind=V5E)
        ragged = report.by_rule("kernel-tile-misaligned")
        assert ragged and any(f.severity == "error" for f in ragged)
        assert any("does not divide" in f.message for f in ragged)

    def test_full_axis_blocks_exempt(self):
        # d=64 trailing blocks and (br, 1) stat blocks cover their
        # whole axis — the shipped kernels must not self-flag
        report = ka.analyze(
            fwd_specs(2, 512, 512, 64, block_q=256, block_k=256)
            + ln.kernel_specs(4096, 1024),
            device_kind=V5E,
        )
        assert report.by_rule("kernel-tile-misaligned") == []


# ---------------------------------------------------------------------------
# Grid coverage / race
# ---------------------------------------------------------------------------


def _synthetic_spec(out_map, semantics=("parallel", "arbitrary"),
                    grid=(2, 2)):
    out = BlockArg(
        name="o", shape=(4, 128, 128), block=(1, 128, 128),
        index_map=out_map, dtype="float32",
    )
    inp = BlockArg(
        name="x", shape=(4, 128, 128), block=(1, 128, 128),
        index_map=lambda i, j: (i, 0, 0), dtype="float32",
    )
    return KernelSpec(
        name="synthetic", grid=grid, inputs=(inp,), outputs=(out,),
        dimension_semantics=semantics,
    )


class TestCoveragePass:
    def test_oob_index_map(self):
        spec = _synthetic_spec(lambda i, j: (i + 3, 0, 0))
        report = ka.analyze(spec, device_kind=V5E)
        assert "kernel-grid-oob" in {f.rule for f in report.errors()}

    def test_parallel_overlap_is_block_race(self):
        # both parallel-axis cells write block (0, ...) — the planted
        # overlapping-index-map defect
        spec = _synthetic_spec(
            lambda i, j: (0, 0, 0), semantics=("parallel", "parallel"),
        )
        report = ka.analyze(spec, device_kind=V5E)
        assert "kernel-block-race" in {f.rule for f in report.errors()}

    def test_arbitrary_axis_revisit_is_not_a_race(self):
        # the flash kernels' accumulate-over-j pattern: the output
        # block ignores the ARBITRARY axis — sanctioned, no finding
        spec = _synthetic_spec(lambda i, j: (i, 0, 0))
        report = ka.analyze(spec, device_kind=V5E)
        assert report.by_rule("kernel-block-race") == []
        assert report.by_rule("kernel-grid-oob") == []

    def test_decode_page_table_out_of_pool(self):
        # a page id beyond the pool is an OOB DMA the coverage pass
        # must catch through the REAL scalar-prefetch index map
        bad_table = np.full((2, 4), 99, np.int32)  # pool has 8 pages
        (spec,) = da.kernel_specs(
            2, 4, 128, pool_pages=8, page=16, pages_per_seq=4,
            page_table=bad_table,
        )
        report = ka.analyze(spec, device_kind=V5E)
        assert "kernel-grid-oob" in {f.rule for f in report.errors()}

    def test_decode_walk_plan_at_the_serving_shape(self):
        """GPT-2 Large as the benchmark serves it: B=32 slots, 64 table
        entries, 16-row pages.  The export is the walk the kernel's
        copies make — K = 8 page operands a pool a step through the
        clamped maps — and its step count is pinned."""
        (spec,) = da.kernel_specs(
            32, 20, 64, pool_pages=1201, page=16, pages_per_seq=64,
        )
        k = spec.meta["pages_per_step"]
        assert k == da.pages_per_step(16, 10 * 128 * 2, 64) == 8
        assert spec.grid == (32, -(-64 // k)) and spec.cells() == 256
        assert spec.dimension_semantics == ("parallel", "arbitrary")
        names = [a.name for a in spec.inputs]
        assert names == (
            ["q"] + [f"k_pages[{i}]" for i in range(k)]
            + [f"v_pages[{i}]" for i in range(k)] + ["rope_cos", "rope_sin"]
        )
        for a in spec.inputs[1:1 + 2 * k]:
            assert a.shape == (1, 1201, 10, 16, 128)
            assert a.block == (1, 1, 10, 16, 128)
        report = ka.analyze(spec, device_kind=V5E)
        assert report.findings == [], report.render()
        # two buffer slots of K pages of K and of V, the rows, the output
        fp = ka.vmem_footprint(spec)
        assert fp["block_bytes"] >= 2 * 2 * k * 10 * 16 * 128 * 2
        assert fp["total_bytes"] < 4 << 20

    def test_decode_walk_maps_clamp_to_the_live_pages(self):
        """On a concrete table with SHORT lengths every page operand of
        every step stays on the sequence's live pages: dead entries may
        hold ids far outside the pool and the coverage pass stays clean;
        at full lengths the same table is out of bounds."""
        b, np_, page, pool = 4, 40, 8, 64
        k = da.pages_per_step(page, 2 * 128 * 2, np_)
        assert k == 16 and np_ % k
        lengths = np.asarray([0, 1, k * page + 1, np_ * page], np.int32)
        live = -(-lengths // page)
        table = np.full((b, np_), 9999, np.int32)
        table[0, 0] = 0  # an idle slot's row: the null page
        for row, n in enumerate(live):
            table[row, :n] = 1 + np.arange(n) + row * 7
        (spec,) = da.kernel_specs(
            b, 4, 64, pool_pages=pool, page=page, pages_per_seq=np_,
            page_table=table, lengths=lengths, kv_wire="int8",
        )
        assert spec.grid == (b, 3)
        report = ka.analyze(spec, device_kind=V5E)
        assert report.by_rule("kernel-grid-oob") == [], report.render()
        assert report.by_rule("kernel-block-race") == []
        pages = [a for a in spec.inputs if a.name.startswith("k_pages")]
        assert len(pages) == k
        for row in range(b):
            last = max(int(live[row]) - 1, 0)
            for j in range(3):
                for i, a in enumerate(pages):
                    want = table[row, min(j * k + i, last)]
                    assert int(a.index_map(row, j)[1]) == want
        # the scale planes are paged like the pools: K pages a step
        # through the same clamped maps, a token a row and a head a lane
        scales = [a for a in spec.inputs if a.name.startswith("k_scale")]
        assert len(scales) == k
        for i, a in enumerate(scales):
            assert a.shape == (1, pool, 1, page, 128)
            assert a.block == (1, 1, 1, page, 128)
            assert int(a.index_map(2, 1)[1]) == table[
                2, min(k + i, int(live[2]) - 1)]
        (full,) = da.kernel_specs(
            b, 4, 64, pool_pages=pool, page=page, pages_per_seq=np_,
            page_table=table,
        )
        report = ka.analyze(full, device_kind=V5E)
        assert "kernel-grid-oob" in {f.rule for f in report.errors()}

    @pytest.mark.parametrize("h,d,hg,w", [
        (25, 64, 25, 128), (20, 80, 20, 128), (12, 96, 12, 128),
        (8, 192, 8, 256),
    ])
    def test_decode_walk_rows_are_whole_tiles(self, h, d, hg, w):
        """Heads that do not pair up, or of 80, 96 or 192 lanes: one head
        a row, the row padded to whole 128-lane tiles — every operand the
        kernel copies or blocks has a lane-dense minor dimension."""
        (spec,) = da.kernel_specs(
            32, h, d, pool_pages=301, page=16, pages_per_seq=64,
            kv_wire="int8",
        )
        for a in list(spec.inputs) + list(spec.outputs):
            assert a.block[-1] % 128 == 0, (a.name, a.block)
        pages = [a for a in spec.inputs if a.name.startswith("k_pages")]
        assert {a.block for a in pages} == {(1, 1, hg, 16, w)}
        report = ka.analyze(spec, device_kind=V5E)
        assert report.errors() == [], report.render()

    def test_decode_walk_scales_in_vmem_do_not_grow_with_the_table(self):
        """The int8 wire at a 32k-token table (2,048 entries): a step's
        scale slab is what VMEM holds, as at 64 entries."""
        def footprint(np_):
            (spec,) = da.kernel_specs(
                8, 32, 128, pool_pages=4097, page=16, pages_per_seq=np_,
                kv_wire="int8",
            )
            return ka.vmem_footprint(spec)["total_bytes"]

        assert footprint(2048) == footprint(64) < 8 << 20

    def test_shipped_kernels_cover_cleanly(self):
        specs = (
            fa.kernel_specs(2, 512, 512, 64, block_q=128, block_k=128)
            + ln.kernel_specs(2048, 768)
            + da.kernel_specs(
                2, 4, 128, pool_pages=8, page=16, pages_per_seq=4,
            )
        )
        report = ka.analyze(specs, device_kind=V5E)
        assert report.by_rule("kernel-grid-oob") == []
        assert report.by_rule("kernel-block-race") == []


# ---------------------------------------------------------------------------
# Causal dead tiles
# ---------------------------------------------------------------------------


class TestDeadTiles:
    def test_hand_checkable_stats(self):
        # seq 4, 2x2 tiles of 2: live {(0,0),(1,0),(1,1)}; causal pairs
        # = 10 of the 12 executed elements -> waste 1/6
        (spec,) = fwd_specs(1, 4, 4, 8, block_q=2, block_k=2)
        stats = ka.dead_tile_stats(spec)
        assert stats["total_tiles"] == 4
        assert stats["live_tiles"] == 3
        assert stats["dead_tiles"] == 1
        assert stats["waste_fraction"] == pytest.approx(1 / 6)

    def test_non_causal_has_no_stats(self):
        (spec,) = fwd_specs(
            1, 256, 256, 64, causal=False, block_q=128, block_k=128,
        )
        assert ka.dead_tile_stats(spec) is None

    def test_naive_causal_config_flags_dead_tiles(self):
        # 2 tiles per side: boundary tiles pay ~33% masked FLOPs
        specs = fwd_specs(1, 1024, 1024, 64, block_q=512, block_k=512)
        report = ka.analyze(
            specs, device_kind=V5E, dead_tile_threshold=0.25,
        )
        assert "kernel-dead-tiles" in report.rule_ids()
        assert all(
            f.severity == "warning"
            for f in report.by_rule("kernel-dead-tiles")
        )

    def test_default_config_under_ci_bound(self):
        # the verify_tier1 pin: tuned long-shape tiles waste < 15%
        specs = fa.kernel_specs(8, 16384, 16384, 128, causal=True)
        for spec in specs:
            stats = ka.dead_tile_stats(spec)
            assert stats["waste_fraction"] < 0.15, (spec.name, stats)


# ---------------------------------------------------------------------------
# Roofline / byte model
# ---------------------------------------------------------------------------


class TestRoofline:
    def test_fetch_counts_replay_the_pipeline(self):
        # grid (bh, nq, nk) row-major: q re-fetched per (bh, i), k/v
        # per cell, o written once per (bh, i)
        (spec,) = fwd_specs(2, 512, 512, 64, block_q=128, block_k=256)
        by_name = {a.name: a for a in spec.inputs + spec.outputs}
        assert ka._fetch_count(by_name["q"], spec.grid) == 2 * 4
        assert ka._fetch_count(by_name["k"], spec.grid) == 2 * 4 * 2
        assert ka._fetch_count(by_name["o"], spec.grid) == 2 * 4

    def test_fetch_count_dependence_probe_on_huge_grid(self):
        arg = BlockArg(
            name="x", shape=(1 << 20, 128), block=(1, 128),
            index_map=lambda i, j, k: (i, 0), dtype="float32",
        )
        # 2^21 cells >> the simulation cap; the probe sees dependence
        # on axis 0 only -> one fetch per axis-0 value
        assert ka._fetch_count(arg, (1 << 19, 2, 2)) == 1 << 19
        assert ka._fetch_count(arg, (1 << 19, 2, 2)) == 1 << 19

    def test_roofline_fields(self):
        (spec,) = fwd_specs(2, 512, 512, 64, block_q=128, block_k=128)
        r = ka.roofline(spec, device_kind=V5E)
        assert r["flops"] > 0 and r["bytes"] > 0
        assert r["ceiling_tflops"] <= 197.0 + 1e-9
        assert r["bound"] in ("compute", "memory", "grid")
        assert r["predicted_tflops"] <= r["ceiling_tflops"] + 1e-9

    def test_larger_tiles_predict_faster_at_long_context(self):
        # the measured r05 fact the model must reproduce: (1024, 1024)
        # beats (128, 128) at the long shape
        def t(b):
            specs = fwd_specs(
                8, 16384, 16384, 128, block_q=b, block_k=b,
            )
            return ka.predict_config(specs, device_kind=V5E)["time_s"]

        assert t(1024) < t(512) < t(128)


# ---------------------------------------------------------------------------
# Prune acceptance on the recorded sweep fixture
# ---------------------------------------------------------------------------


class TestPruneRecordedSweep:
    @pytest.fixture(scope="class")
    def fixture(self):
        with open(os.path.join(DATA, "attn_sweep_r05.json")) as f:
            return json.load(f)

    @pytest.mark.parametrize("shape", ["long", "mha"])
    def test_prune_eliminates_30pct_and_keeps_the_best(
        self, fixture, shape
    ):
        from tools import attn_tune

        sweep = next(
            s for s in fixture["sweeps"] if s["shape"] == shape
        )
        measured = {
            tuple(int(x) for x in cell.split(",")): tflops
            for cell, tflops in sweep["cells"].items()
        }
        verdicts = attn_tune._prune_verdicts(
            shape, sweep["mode"], sweep["blocks"], 1.5, fixture["chip"]
        )
        assert set(verdicts) == set(measured)
        kept = {
            c for c, (v, _, _) in verdicts.items() if v == "KEEP"
        }
        pruned = len(verdicts) - len(kept)
        # >= 30% of the default sweep grid eliminated...
        assert pruned >= 0.3 * len(verdicts), (pruned, len(verdicts))
        # ...while every config within 5% of the measured best survives
        best = max(measured.values())
        within = {c for c, m in measured.items() if m >= 0.95 * best}
        assert within <= kept, (within, kept)

    def test_dq_only_prune_prices_the_dq_kernel_alone(self):
        """The bwd-only phase-2 sweep varies dq tiles with dkdv
        pinned: its keep set must come from a dq-only prediction, not
        the combined dkdv+dq one (a cell with a slow dkdv can hold
        the best dq tile)."""
        from tools import attn_tune

        combined = attn_tune._prune_verdicts(
            "tiny", "bwd-only", [128, 256], 1e9, V5E
        )
        dq_only = attn_tune._prune_verdicts(
            "tiny", "dq-only", [128, 256], 1e9, V5E
        )
        assert set(combined) == set(dq_only)
        for cell in dq_only:
            # dq-only predictions price strictly less work
            assert (
                dq_only[cell][1]["time_s"]
                < combined[cell][1]["time_s"]
            )

    def test_infeasible_cells_prune_regardless_of_speed(self):
        from tools import attn_tune

        verdicts = attn_tune._prune_verdicts(
            "long", "fwd", [1024, 4096], 1e9, V5E
        )
        verdict, _, reason = verdicts[(4096, 4096)]
        assert verdict == "PRUNE" and "infeasible" in reason
        assert "kernel-vmem-overflow" in reason


# ---------------------------------------------------------------------------
# Tuning cache round-trips
# ---------------------------------------------------------------------------


class TestTuneCache:
    @pytest.fixture(autouse=True)
    def _clean(self, monkeypatch):
        monkeypatch.delenv(tune_cache.ENV_VAR, raising=False)
        tune_cache.reset()
        yield
        tune_cache.reset()

    def _arm(self, monkeypatch, tmp_path, data):
        path = tmp_path / "tune_cache.json"
        path.write_text(json.dumps(data))
        monkeypatch.setenv(tune_cache.ENV_VAR, str(path))
        tune_cache.reset()
        return str(path)

    def test_flash_round_trip(self, monkeypatch, tmp_path):
        self._arm(monkeypatch, tmp_path, {
            "version": 1,
            "flash_attention": [{
                "sq": 4096, "d": 64, "causal": True, "dtype": None,
                "backend": None,
                "tiles": {"fwd": [512, 1024], "bwd": [256, 512]},
            }],
        })
        assert fa._tuned_tile("fwd", 4096, 4096, 64, True) == (512, 1024)
        assert fa._tuned_tile("bwd", 4096, 4096, 64, True) == (256, 512)
        # no entry for this mode / shape -> (None, None)
        assert fa._tuned_tile("bwd_dq", 4096, 4096, 64, True) == (None, None)
        assert fa._tuned_tile("fwd", 8192, 8192, 64, True) == (None, None)

    def test_cached_tile_must_divide_the_axis(self, monkeypatch, tmp_path):
        self._arm(monkeypatch, tmp_path, {
            "flash_attention": [{
                "sq": 4096, "d": 64, "causal": True,
                "tiles": {"fwd": [512, 1024]},
            }],
        })
        # cross-attention sk=768: the cached bk=1024 cannot tile it
        assert fa._tuned_tile("fwd", 4096, 768, 64, True) == (512, None)

    def test_cache_wins_over_source_table(self, monkeypatch, tmp_path):
        # (16384, 128, True) is a committed _TUNED_TILES entry
        assert fa._tuned_tile("fwd", 16384, 16384, 128, True) == \
            (1024, 1024)
        self._arm(monkeypatch, tmp_path, {
            "flash_attention": [{
                "sq": 16384, "d": 128, "causal": True,
                "tiles": {"fwd": [512, 512]},
            }],
        })
        assert fa._tuned_tile("fwd", 16384, 16384, 128, True) == (512, 512)

    def test_backend_mismatch_falls_through(self, monkeypatch, tmp_path):
        self._arm(monkeypatch, tmp_path, {
            "flash_attention": [{
                "sq": 4096, "d": 64, "causal": True,
                "backend": "TPU v999",
                "tiles": {"fwd": [512, 512]},
            }],
        })
        assert fa._tuned_tile("fwd", 4096, 4096, 64, True) == (None, None)

    def test_layer_norm_round_trip(self, monkeypatch, tmp_path):
        self._arm(monkeypatch, tmp_path, {
            "layer_norm": [{"hidden": 4096, "block_rows": 16}],
        })
        assert ln._block_rows(16384, 4096) == 16
        # uncached hidden falls back to the source table
        assert ln._block_rows(16384, 1024) == \
            ln._TUNED_BLOCK_ROWS[1024]

    def test_dispatch_uses_cached_tile(self, monkeypatch, tmp_path):
        """End to end: the cache entry changes the block shape of the
        REAL traced pallas_call."""
        self._arm(monkeypatch, tmp_path, {
            "flash_attention": [{
                "sq": 640, "d": 64, "causal": False,
                "tiles": {"fwd": [64, 128]},
            }],
        })
        captured = {}
        real = fa.pl.pallas_call

        def spy(kernel, **kw):
            captured.update(kw)
            return real(kernel, **kw)

        monkeypatch.setattr(fa.pl, "pallas_call", spy)
        q = jnp.zeros((1, 640, 64), jnp.bfloat16)
        jax.eval_shape(
            lambda q, k, v: fa.flash_fwd(
                q, k, v, None, scale=1.0, causal=False
            ),
            q, q, q,
        )
        assert captured["in_specs"][0].block_shape == (1, 64, 64)
        assert captured["in_specs"][1].block_shape == (1, 128, 64)

    def test_update_flash_merge_write(self, tmp_path, monkeypatch):
        path = str(tmp_path / "cache.json")
        tune_cache.update_flash(
            path, sq=2048, d=64, causal=True,
            tiles={"fwd": (1024, 1024)},
        )
        tune_cache.update_flash(
            path, sq=2048, d=64, causal=True,
            tiles={"fwd": (512, 512), "bwd": (256, 1024)},
        )
        tune_cache.update_flash(
            path, sq=4096, d=64, causal=True,
            tiles={"fwd": (256, 256)},
        )
        data = json.loads(open(path).read())
        assert len(data["flash_attention"]) == 2  # same-key merged
        monkeypatch.setenv(tune_cache.ENV_VAR, path)
        tune_cache.reset()
        assert fa._tuned_tile("fwd", 2048, 2048, 64, True) == (512, 512)
        assert fa._tuned_tile("bwd", 2048, 2048, 64, True) == (256, 1024)
        assert fa._tuned_tile("fwd", 4096, 4096, 64, True) == (256, 256)

    def test_bwd_write_keeps_the_fwd_winner(self, tmp_path, monkeypatch):
        """The default attn_tune --cache-out flow: a fwd sweep's write
        followed by a bwd sweep's write to the SAME key must
        accumulate tile modes, not clobber."""
        path = str(tmp_path / "cache.json")
        tune_cache.update_flash(
            path, sq=2048, d=64, causal=True,
            tiles={"fwd": (1024, 1024)},
        )
        tune_cache.update_flash(
            path, sq=2048, d=64, causal=True,
            tiles={"bwd": (256, 1024), "bwd_dq": (512, 512)},
        )
        monkeypatch.setenv(tune_cache.ENV_VAR, path)
        tune_cache.reset()
        assert fa._tuned_tile("fwd", 2048, 2048, 64, True) == (1024, 1024)
        assert fa._tuned_tile("bwd", 2048, 2048, 64, True) == (256, 1024)
        assert fa._tuned_tile("bwd_dq", 2048, 2048, 64, True) == (512, 512)

    def test_malformed_cache_warns_and_is_ignored(
        self, monkeypatch, tmp_path
    ):
        path = tmp_path / "bad.json"
        path.write_text("{not json")
        monkeypatch.setenv(tune_cache.ENV_VAR, str(path))
        tune_cache.reset()
        with pytest.warns(UserWarning, match="malformed tuning cache"):
            assert tune_cache.flash_tiles("fwd", 2048, 64, True) is None
        # and dispatch falls back to the source table untouched
        assert fa._tuned_tile("fwd", 16384, 16384, 128, True) == \
            (1024, 1024)


# ---------------------------------------------------------------------------
# Defaults, report plumbing, board publication
# ---------------------------------------------------------------------------


class TestDefaultsAndReport:
    def test_default_kernels_are_clean(self):
        report = ka.analyze_default_kernels(device_kind=V5E)
        assert report.findings == [], report.render()
        assert set(report.rules_run) == set(ka.KERNEL_PASSES)
        names = {e["name"] for e in report.sections["kernels"]}
        assert names == {
            "flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq",
            "layer_norm_fwd", "layer_norm_bwd", "paged_decode_fwd",
        }
        for e in report.sections["kernels"]:
            assert e["vmem"]["total_bytes"] <= e["vmem_budget_bytes"]

    def test_pass_timings_recorded(self):
        report = ka.analyze_default_kernels(device_kind=V5E)
        for name in ka.KERNEL_PASSES:
            assert name in report.pass_timings

    def test_rules_are_cataloged(self):
        for rule in (
            "kernel-vmem-overflow", "kernel-tile-misaligned",
            "kernel-grid-oob", "kernel-block-race",
            "kernel-dead-tiles", "kernel-hardcoded-block",
        ):
            assert rule in analysis.RULES

    def test_publish_kernel_report_gauges_the_board(self):
        from apex_tpu.observability.metrics import board

        report = ka.analyze_default_kernels(device_kind=V5E)
        ka.publish_kernel_report(report)
        snap = board.snapshot()
        assert snap["analysis/kernels/errors"] == 0
        assert snap["analysis/kernels/flash_fwd/vmem_bytes"] > 0
        assert snap["analysis/kernels/flash_fwd/predicted_tflops"] > 0
        assert 0 < snap["analysis/kernels/flash_fwd/dead_tile_waste"] < 0.15


# ---------------------------------------------------------------------------
# repo_lint source rule (the kernel-hardcoded-block satellite)
# ---------------------------------------------------------------------------


def test_repo_lint_kernel_hardcoded_block():
    from tools import repo_lint

    planted = [
        "o, lse = fa.flash_fwd(q, k, v, None, scale=s,",
        "                      block_q=128, block_k=block)",
    ]
    got = repo_lint._kernel_violations("x/m.py", planted, jitted=True)
    assert len(got) == 1 and got[0][1] == 2
    assert "tuned-tile lookup" in got[0][3]

    # variable-valued plumbing and None defaults never match
    clean = [
        "def flash_fwd(q, k, v, *, block_q=None, block_k=None):",
        "    fa.flash_fwd(q, k, v, None, block_q=bq, block_k=bk)",
    ]
    assert repo_lint._kernel_violations("x/m.py", clean, True) == []
    # host-side files (tuners, tests) are exempt
    assert repo_lint._kernel_violations("x/m.py", planted, False) == []
    # the waiver comment works like every other repo_lint rule
    waived = ["flash_fwd(q, k, v, block_q=128)  # repo-lint: allow why"]
    assert repo_lint._kernel_violations("x/m.py", waived, True) == []
