"""Step-time attribution, roofline, and the bench regression gate.

Covers ISSUE 6: the trace parser on recorded fixtures (clean + a
planted unattributable gap), cost-model attribution of a real jitted
step (matmul dominance, named-scope bucketing), the shared peak/bucket
model in ``observability.meter`` (and the pin that bench.py no longer
carries its own copy), the watchdog fraction rules, and
``tools/bench_diff.py`` — including the committed r03→r05 flash
flatline, the exact miss this layer exists to catch.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from apex_tpu.observability import attribution as A
from apex_tpu.observability import meter as M
from apex_tpu.observability.metrics import board

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the chip the cost-model tests model (the CPU they run on has no peak)
V5E = "TPU v5 lite"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

sys.path.insert(0, REPO)

from tools import bench_diff as bd  # noqa: E402


def _load_fixture(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# trace parser on recorded fixtures
# ---------------------------------------------------------------------------


class TestTraceFixtures:
    def test_clean_fixture_buckets_and_sum(self):
        meas = A.attribute_trace(_load_fixture("attribution_trace_clean.json"))
        assert meas.source == "device-ops"
        # wrappers (while.1 / jit_train_step) and host frames excluded:
        # exactly the five op rows, 1400us of busy time
        assert meas.events == 5
        assert meas.busy_ms == pytest.approx(1.4)
        assert meas.bucket_ms["matmul"] == pytest.approx(0.9)
        assert meas.bucket_ms["norm_elementwise"] == pytest.approx(0.3)
        assert meas.bucket_ms["collective"] == pytest.approx(0.2)
        fr = meas.fractions()
        assert sum(fr.values()) == pytest.approx(1.0, abs=1e-9)
        # 50us of dispatch gap over a 1450us span
        assert fr["host_stall"] == pytest.approx(50 / 1450, abs=1e-6)
        assert fr["collective"] == pytest.approx(
            (200 / 1400) * (1400 / 1450), abs=1e-6
        )

    def test_gap_fixture_detects_host_stall(self):
        meas = A.attribute_trace(_load_fixture("attribution_trace_gap.json"))
        fr = meas.fractions()
        assert sum(fr.values()) == pytest.approx(1.0, abs=1e-9)
        # the planted 1000us hole: no op accounts for it -> host stall
        assert fr["host_stall"] == pytest.approx(1050 / 2450, abs=1e-6)
        assert fr["host_stall"] > 0.25
        # busy time unchanged: the gap shifts ops, it does not add work
        assert meas.busy_ms == pytest.approx(1.4)

    def test_hlo_map_overrides_name_heuristic(self):
        meas = A.attribute_trace(
            _load_fixture("attribution_trace_clean.json"),
            hlo_map={"dot.12": "attention"},
        )
        assert meas.bucket_ms["attention"] == pytest.approx(0.5)
        assert meas.bucket_ms["matmul"] == pytest.approx(0.4)

    def test_executor_span_fallback_uses_cost_weights(self):
        trace = {"traceEvents": [
            {"ph": "M", "pid": 1, "name": "process_name",
             "args": {"name": "/host:CPU"}},
            {"ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 800,
             "name": "TfrtCpuExecutable::Execute", "args": {}},
            {"ph": "X", "pid": 1, "tid": 1, "ts": 900, "dur": 100,
             "name": "TfrtCpuExecutable::Execute", "args": {}},
        ]}
        meas = A.attribute_trace(
            trace, cost_weights={"matmul": 0.75, "collective": 0.25}
        )
        assert meas.source == "executor-spans"
        fr = meas.fractions()
        assert sum(fr.values()) == pytest.approx(1.0, abs=1e-9)
        assert fr["host_stall"] == pytest.approx(0.1)
        assert meas.bucket_ms["matmul"] == pytest.approx(0.675)

    def test_stall_split_by_innermost_host_phase(self):
        """Idle gaps between device operations go to the innermost
        serve/* / engine/* annotation open over them, on the trace's
        own clock; what none covers is `unannotated`; the split sums to
        the stall."""
        def op(ts, dur, name):
            return {"ph": "X", "pid": 1, "tid": 1, "ts": ts, "dur": dur,
                    "name": name}

        def host(ts, dur, name):
            return {"ph": "X", "pid": 2, "tid": 7, "ts": ts, "dur": dur,
                    "name": name}

        trace = {"traceEvents": [
            {"ph": "M", "pid": 1, "name": "process_name",
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "pid": 1, "tid": 1, "name": "thread_name",
             "args": {"name": "XLA Ops"}},
            {"ph": "M", "pid": 2, "name": "process_name",
             "args": {"name": "/host:CPU"}},
            op(0, 1000, "fusion.1"),          # gap 1000..5000
            op(5000, 1000, "fusion.2"),       # gap 6000..9000
            op(9000, 1000, "fusion.3"),
            host(500, 6000, "serve/step"),    # 500..6500
            host(2000, 2000, "engine/stage"),  # 2000..4000, inside it
            host(2100, 100, "$scheduler.py:1 _count"),   # not a phase
            host(4500, 1000, "engine/decode"),  # 4500..5500
        ]}
        meas = A.attribute_trace(trace)
        assert meas.stall_ms == pytest.approx(7.0)
        by = meas.stall_by_phase_ms
        # gap 1: step 1000-2000 and 4000-4500, stage 2000-4000,
        # decode 4500-5000; gap 2: step 6000-6500, nothing after
        assert by["serve/step"] == pytest.approx(1.0 + 0.5 + 0.5)
        assert by["engine/stage"] == pytest.approx(2.0)
        assert by["engine/decode"] == pytest.approx(0.5)
        assert by[A.UNANNOTATED] == pytest.approx(2.5)
        assert sum(by.values()) == pytest.approx(meas.stall_ms)
        # fractions() is untouched
        assert meas.fractions()["host_stall"] == pytest.approx(0.7)

    def test_empty_trace_is_all_zero_not_nan(self):
        meas = A.attribute_trace({"traceEvents": []})
        fr = meas.fractions()
        assert fr == {"compute": 0.0, "collective": 0.0, "host_stall": 0.0}

    def test_trace_step_period_median_rejects_outlier(self):
        # the same op recurring every 1000us, except one 50000us gap
        # (the profiler's first-capture anomaly): the median period is
        # still the honest step time
        evs = [
            {"ph": "X", "pid": 1, "tid": 1, "name": "dot.12",
             "ts": ts, "dur": 10, "args": {}}
            for ts in (0, 50_000, 51_000, 52_000, 53_000)
        ]
        period = A.trace_step_period({"traceEvents": evs})
        assert period == pytest.approx(1000 / 1e6)
        # single occurrence per op -> indeterminate, not a crash
        assert A.trace_step_period(
            _load_fixture("attribution_trace_clean.json")
        ) == 0.0


# ---------------------------------------------------------------------------
# cost-model attribution of a real jitted step
# ---------------------------------------------------------------------------


def _toy_step_hlo(d=512, batch=256):
    def step(params, x, y):
        def loss_fn(p):
            h = jnp.tanh(x @ p["w1"])
            pred = h @ p["w2"]
            return jnp.mean((pred - y) ** 2)

        loss, g = jax.value_and_grad(loss_fn)(params)
        new = jax.tree_util.tree_map(
            lambda p, gg: p - 1e-2 * gg, params, g
        )
        return new, loss

    params = {"w1": jnp.ones((d, d)), "w2": jnp.ones((d, d))}
    x = jnp.ones((batch, d))
    y = jnp.ones((batch, d))
    return jax.jit(step).lower(params, x, y).compile().as_text()


class TestCostModel:
    def test_matmul_bucket_dominates_toy_train_step(self):
        cost = A.attribute_cost_model(_toy_step_hlo(), device_kind=V5E)
        total = cost.total_flops
        assert total > 0
        # fwd+bwd of two d x d matmuls: the dots own nearly all FLOPs —
        # the dominance claim the ISSUE pins for the cost model
        assert cost.buckets["matmul"]["flops"] > 0.8 * total
        # est time is bandwidth-ruled at this size, where the update's
        # elementwise bytes legitimately compete — matmul still holds a
        # substantial share
        assert cost.bucket_fractions()["matmul"] > 0.25
        fr = cost.fractions()
        assert sum(fr.values()) == pytest.approx(1.0)
        assert fr["host_stall"] == 0.0  # invisible to the compiled program

    def test_named_scope_buckets_dot_as_attention(self):
        def f(x, w):
            with jax.named_scope("flash_attention_core"):
                s = x @ w
            return jnp.sum(s)

        text = jax.jit(f).lower(
            jnp.ones((64, 64)), jnp.ones((64, 64))
        ).compile().as_text()
        cost = A.attribute_cost_model(text, device_kind=V5E)
        assert cost.buckets["attention"]["flops"] > 0
        assert cost.buckets["matmul"]["flops"] == 0.0

    def test_dot_flops_exact(self):
        text = jax.jit(lambda a, b: a @ b).lower(
            jnp.ones((32, 48)), jnp.ones((48, 16))
        ).compile().as_text()
        cost = A.attribute_cost_model(text, device_kind=V5E)
        assert cost.total_flops == pytest.approx(2 * 32 * 16 * 48)

    def test_multi_program_merge_and_bucket_map(self):
        t1 = _toy_step_hlo(d=32, batch=8)
        t2 = _toy_step_hlo(d=32, batch=8)
        merged = A.attribute_cost_model([t1, t2], device_kind=V5E)
        single = A.attribute_cost_model(t1, device_kind=V5E)
        assert merged.total_flops == pytest.approx(2 * single.total_flops)
        hmap = A.hlo_bucket_map(t1)
        assert hmap  # raw instruction names -> bucket
        assert set(hmap.values()) <= set(M.BUCKETS)

    def test_collective_bucketed_from_psum_hlo(self):
        hlo = """
HloModule m, entry_computation_layout={(f32[1024]{0})->f32[1024]{0}}

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.1 = f32[] add(f32[] %a, f32[] %b)
}

ENTRY %main (p0: f32[1024]) -> f32[1024] {
  %p0 = f32[1024]{0} parameter(0)
  %mul.1 = f32[1024]{0} multiply(f32[1024]{0} %p0, f32[1024]{0} %p0)
  ROOT %all-reduce.3 = f32[1024]{0} all-reduce(f32[1024]{0} %mul.1), replica_groups={}, to_apply=%sum
}
"""
        cost = A.attribute_cost_model(hlo, device_kind=V5E)
        assert cost.buckets["collective"]["bytes"] == 4096
        assert cost.fractions()["collective"] > 0


# ---------------------------------------------------------------------------
# the shared peak/bucket model (meter.py satellite)
# ---------------------------------------------------------------------------


class TestMeterModel:
    def test_peak_lookup_is_exact_and_has_no_default(self):
        assert M.peak_flops_for("TPU v5e") == 197e12
        assert M.peak_flops_for("TPU v5 lite") == 197e12
        assert M.peak_hbm_bandwidth_for("TPU v4") == 1228e9
        for lookup in (
            M.peak_flops_for, M.peak_hbm_bandwidth_for,
            M.peak_ici_bandwidth_for, M.vmem_bytes_for,
        ):
            for kind in ("cpu", "", "TPU v5p something"):
                with pytest.raises(M.UnknownDeviceError, match="known:"):
                    lookup(kind)

    def test_cost_model_refuses_the_local_cpu(self):
        with pytest.raises(M.UnknownDeviceError, match="cpu"):
            A.attribute_cost_model(_toy_step_hlo())

    def test_step_meter_without_a_known_chip_reports_no_mfu(self):
        class Dev:
            def __init__(self, kind):
                self.device_kind = kind

        def run(**kw):
            t = [0.0]
            m = M.StepMeter(flops_per_step=1e12, clock=lambda: t[0], **kw)
            for _ in range(3):
                m.tick()
                t[0] += 0.5
            return m

        unknown = run(devices=[Dev("cpu")])
        assert unknown.mfu == 0.0
        assert "train/mfu" not in unknown.summary()
        assert "train/mfu" not in run().summary()
        # the denominator is the devices handed in, not what is visible
        two = run(devices=[Dev("TPU v5 lite")] * 2)
        assert two.summary()["train/mfu"] == pytest.approx(
            1e12 / (0.5 * 2 * 197e12)
        )

    def test_chip_peak_flops_delegates_to_string_helper(self):
        class Dev:
            device_kind = "TPU v6 lite"

        assert M.chip_peak_flops(Dev()) == M.peak_flops_for("TPU v6 lite")

    def test_categorize_op_priorities(self):
        assert M.categorize_op("all-reduce") == "collective"
        assert M.categorize_op("all-gather-start") == "collective"
        # attention scope wins over the dot opcode: the attention
        # bucket owns its matmuls
        assert M.categorize_op(
            "dot", "jit(f)/flash_attention/dot_general"
        ) == "attention"
        assert M.categorize_op("dot", "jit(f)/mlp/dot_general") == "matmul"
        assert M.categorize_op("convolution") == "matmul"
        assert M.categorize_op(
            "fusion", "jit(f)/conv_general_dilated"
        ) == "matmul"
        # dtype casts must NOT ride the "conv" substring into matmul —
        # amp steps are full of them (both call paths: opcode from the
        # cost model, event-name lead token from the trace parser)
        assert M.categorize_op(
            "convert", "jit(f)/convert_element_type"
        ) == "norm_elementwise"
        assert M.categorize_op("convert", "convert_fusion.5") == \
            "norm_elementwise"
        assert M.categorize_op("tanh") == "norm_elementwise"
        assert M.categorize_op(
            "fusion", "jit(f)/layer_norm/reduce"
        ) == "norm_elementwise"
        assert M.categorize_op("copy") == "other"
        assert set((M.categorize_op(o) for o in (
            "dot", "all-reduce", "add", "copy"
        ))) <= set(M.BUCKETS)

    def test_bench_shares_the_meter_peak_model(self):
        """bench.py must not carry its own peak table (the satellite's
        one-denominator pin)."""
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "bench", os.path.join(REPO, "bench.py")
        )
        bench = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bench)
        assert bench._chip_peak is M.chip_peak_flops
        assert bench._train_flops is M.transformer_train_flops
        import re

        with open(os.path.join(REPO, "bench.py")) as f:
            src = f.read()
        # no local peak-FLOPs constants (197e12-style literals; the
        # 1e12 TFLOP unit conversion is fine)
        assert not re.search(r"\b\d{2,}(\.\d+)?e12\b", src), (
            "bench.py grew its own peak constant; use "
            "observability.meter.peak_flops_for"
        )


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------


class TestRoofline:
    def test_rows_verdicts_and_total_mfu(self):
        cost = A.attribute_cost_model(
            _toy_step_hlo(), device_kind="TPU v5e"
        )
        step_time = 1e-3
        rows = A.roofline_report(cost, step_time_s=step_time)
        total = rows[-1]
        assert total.bucket == "total"
        assert total.pct_peak == pytest.approx(
            cost.total_flops / (step_time * M.peak_flops_for("TPU v5e"))
        )
        by_bucket = {r.bucket: r for r in rows}
        # a d=512 matmul at AI ~ 50 FLOP/B sits under the v5e ridge
        # (197e12/819e9 ~ 241): bandwidth-bound verdict
        assert by_bucket["matmul"].bound == "bandwidth"
        for r in rows[:-1]:
            assert r.bound in ("compute", "bandwidth", "comm")
        assert "bucket" in A.render_roofline(rows).splitlines()[0]

    def test_measured_shares_scale_bucket_time(self):
        cost = A.attribute_cost_model(_toy_step_hlo(), device_kind=V5E)
        meas = A.attribute_trace(
            _load_fixture("attribution_trace_clean.json")
        )
        rows = A.roofline_report(cost, step_time_s=1.45e-3, measured=meas)
        by_bucket = {r.bucket: r for r in rows}
        # matmul owned 900/1450 of the measured span
        assert by_bucket["matmul"].time_ms == pytest.approx(0.9, rel=1e-6)


# ---------------------------------------------------------------------------
# publication + the watchdog fraction rules
# ---------------------------------------------------------------------------


class TestFractionRules:
    def teardown_method(self):
        board.clear()

    def test_rules_fire_from_attribution_object(self):
        import apex_tpu.observability as obs

        wd = obs.Watchdog(
            rules=[obs.CollectiveFractionRule(max_fraction=0.3),
                   obs.HostStallRule(max_fraction=0.2)],
            attribution={"compute": 0.3, "collective": 0.4,
                         "host_stall": 0.3},
        )
        fired = {e.rule for e in wd.check(0)}
        assert fired == {"collective_fraction", "host_stall"}

    def test_rules_fall_back_to_board_and_stay_silent_without(self):
        import apex_tpu.observability as obs

        wd = obs.Watchdog(rules=[obs.HostStallRule(max_fraction=0.15)])
        assert wd.check(0) == []  # nothing published -> silent
        meas = A.attribute_trace(
            _load_fixture("attribution_trace_gap.json")
        )
        A.publish_attribution(meas)
        events = wd.check(64)
        assert [e.rule for e in events] == ["host_stall"]
        assert events[0].value == pytest.approx(1050 / 2450, abs=1e-6)

    def test_publish_writes_board_and_reporter(self, tmp_path):
        import apex_tpu.observability as obs

        out = tmp_path / "attr.jsonl"
        rep = obs.Reporter([obs.JSONLSink(str(out))])
        meas = A.attribute_trace(
            _load_fixture("attribution_trace_clean.json")
        )
        fr = A.publish_attribution(meas, reporter=rep, step=7)
        rep.close()
        assert board.get("attribution/collective_fraction") == \
            pytest.approx(fr["collective"])
        recs = [json.loads(l) for l in out.read_text().splitlines()]
        names = {r["metric"] for r in recs}
        assert "attribution/host_stall_fraction" in names
        assert "attribution/bucket/matmul" in names
        assert all(list(r)[:4] == ["metric", "value", "unit",
                                   "vs_baseline"] for r in recs)

    def test_default_rules_include_fraction_rules(self):
        import apex_tpu.observability as obs

        rules = obs.default_rules(host_stall={"max_fraction": 0.5})
        names = [r.name for r in rules]
        assert "collective_fraction" in names
        assert "host_stall" in names
        assert [r for r in rules if r.name == "host_stall"][0] \
            .max_fraction == 0.5


# ---------------------------------------------------------------------------
# tools/bench_diff.py — the regression/flatline gate
# ---------------------------------------------------------------------------


def _rec(metric, value, unit="", degenerate=False, **extra):
    rec = {"metric": metric, "value": value, "unit": unit,
           "vs_baseline": None}
    if degenerate:
        rec["degenerate"] = True
    rec.update(extra)
    return rec


def _write_jsonl(path, records):
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
    return str(path)


class TestBenchDiff:
    def test_regression_direction_higher_and_lower(self):
        cur = bd.collapse([_rec("tflops", 40.0), _rec("step_ms", 12.0)])
        base = bd.collapse([_rec("tflops", 50.0), _rec("step_ms", 10.0)])
        rows = {r["metric"]: r for r in bd.compare(cur, base)}
        assert rows["tflops"]["status"] == "regressed"  # higher-better
        assert rows["step_ms"]["status"] == "regressed"  # lower-better
        rows = {r["metric"]: r for r in bd.compare(base, cur)}
        assert rows["tflops"]["status"] == "improved"
        assert rows["step_ms"]["status"] == "improved"

    def test_median_of_trials(self):
        cur = bd.collapse([_rec("m", v) for v in (10.0, 99.0, 11.0)])
        assert cur["m"]["value"] == 11.0
        assert cur["m"]["trials"] == 3

    def test_degenerate_rows_excluded_from_gating(self):
        cur = bd.collapse([_rec("dp_x", 1.0, "img/s (dp=1)",
                                degenerate=True)])
        base = bd.collapse([_rec("dp_x", 100.0, "img/s (dp=8)")])
        rows = bd.compare(cur, base)
        assert rows[0]["status"] == "degenerate"

    def test_flat_detection_and_tolerance(self):
        base = bd.collapse([_rec("tflops", 43.0)])
        flat = bd.collapse([_rec("tflops", 43.1)])
        moved = bd.collapse([_rec("tflops", 45.0)])
        assert bd.compare(flat, base)[0]["status"] == "flat"
        assert bd.compare(moved, base)[0]["status"] != "flat"

    def test_loader_handles_wrapper_and_jsonl(self, tmp_path):
        w = tmp_path / "wrap.json"
        w.write_text(json.dumps(
            {"n": 5, "rc": 3, "parsed": _rec("m", None, "NOT MEASURED")}
        ))
        recs = bd.load_records(str(w))
        assert len(recs) == 1 and recs[0]["metric"] == "m"
        j = _write_jsonl(tmp_path / "x.jsonl",
                         [_rec("a", 1.0), _rec("b", 2.0)])
        assert len(bd.load_records(j)) == 2

    def test_schema_check_degenerate_honesty(self):
        ok = [_rec("x", 1.0, "ms/step (dp=1, ...)", degenerate=True),
              _rec("y", 2.0, "img/s (dp=8, ...)")]
        assert bd.check_schema(ok) == []
        missing = [_rec("x", 1.0, "ms/step (dp=1, ...)")]
        assert any("not marked degenerate" in p
                   for p in bd.check_schema(missing))
        dishonest = [_rec("y", 2.0, "img/s (dp=8, ...)", degenerate=True)]
        assert any("real multi-device" in p
                   for p in bd.check_schema(dishonest))
        bad_order = [{"value": 1.0, "metric": "z", "unit": "",
                      "vs_baseline": None}]
        assert any("contract" in p for p in bd.check_schema(bad_order))

    def test_committed_rounds_reproduce_the_flatline_catch(self, tmp_path):
        """r03 vs r05: the flash line sat at 43 TFLOP/s and nothing
        failed — the gate must catch exactly that from the committed
        artifacts."""
        r05 = os.path.join(REPO, "BENCH_all_r05.json")
        r03 = os.path.join(REPO, "BENCH_all_r03.json")
        rc_flat = bd.main([
            r05, "--baseline", r03, "--fail-on-flat",
        ])
        assert rc_flat == 1
        rc_reg = bd.main([
            r05, "--baseline", r03, "--fail-on-regression",
        ])
        assert rc_reg == 0
        out = tmp_path / "diff.json"
        bd.main([r05, "--baseline", r03, "--json", str(out)])
        rows = {r["metric"]: r
                for r in json.loads(out.read_text())["rows"]}
        assert rows["long_context_flash_attn_tflops"]["status"] == "flat"
        assert rows["tp_gpt_block_step_ms"]["status"] == "degenerate"

    def test_fail_on_flat_when_metric_missing(self, tmp_path):
        cur = _write_jsonl(tmp_path / "c.jsonl", [_rec("other", 1.0)])
        base = _write_jsonl(tmp_path / "b.jsonl", [_rec("other", 1.0)])
        rc = bd.main([cur, "--baseline", base, "--fail-on-flat",
                      "long_context_flash_attn_tflops"])
        assert rc == 1

    def test_require_same_metrics(self, tmp_path):
        cur = _write_jsonl(tmp_path / "c.jsonl", [_rec("a", 1.0)])
        base = _write_jsonl(tmp_path / "b.jsonl",
                            [_rec("a", 1.0), _rec("b", 2.0)])
        assert bd.main([cur, "--baseline", base,
                        "--require-same-metrics"]) == 1
        assert bd.main([cur, "--baseline", base]) == 0

    def test_golden_cpu_line_passes_schema(self):
        golden = bd.load_records(
            os.path.join(REPO, "tools", "bench_golden_cpu.jsonl")
        )
        assert bd.check_schema(golden) == []
        # smoke + serving + train3d rows — the verify_tier1.sh PERF
        # pass runs all three configs against this file
        assert {r["metric"] for r in golden} == {
            "smoke_mlp_step_ms", "smoke_dp_mlp_step_ms",
            "serve_prefill_tokens_per_s", "serve_decode_tokens_per_s",
            "serve_ttft_ms",
            # the prefix-cache rows: warm-cache hit TTFT through the
            # scheduler + the deterministic analytic prefill-FLOPs
            # saving of a full hit (docs/serving.md "Prefix caching")
            "serve_prefix_hit_ttft_ms", "serve_prefill_flops_saved_pct",
            # the live ops plane rows (ISSUE 11): exporter scrape cost
            # + the deterministic burn-rate drill
            "ops_scrape_ms", "slo_alerts_fired",
            # the serving resilience rows (ISSUE 14): request goodput
            # under the serve chaos storm + p99 TTFT inflation vs the
            # fault-free reference (deterministic virtual-clock drill)
            "serve_chaos_goodput_pct", "serve_chaos_p99_inflation",
            # the speculative-decode rows (ISSUE 18): self-draft k=4
            # greedy acceptance (exact by construction) + emitted
            # tokens per decode step (docs/serving.md "Speculative
            # decoding")
            "serve_spec_accept_rate", "serve_spec_tokens_per_step",
            # the composable trainer's honest multi-device rows
            # (ISSUE 12): dp/tp >= 2 on the mocked 8-device mesh —
            # check_schema refuses degenerate train3d rows
            "train3d_dp2_step_ms", "train3d_tp2_step_ms",
            "train3d_dp2tp2_step_ms", "train3d_lint_errors",
            # the host-side analyzer row (ISSUE 19): lock-discipline +
            # replay-purity ERROR findings over the whole package,
            # pinned at 0 (docs/analysis.md "Concurrency &
            # replay-purity passes")
            "concurrency_lint_errors",
            # the goodput storm-drill rows (ISSUE 13): chaos-storm
            # goodput, zero-stall bound, ckpt enqueue/finalize stall,
            # input-stall fraction, bit-exact-resume drift
            "goodput_storm_pct", "goodput_zero_stall_pct",
            "goodput_ckpt_enqueue_ms", "goodput_ckpt_finalize_ms",
            "goodput_input_stall_frac", "goodput_resume_loss_drift",
            # the fleet control-plane rows (ISSUE 16): request goodput
            # under the crash+preempt+spike+deploy storm, accepted
            # requests lost by rolling deploys (must be 0), p99 TTFT
            # inflation vs the fault-free fixed-size reference
            "fleet_chaos_goodput_pct", "fleet_deploy_lost_requests",
            "fleet_p99_inflation",
            # the canary deploy-gate rows (ISSUE 20): ticks from window
            # open to the planted regression's FAIL verdict + rollback,
            # and FAIL verdicts across clean re-seeded deploys (must
            # stay 0.0 — docs/serving.md "Canary deploys")
            "fleet_canary_detect_ticks", "fleet_canary_false_positive",
        }


# ---------------------------------------------------------------------------
# bench.py degenerate marking (satellite pin)
# ---------------------------------------------------------------------------


class TestBenchEmit:
    def _bench(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "bench_for_emit", os.path.join(REPO, "bench.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_emit_degenerate_key_contract(self, capsys):
        bench = self._bench()
        bench._emit("m1", 1.0, "img/s (dp=1)", None, degenerate=True)
        bench._emit("m2", 2.0, "img/s (dp=8)", None)
        lines = [json.loads(l)
                 for l in capsys.readouterr().out.splitlines()]
        assert lines[0]["degenerate"] is True
        assert "degenerate" not in lines[1]
        # key order is the driver contract
        assert list(lines[0])[:4] == ["metric", "value", "unit",
                                      "vs_baseline"]
        # and --gate sees exactly what was printed
        assert bench._GATE_RECORDS[-2:] == lines

    def test_degenerate_sites_cover_multi_device_configs(self):
        """ddp_syncbn, tp_gpt and zero must keep marking their
        single-device runs: the source carries the degenerate= marking
        at each emit site (the honest-trajectory satellite)."""
        with open(os.path.join(REPO, "bench.py")) as f:
            src = f.read()
        assert src.count("degenerate=dp == 1") >= 3  # ddp, zero, smoke-dp
        assert src.count("degenerate=tp == 1") >= 1  # tp_gpt


# ---------------------------------------------------------------------------
# tools/step_profile.py acceptance (ISSUE 6)
# ---------------------------------------------------------------------------


class TestStepProfile:
    def test_resilient_target_fractions_on_a_device_with_no_peak(
        self, tmp_path
    ):
        """The trace -> attribution -> fractions path on the CPU: the
        fractions sum to 1 +- 0.02 over the three-way bucket set, and
        what needs a chip's peak (roofline, MFU, the cost model's time
        shares) reads "not measured" instead of an assumed chip's
        numbers.  The full acceptance line, roofline 'total' row and
        MFU agreement <= 5% included, is tests_tpu/test_step_profile.py."""
        out = tmp_path / "profile.json"
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("APEX_TPU_TRACE_STEPS", None)
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "step_profile.py"),
             "--target", "resilient", "--steps", "5",
             "--json", str(out)],
            capture_output=True, text=True, env=env, timeout=420,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        payload = json.loads(out.read_text())
        assert payload["device"] == {"platform": "cpu", "kind": "cpu"}
        assert payload["fraction_sum"] == pytest.approx(1.0, abs=0.02)
        fr = payload["fractions"]
        assert set(fr) == {"compute", "collective", "host_stall"}
        assert all(0.0 <= v <= 1.0 for v in fr.values())
        assert set(payload["bucket_fractions"]) == set(M.BUCKETS)
        assert payload["step_time_ms"] > 0
        for key in ("roofline", "mfu", "cost_fractions", "cost_buckets"):
            assert payload[key] == "not measured", key
        assert "step fractions (" in proc.stdout
        assert "on cpu)" in proc.stdout
        assert "MFU: not measured" in proc.stdout
        assert "roofline=" not in proc.stdout
        assert "'cpu'" in proc.stderr  # names the device it found

    def test_hlo_mode_needs_a_chip(self, tmp_path):
        """--hlo is the cost model alone: nothing in it survives without
        a peak, so it exits non-zero naming the device, no traceback."""
        hlo = tmp_path / "step.hlo"
        hlo.write_text(_toy_step_hlo(d=32, batch=8))
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "step_profile.py"),
             "--hlo", str(hlo)],
            capture_output=True, text=True, timeout=120,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        assert proc.returncode == 1
        assert "device kind 'cpu'" in proc.stderr
        assert "Traceback" not in proc.stderr and "MFU" not in proc.stdout
