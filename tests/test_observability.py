"""The observability subsystem: registry under jit on the 8-device
mesh, goodput accounting across an injected-chaos rollback, JSONL
schema convergence with bench.py, comm gauge publication, trace
scheduling, and the <1% registry overhead budget (ISSUE 3 acceptance).
"""

import json
import os
import struct
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu import parallel_state as ps
from apex_tpu.observability import (
    GoodputAccountant,
    JSONLSink,
    MetricRegistry,
    Reporter,
    StepMeter,
    TensorBoardSink,
    TraceScheduler,
    bench_record,
    board,
    transformer_train_flops,
)
from apex_tpu.observability.export import CSVSink, _masked_crc
from apex_tpu.observability.trace import parse_trace_spec, window_dir
from apex_tpu.parallel import comm
from apex_tpu.resilience import chaos, run_resilient

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# MetricRegistry
# ---------------------------------------------------------------------------


def test_registry_accumulate_fetch_under_jit_on_mesh(eight_devices):
    """Counters/gauges/max fold inside a jitted shard_map step over the
    8-device mesh; the host fetches on the cadence, never per step."""
    mesh = ps.initialize_model_parallel(devices=eight_devices)
    reg = MetricRegistry(fetch_every=4)
    reg.counter("steps")
    reg.gauge("grad_norm")
    reg.maximum("max_norm")
    state = reg.init()

    @jax.jit
    def step(mstate, x):
        def inner(mstate, local):
            norm = jnp.sqrt(
                jax.lax.psum(jnp.sum(local.astype(jnp.float32) ** 2), "dp")
            )
            return reg.update(
                mstate,
                {"steps": 1, "grad_norm": norm, "max_norm": norm},
            )

        return jax.shard_map(
            inner, mesh=mesh, in_specs=(P(), P("dp")), out_specs=P(),
            check_vma=False,
        )(mstate, x)

    for s in range(10):
        x = jnp.full((8, 4), float(s + 1))
        state = step(state, x)
        reg.observe(s, state)

    # cadence semantics: observe(8) materialized the copy started at
    # observe(4) — values are present but deliberately stale, and no
    # step in between blocked on the device
    assert reg.fetched_step == 4
    assert reg.values()["steps"] == 5.0  # counter after steps 0..4

    vals = reg.fetch()  # force-drain at shutdown
    assert reg.fetched_step == 9
    assert vals["steps"] == 10.0
    expected = float(np.sqrt(32.0) * 10.0)  # psum over all 32 elements
    np.testing.assert_allclose(vals["grad_norm"], expected, rtol=1e-6)
    np.testing.assert_allclose(vals["max_norm"], expected, rtol=1e-6)


def test_registry_rejects_undeclared_metric():
    reg = MetricRegistry()
    reg.gauge("known")
    with pytest.raises(KeyError):
        reg.update(reg.init(), {"typo": 1.0})


class _DeadBuffer:
    """An array-like whose host materialization fails — the shape of a
    device buffer poisoned by the crash being debugged."""

    def __float__(self):
        raise RuntimeError("device buffer dead")


def test_fetch_flushes_pending_even_when_inflight_raises():
    """ISSUE 5 satellite pin: the NEWEST (pending) stash lands in a
    ``finally`` — an exception materializing the OLDER in-flight copy
    must not leave the flight recorder's last frame a cadence stale."""
    reg = MetricRegistry(fetch_every=4)
    reg.gauge("x")
    reg._inflight = (0, {"x": _DeadBuffer()})
    reg._pending = (1, {"x": 2.5})
    with pytest.raises(RuntimeError, match="device buffer dead"):
        reg.fetch()
    assert reg.values()["x"] == 2.5  # the pending stash was flushed
    assert reg.fetched_step == 1
    # both buffers are consumed: a second fetch is clean
    assert reg.fetch() == {"x": 2.5}


def test_close_drains_best_effort_and_never_raises():
    """The dump path: per-value failures keep previous values, healthy
    scalars in the same stash still land, and close() returns."""
    reg = MetricRegistry(fetch_every=4)
    reg.gauge("dead")
    reg.gauge("alive")
    reg._inflight = (2, {"dead": 1.0, "alive": 1.0})
    reg._pending = (3, {"dead": _DeadBuffer(), "alive": 7.0})
    values = reg.close()
    assert values["alive"] == 7.0  # newest healthy value won
    assert values["dead"] == 1.0  # poisoned newest -> previous kept
    assert reg.fetched_step == 3
    assert reg._inflight is None and reg._pending is None


def test_close_fully_poisoned_stash_does_not_claim_freshness():
    """A stash where NOTHING materialized must not advance
    fetched_step: the flight dump would otherwise stamp cadence-old
    values with the crash step."""
    reg = MetricRegistry(fetch_every=4)
    reg.gauge("x")
    reg._inflight = (8, {"x": 1.0})
    reg._pending = (14, {"x": _DeadBuffer()})
    assert reg.close() == {"x": 1.0}
    assert reg.fetched_step == 8  # not 14: step 14 never landed


def test_registry_overhead_under_one_percent():
    """ISSUE 3 acceptance: at the default fetch cadence the registry
    adds <1% step-time overhead.

    The device-side claim is asserted on XLA's compiled cost model
    (flops + bytes accessed of an instrumented vs bare 32-step chunk):
    the registry adds a handful of scalar ops to a program, which the
    cost model prices deterministically — measured ~1e-7 relative flops
    and ~4e-5 relative bytes, four orders under the budget.  Wall clock
    on this 1-core shared container wobbles ±10% between IDENTICAL runs
    (tests/conftest.py documents ±30 s on a 240 s tier), so the timed
    comparison below is only a coarse tripwire for a host-path
    regression (e.g. an accidental per-step blocking fetch), not the
    <1% assertion itself.
    """
    reg = MetricRegistry(fetch_every=32)  # default cadence: fetch 1/32
    reg.gauge("loss")
    reg.counter("steps")
    x = jnp.eye(256, dtype=jnp.float32) * 0.5
    chunk = 32  # one fetch per chunk == the default cadence

    def make_chunk(instrumented):
        @jax.jit
        def fn(w, m):
            def body(carry, _):
                w, m = carry
                w = jnp.tanh(w @ x)
                loss = jnp.sum(w)  # both arms compute the loss — a real
                # step has it anyway; the registry ADDS only the fold
                if instrumented:
                    m = reg.update(m, {"loss": loss, "steps": 1})
                return (w, m), loss

            (w, m), losses = jax.lax.scan(body, (w, m), None, length=chunk)
            return w, m, losses[-1]

        return fn

    chunk_bare, chunk_inst = make_chunk(False), make_chunk(True)
    w0 = jnp.ones((256, 256), jnp.float32)
    m0 = reg.init()

    def costs(fn):
        c = fn.lower(w0, m0).compile().cost_analysis()
        c = c[0] if isinstance(c, (list, tuple)) else c
        return float(c.get("flops", 0.0)), float(c.get("bytes accessed", 0.0))

    bare_flops, bare_bytes = costs(chunk_bare)
    inst_flops, inst_bytes = costs(chunk_inst)
    assert bare_flops > 0 and bare_bytes > 0
    assert (inst_flops - bare_flops) / bare_flops < 0.01, (
        f"instrumented chunk flops {inst_flops} vs bare {bare_flops}"
    )
    assert (inst_bytes - bare_bytes) / bare_bytes < 0.01, (
        f"instrumented chunk bytes {inst_bytes} vs bare {bare_bytes}"
    )

    def time_once(fn, observe, base_step):
        t0 = time.perf_counter()
        w, m, loss = fn(w0, m0)
        if observe:
            for j in range(chunk):  # the real per-step host cost
                reg.observe(base_step + j, m)
        float(loss)  # device->host sync point
        return time.perf_counter() - t0

    for fn in (chunk_bare, chunk_inst):  # warmup/compile both arms
        w, m, loss = fn(w0, m0)
        float(loss)
    # PAIRED back-to-back trials: a background-load spike inflates both
    # halves of a pair, so the MIN ratio over pairs is stable where an
    # absolute min-of-each-arm is not (this 1-core box drifts ±30%
    # under concurrent suite load); one clean pair is enough, and a
    # systematic per-step blocking fetch would inflate EVERY pair
    ratios = []
    for t in range(9):
        tb = time_once(chunk_bare, False, 0)
        ti = time_once(chunk_inst, True, t * chunk)
        ratios.append(ti / tb)
    overhead = min(ratios) - 1.0
    assert overhead < 0.25, (
        f"host-path tripwire: best instrumented/bare chunk ratio "
        f"{min(ratios):.3f} — did a per-step blocking fetch sneak in? "
        f"(all ratios: {[round(r, 3) for r in ratios]})"
    )
    # and the fold actually happened
    assert reg.fetch()["steps"] > 0


# ---------------------------------------------------------------------------
# goodput accounting across an injected-chaos rollback
# ---------------------------------------------------------------------------


def test_goodput_accounting_across_chaos_rollback(tmp_path):
    """Chaos NaNs three consecutive steps (healing after 3 hits), the
    runner rolls back past two accepted-but-unsaved steps; the
    accountant's ledger matches RunResult exactly and prices the
    discarded work."""
    acct = GoodputAccountant()
    state = {"w": jnp.zeros(())}

    def step_fn(state, batch):
        grads = {"w": jnp.ones(())}
        grads = chaos.corrupt_tree(grads, int(batch))
        skipped = bool(jnp.isnan(grads["w"]) | jnp.isinf(grads["w"]))
        if not skipped:
            state = {"w": state["w"] + grads["w"]}
        return state, {"skipped": skipped}

    with chaos.inject(
        chaos.Fault(chaos.GRADS, steps=(3, 4, 5), mode="nan", max_hits=3)
    ):
        result = run_resilient(
            step_fn,
            state,
            lambda step: step,
            directory=tmp_path / "ckpt",
            num_steps=8,
            save_interval_steps=5,  # steps 1..2 accepted but UNSAVED
            rollback_after=3,
            observer=acct,
        )

    # first pass: 0,1,2 accepted (only 0 checkpointed), 3,4,5 skipped
    # -> rollback to anchor 0; replay 1..7 accepted (faults exhausted)
    assert result.skipped_steps == 3
    assert result.rollbacks == 1
    assert result.steps_run == 13
    assert acct.skipped == result.skipped_steps
    assert acct.rollbacks == result.rollbacks
    assert acct.executed == result.steps_run
    assert acct.accepted == 10
    # rollback span 5 - 0 = 5, of which 3 were the skips: steps 1 and 2
    # were accepted work the rollback threw away
    assert acct.discarded == 2
    assert acct.goodput() == pytest.approx(8 / 13)
    # step 0's increment survived in the restored checkpoint; replayed
    # steps 1..7 added the rest — the discarded first-pass 1..2 did not
    assert float(result.state["w"]) == 8.0


def test_goodput_prices_broken_skip_streaks_exactly(tmp_path):
    """A skip streak BROKEN by an accepted step inside the rollback
    span must not be double-charged: the runner reports the exact
    accepted-but-unsaved count (here 1 — step 7), not the span-minus-
    final-streak estimate (which would say 2)."""
    acct = GoodputAccountant()

    def step_fn(state, batch):
        grads = {"w": jnp.ones(())}
        grads = chaos.corrupt_tree(grads, int(batch))
        skipped = bool(jnp.isnan(grads["w"]) | jnp.isinf(grads["w"]))
        if not skipped:
            state = {"w": state["w"] + grads["w"]}
        return state, {"skipped": skipped}

    with chaos.inject(
        chaos.Fault(chaos.GRADS, steps=(6,), mode="nan", max_hits=1),
        chaos.Fault(chaos.GRADS, steps=(8, 9, 10), mode="nan", max_hits=3),
    ):
        result = run_resilient(
            step_fn,
            {"w": jnp.zeros(())},
            lambda step: step,
            directory=tmp_path / "ckpt",
            num_steps=12,
            save_interval_steps=5,
            rollback_after=3,
            observer=acct,
        )

    # pass 1: 0..5 accepted (saved at 0 and 5), 6 skip, 7 accept
    # (unsaved), 8..10 skip -> rollback to anchor 5; replay 6..11 clean
    assert result.skipped_steps == 4
    assert result.rollbacks == 1
    assert acct.discarded == 1  # ONLY step 7 — not (span 5 - streak 3) = 2
    assert acct.executed == result.steps_run == 17
    assert acct.accepted == 13
    assert acct.goodput() == pytest.approx(12 / 17)


def test_goodput_snapshot_is_the_stable_read_api():
    """ISSUE 5 satellite: snapshot() carries the monotonic counts +
    derived fractions consumers (flight dump, fleet rows, the example's
    final goodput line) read instead of reaching into fields."""
    acct = GoodputAccountant()
    for i in range(10):
        acct.on_step(i, skipped=(i >= 8))
    acct.on_rollback(9, 5, 2, discarded=1)
    acct.on_retry("save", 1, OSError("disk"))
    snap = acct.snapshot()
    assert snap == {
        "accepted": 8, "skipped": 2, "discarded": 1, "rollbacks": 1,
        "retries": 1, "resumes": 0, "preempted": False,
        "executed": 10, "productive": 7, "goodput": 0.7,
    }
    # a snapshot is a copy, not a live view
    acct.on_step(10, skipped=False)
    assert snap["accepted"] == 8


def test_goodput_counts_checkpoint_retries(tmp_path):
    """A healing checkpoint-save fault reaches the accountant through
    the runner's retry bridge."""
    from apex_tpu.resilience import RetryPolicy

    acct = GoodputAccountant()

    def step_fn(state, batch):
        return {"n": state["n"] + 1}, None

    with chaos.inject(
        chaos.Fault(
            chaos.CHECKPOINT_SAVE, steps=(2,), mode="raise", max_hits=1
        )
    ):
        with pytest.warns(RuntimeWarning, match="checkpoint save"):
            result = run_resilient(
                step_fn,
                {"n": jnp.zeros((), jnp.int32)},
                lambda step: step,
                directory=tmp_path / "ckpt",
                num_steps=4,
                policy=RetryPolicy(
                    max_attempts=3, backoff=0.0, sleep=lambda _: None
                ),
                observer=acct,
            )
    assert result.last_step == 3
    assert acct.retries == 1
    assert acct.goodput() == 1.0  # a retried save wastes no step


# ---------------------------------------------------------------------------
# export: schema convergence with bench.py, sinks
# ---------------------------------------------------------------------------


def test_jsonl_schema_round_trips_vs_bench_line(tmp_path, capsys):
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)

    bench._emit("bert_large_lamb_mfu", 0.5884, "MFU", 1.1768)
    bench_line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    path = tmp_path / "metrics.jsonl"
    with JSONLSink(path) as sink:
        sink.write(bench_record("bert_large_lamb_mfu", 0.5884, "MFU", 1.1768))
    ours = json.loads(path.read_text())

    assert ours == bench_line
    assert list(ours) == ["metric", "value", "unit", "vs_baseline"]


def test_jsonl_sink_writes_nonfinite_as_null(tmp_path):
    """NaN grad norms / untouched ±inf min-max seeds must not produce
    bare NaN tokens (invalid JSON for jq/JS consumers)."""
    path = tmp_path / "nan.jsonl"
    with JSONLSink(path) as sink:
        sink.write(bench_record("guard/grad_norm", float("nan"), "", None))
        sink.write(bench_record("m/min", float("inf"), "", None, step=2))
    lines = path.read_text().splitlines()
    assert "NaN" not in lines[0] and "Infinity" not in lines[1]
    assert json.loads(lines[0])["value"] is None
    assert json.loads(lines[1])["value"] is None
    assert json.loads(lines[1])["step"] == 2


def test_reporter_merges_sources_and_steps(tmp_path):
    reg = MetricRegistry(fetch_every=1)
    reg.gauge("train/loss", unit="nats")
    state = reg.update(reg.init(), {"train/loss": jnp.float32(2.5)})
    reg.observe(0, state)
    reg.fetch()

    clockv = [0.0]

    def clock():
        return clockv[0]

    meter = StepMeter(
        tokens_per_step=128,
        flops_per_step=transformer_train_flops(1000, 128),
        peak_flops=1e12,
        clock=clock,
    )
    for _ in range(3):
        meter.tick()
        clockv[0] += 0.25

    acct = GoodputAccountant()
    acct.on_step(0, skipped=False)
    acct.on_step(1, skipped=True)

    path = tmp_path / "telemetry.jsonl"
    with Reporter(
        [JSONLSink(path)], registry=reg, meter=meter, goodput=acct,
        include_board=False,
    ) as rep:
        values = rep.report(7)

    recs = [json.loads(line) for line in path.read_text().splitlines()]
    by_metric = {r["metric"]: r for r in recs}
    assert values["train/loss"] == 2.5
    assert by_metric["train/loss"]["unit"] == "nats"
    assert all(r["step"] == 7 for r in recs)
    assert by_metric["train/step_time_ms"]["value"] == pytest.approx(250.0)
    assert by_metric["train/goodput"]["value"] == 0.5
    assert by_metric["train/mfu"]["value"] == pytest.approx(
        6 * 1000 * 128 / (0.25 * 1e12)
    )
    # every line is the bench schema + step
    for r in recs:
        assert list(r)[:4] == ["metric", "value", "unit", "vs_baseline"]


def test_csv_sink_fixed_header(tmp_path):
    path = tmp_path / "m.csv"
    with CSVSink(path) as sink:
        sink.write(bench_record("a", 1, "u", None, step=0))
        sink.write(bench_record("b", 2, "u", None, step=1, extra="dropped"))
    lines = path.read_text().strip().splitlines()
    assert lines[0] == "metric,value,unit,vs_baseline,step"
    assert len(lines) == 3 and "dropped" not in lines[2]


def test_tensorboard_sink_valid_tfrecord_framing(tmp_path):
    with TensorBoardSink(tmp_path) as sink:
        sink.write(bench_record("train/loss", 2.5, "", None, step=3))
        sink.add_scalars(4, {"train/mfu": 0.5})
        path = sink.path
    data = open(path, "rb").read()
    events = []
    off = 0
    while off < len(data):
        (length,) = struct.unpack_from("<Q", data, off)
        (len_crc,) = struct.unpack_from("<I", data, off + 8)
        assert len_crc == _masked_crc(data[off:off + 8])
        payload = data[off + 12:off + 12 + length]
        (payload_crc,) = struct.unpack_from("<I", data, off + 12 + length)
        assert payload_crc == _masked_crc(payload)
        events.append(payload)
        off += 12 + length + 4
    assert len(events) == 3  # file_version + two scalar events
    assert b"brain.Event:2" in events[0]
    assert b"train/loss" in events[1] and b"train/mfu" in events[2]


# ---------------------------------------------------------------------------
# comm gauges on the board
# ---------------------------------------------------------------------------


def test_sync_gradients_publishes_board_gauges(eight_devices):
    board.clear()
    mesh = ps.initialize_model_parallel(devices=eight_devices)
    tree = {"w": jnp.ones((4096,)), "b": jnp.ones((8,))}
    fn = jax.jit(
        jax.shard_map(
            lambda t: comm.sync_gradients(t, wire="int8", chunks=2),
            mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False,
        )
    )
    hlo = fn.lower(tree).compile().as_text()
    summary = comm.collective_summary(hlo)
    snap = board.snapshot()

    assert snap["comm/sync/wire"] == "int8"
    assert snap["comm/sync/bucket_elements"] == 4096
    # the trace-time plan matches the compiled program's collectives:
    # chunked all_to_all (reduce-scatter phase) + all_gather phase, and
    # one exact psum for the small leaf
    assert (
        snap["comm/rs/collectives"]
        == summary.get("all-to-all", {}).get("count", 0)
    )
    assert (
        snap["comm/ag/collectives"]
        == summary.get("all-gather", {}).get("count", 0)
    )
    assert (
        snap["comm/sync/psum_leaves"]
        == summary.get("all-reduce", {}).get("count", 0)
    )

    comm.publish_collective_summary(summary, world=8)
    snap = board.snapshot()
    assert snap["comm/hlo/all_to_all_count"] == snap["comm/rs/collectives"]
    assert snap["comm/hlo/ring_wire_bytes"] == comm.ring_wire_bytes(
        summary, 8
    )
    board.clear()


# ---------------------------------------------------------------------------
# trace scheduling
# ---------------------------------------------------------------------------


def test_parse_trace_spec_forms():
    assert parse_trace_spec("120+3") == (120, 122, None)
    assert parse_trace_spec("5..9") == (5, 9, None)
    assert parse_trace_spec("7") == (7, 7, None)
    assert parse_trace_spec("4+2:/tmp/prof") == (4, 5, "/tmp/prof")
    with pytest.raises(ValueError):
        parse_trace_spec("banana")
    with pytest.raises(ValueError):
        parse_trace_spec("9..4")


def test_trace_scheduler_window(tmp_path):
    calls = []
    sched = TraceScheduler(
        "5+2", base_dir=str(tmp_path),
        _start_fn=lambda d: calls.append(("start", d)),
        _stop_fn=lambda: calls.append(("stop",)),
    )
    for step in range(10):
        sched.on_step(step)
    sched.stop()
    expect_dir = window_dir(str(tmp_path), 5, 6)
    assert calls == [("start", expect_dir), ("stop",)]
    assert os.path.isdir(expect_dir)
    assert not sched.active  # one window per arming

    idle = TraceScheduler(spec="", base_dir=str(tmp_path))
    for step in range(3):
        idle.on_step(step)  # cheap no-ops
    assert not idle.active


def test_trace_scheduler_rearms_after_rollback_rewind(tmp_path):
    """A rollback replay rewinding steps mid-window aborts the capture
    and retakes the window cleanly on the replay pass."""
    calls = []
    sched = TraceScheduler(
        "5+3", base_dir=str(tmp_path),
        _start_fn=lambda d: calls.append("start"),
        _stop_fn=lambda: calls.append("stop"),
    )
    for step in (0, 1, 2, 3, 4, 5, 6):  # window arms at 5
        sched.on_step(step)
    assert calls == ["start"]
    for step in (3, 4, 5, 6, 7, 8):  # rollback replay from step 3
        sched.on_step(step)
    # rewind to 3 aborts; the replay reaches 5 and recaptures 5..7
    assert calls == ["start", "stop", "start", "stop"]
    assert not sched.active and not sched.tracing

    # a rollback anchor INSIDE the window must not restart mid-window —
    # a partial capture under a dir named for the full range would lie
    calls2 = []
    s2 = TraceScheduler(
        "5+3", base_dir=str(tmp_path),
        _start_fn=lambda d: calls2.append("start"),
        _stop_fn=lambda: calls2.append("stop"),
    )
    for step in (4, 5, 6):
        s2.on_step(step)
    for step in (6, 7, 8, 9):  # replay from inside the window
        s2.on_step(step)
    assert calls2 == ["start", "stop"]


def test_trace_submodule_not_shadowed():
    """The package attribute `observability.trace` is the SUBMODULE (the
    trace() function is deliberately not re-exported — it would shadow
    the submodule), `apex_tpu.utils` keeps its two aliases, and the
    NVTX-style push/pop hooks and their shim are gone: a host span is
    `SpanRecorder.phase()`."""
    import importlib
    import types

    import apex_tpu.observability as obs
    import apex_tpu.utils as utils

    obs_trace = obs.trace
    assert isinstance(obs_trace, types.ModuleType)
    assert obs_trace is sys.modules["apex_tpu.observability.trace"]
    assert utils.trace is obs_trace.trace
    assert utils.annotate is obs_trace.annotate
    for name in ("nvtx_range", "range_push", "range_pop"):
        for mod in (obs, obs_trace, utils):
            assert not hasattr(mod, name)
    with pytest.raises(ModuleNotFoundError):
        importlib.import_module("apex_tpu.utils.profiling")
