"""The ``sched.*`` readers (benchmark/span_readers.py) on a synthetic `run`
and a hand-filled process ring: alignment by count, the window and
profiler filters, the raise on a short ring, nothing to read on a program
without the ring; and both serving cells rehearsed with the trace on print
all of their new names."""

import pytest

from benchmark import span_readers as sr
from benchmark.tests.test_cells import BENCH, rehearse

NEW = {m["name"]: m for m in BENCH["per_layer"] if m["name"].startswith("sched.")}


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def fill_step(rec, clk, *, admits=(), decode=True, batch=1.0, stage=2.0,
              device=50.0, retire=3.0, publish=4.0, loose=0.5):
    """One serve/step, milliseconds given per phase; ``admits`` is a list
    of (host_ms, device_ms); ``loose`` is the step's own time."""
    def spend(ms):
        clk.t += ms / 1e3

    with rec.phase("serve/step"):
        spend(loose)
        for host, dev in admits:
            with rec.phase("serve/admit"):
                with rec.phase("engine/stage", program="prefill_8"):
                    spend(host / 2)
                with rec.phase("engine/prefill"):
                    spend(dev)
                spend(host / 2)
        if decode:
            with rec.phase("serve/batch"):
                spend(batch)
            with rec.phase("engine/stage", program="decode"):
                spend(stage)
            with rec.phase("engine/decode"):
                spend(device)
            with rec.phase("serve/retire"):
                spend(retire)
        with rec.phase("serve/publish"):
            spend(publish)


@pytest.fixture
def ring(monkeypatch):
    from apex_tpu.observability import spans

    clk = Clock()
    rec = spans.SpanRecorder(256, clock=clk)
    monkeypatch.setattr(spans, "_PROCESS", rec)
    return rec, clk


def run_of(steps, window=10.0):
    """A `run` as the driver leaves it: (t, running, ctx_sum, prefill
    flops, decode flops, traced, tokens) per step."""
    return {"facts": {"window_s": window, "steps": [
        (t, 1, 0, 0.0, 0.0, traced, 0) for t, traced in steps]}}


def test_readers_line_up_by_count_and_filter_window_and_profiler(ring):
    rec, clk = ring
    fill_step(rec, clk, retire=900.0)          # warm-up: before the drive
    fill_step(rec, clk, retire=900.0)
    fill_step(rec, clk)                        # 1: in window, profiler off
    fill_step(rec, clk, retire=700.0)          # 2: traced: left out
    fill_step(rec, clk, admits=[(6.0, 60.0)], stage=4.0)   # 3: in window
    fill_step(rec, clk, decode=False, admits=[(8.0, 60.0)])   # 4: no decode
    fill_step(rec, clk, retire=800.0)          # 5: past the window
    run = run_of([(1.0, False), (2.0, True), (3.0, False), (4.0, False),
                  (11.0, False)])
    # hosts: step 1 = .5+1+2+3+4 = 10.5; step 3 = 10.5 + 2 + 6 = 18.5;
    # step 4 = .5 + 8 + 4 = 12.5
    assert sr.host_ms_per_step(run) == pytest.approx(12.5)
    assert sr.host_ms_per_decode_step(run) == pytest.approx((10.5 + 18.5) / 2)
    # the decode's own stage only: the prefill's lies under serve/admit
    assert sr.stage_ms_per_step(run) == pytest.approx((3.0 + 5.0) / 2)
    assert sr.retire_ms_per_step(run) == pytest.approx(3.0)
    assert sr.publish_ms_per_step(run) == pytest.approx(4.0)
    assert sr.admit_host_ms(run) == pytest.approx(7.0)


def test_short_ring_raises_with_both_counts(ring):
    rec, clk = ring
    fill_step(rec, clk)
    fill_step(rec, clk)
    run = run_of([(1.0, False), (2.0, False), (3.0, False)])
    with pytest.raises(RuntimeError, match=r"holds 2 .* took 3 steps"):
        sr.host_ms_per_step(run)


def test_nothing_to_read_returns_none(ring, monkeypatch):
    rec, clk = ring
    fill_step(rec, clk, decode=False)
    # no step of the run chosen (all traced), no decode, no admission
    assert sr.host_ms_per_step(run_of([(1.0, True)])) is None
    run = run_of([(1.0, False)])
    assert sr.host_ms_per_decode_step(run) is None
    assert sr.admit_host_ms(run) is None
    assert sr.host_ms_per_step(run_of([])) is None
    # a program from before the phases keeps no ring: not an error
    from apex_tpu.observability import spans

    monkeypatch.delattr(spans, "process_recorder")
    assert sr.ring_spans() is None
    for name in NEW:
        assert sr_read(name)(run) is None


def sr_read(name):
    from benchmark.run import read_metric

    return lambda run: read_metric(name, run)


def test_manifest_entries_of_the_new_metrics():
    assert len(NEW) == 6
    for m in NEW.values():
        assert (m["source"], m["layer"], m["unit"], m["better"]) == (
            "program_span", "serving host loop", "ms", "lower")
        assert len(m["workloads"]) == 1
        cell = m["workloads"][0]
        assert m["name"].endswith(".chat") == cell.endswith("chat-steady")
        assert m["moves"] == ("serve.itl_p95_ms" if cell.endswith("chat-steady")
                              else "serve.tokens_per_s")
    assert [m["name"] for m in BENCH["per_layer"]][-6:] == list(NEW)


@pytest.mark.parametrize("cell", sorted({m["workloads"][0] for m in NEW.values()}))
def test_traced_rehearsal_prints_every_new_name(cell):
    line, _ = rehearse(cell, "--trace", "1")
    want = {n for n, m in NEW.items() if cell in m["workloads"]}
    assert want <= set(line["rehearsal"])
    assert all(line["rehearsal"][n] > 0 for n in want)
    # and an untraced run prints what it printed before: no per-layer name
    line0, _ = rehearse(cell, "--trace", "0")
    assert not set(NEW) & (set(line0["rehearsal"]) | set(line0["metrics"]))
