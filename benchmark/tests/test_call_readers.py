"""The ``engine.dispatch_ms*`` / ``engine.exposed_read_ms*`` readers
(benchmark/call_readers.py) on a hand-filled process ring and a synthetic
trace: each reader's arithmetic, the window and profiler filters it shares
with the ``sched.*`` readers, nothing to read on a ring without the new
phases or a trace without the program; and the rehearsals of the four
serving cells print the names their trace can give."""

import pytest

from benchmark import call_readers as cr
from benchmark.tests.test_cells import BENCH, rehearse
from benchmark.tests.test_span_readers import Clock, run_of
from benchmark.trace.reduce import Trace

NEW = {m["name"]: m for m in BENCH["per_layer"]
       if m["name"].startswith(("engine.dispatch_ms", "engine.exposed_read_ms"))}


@pytest.fixture
def ring(monkeypatch):
    from apex_tpu.observability import spans

    clk = Clock()
    rec = spans.SpanRecorder(512, clock=clk)
    monkeypatch.setattr(spans, "_PROCESS", rec)
    return rec, clk


def spend(clk, ms):
    clk.t += ms / 1e3


def call(rec, clk, name, program, dispatch, reads, *, phases=True):
    """One read-at-once call: ``name`` around a dispatch and its reads
    (milliseconds); ``phases=False`` is a program from before them."""
    with rec.phase(name):
        if not phases:
            spend(clk, dispatch + sum(reads))
            return
        with rec.phase("engine/dispatch", program=program):
            spend(clk, dispatch)
        for what, ms in zip(("tokens", "finite"), reads):
            with rec.phase("engine/read", program=program, what=what):
                spend(clk, ms)


def gpt_step(rec, clk, *, prefills=(), decode=None, phases=True):
    """A GPT scheduler step: ``prefills`` [(program, dispatch, reads)] each
    under its admission, then ``decode`` (dispatch, reads)."""
    with rec.phase("serve/step"):
        spend(clk, 0.5)
        for program, dispatch, reads in prefills:
            with rec.phase("serve/admit"):
                with rec.phase("engine/stage", program=program):
                    spend(clk, 0.2)
                call(rec, clk, "engine/prefill", program, dispatch, reads,
                     phases=phases)
        if decode is not None:
            with rec.phase("engine/stage", program="decode"):
                spend(clk, 0.1)
            call(rec, clk, "engine/decode", "decode", *decode, phases=phases)
        with rec.phase("serve/publish"):
            spend(clk, 0.1)


def lazy_step(rec, clk, dispatches, decode_dispatch):
    """A hybrid stack's step: lazy prefills dispatched under their stage,
    resolved after the admit loop, then the decode block."""
    with rec.phase("serve/step"):
        for ms in dispatches:
            with rec.phase("serve/admit"):
                with rec.phase("engine/stage", program="prefill_256"):
                    spend(clk, 0.3)
                    with rec.phase("engine/dispatch", program="prefill_256"):
                        spend(clk, ms)
        for _ in dispatches:
            with rec.phase("engine/prefill"):
                with rec.phase("engine/read", program="prefill_256",
                               what="tokens"):
                    spend(clk, 9.0)
        call(rec, clk, "engine/decode", "decode", decode_dispatch,
             (250.0, 0.1))


def trace_of(**programs):
    """A synthetic trace whose programs line holds, per module name, the
    given device durations (ms)."""
    ev, t = [], 0.0
    for name, durs in programs.items():
        for d in durs:
            ev.append((t, d / 1e3, name))
            t += 1.0
    return Trace(ops={"/device:TPU:0": []}, modules={"/device:TPU:0": ev},
                 host=[])


def test_decode_readers(ring):
    rec, clk = ring
    gpt_step(rec, clk, decode=(9.0, (90.0, 9.0)))           # warm-up
    gpt_step(rec, clk, decode=(0.3, (4.0, 0.2)))            # 1
    gpt_step(rec, clk, decode=(5.0, (50.0, 5.0)))           # 2: traced
    gpt_step(rec, clk, decode=(0.5, (4.4, 0.4)),            # 3
             prefills=[("prefill_128", 0.7, (20.0, 0.3))])
    gpt_step(rec, clk, decode=(0.4, (4.2, 0.3)))            # 4
    gpt_step(rec, clk, decode=(7.0, (70.0, 7.0)))           # 5: past the window
    run = run_of([(1.0, False), (2.0, True), (3.0, False), (4.0, False),
                  (11.0, False)])
    assert cr.dispatch_ms_decode(run) == pytest.approx(0.4)
    # no trace: nothing to subtract
    assert cr.exposed_read_ms_decode(run) is None
    run["trace"] = trace_of(jit_serve_decode=[3.5, 3.7, 3.9],
                            jit_serve_prefill_128=[15.0])
    # reads 4.2, 4.8, 4.5 -> median 4.5, less the device's 3.7
    assert cr.exposed_read_ms_decode(run) == pytest.approx(0.8)
    assert cr.dispatch_ms_prefill(run) == pytest.approx(0.7)
    # no call of the batch's program
    assert cr.exposed_read_ms_prefill(run) is None


def test_prefill_readers_keep_the_batch_program(ring):
    rec, clk = ring
    big = cr.BATCH_PROGRAM
    gpt_step(rec, clk, prefills=[(big, 0.6, (30.0, 0.2)),
                                 (big, 0.8, (32.0, 0.2)),
                                 ("prefill_512", 2.0, (99.0, 0.2))],
             decode=(0.3, (8.0, 0.1)))
    gpt_step(rec, clk, prefills=[(big, 0.4, (28.0, 0.4))])
    run = run_of([(1.0, False), (2.0, False)])
    run["trace"] = trace_of(jit_serve_prefill_1024=[27.0, 28.0, 29.0],
                            jit_serve_prefill_512=[90.0])
    # dispatch over every prefill: 0.6 0.8 2.0 0.4 -> 0.7
    assert cr.dispatch_ms_prefill(run) == pytest.approx(0.7)
    # the 1,024-bucket calls' reads 30.2 32.2 28.4 -> 30.2, less 28.0
    assert cr.exposed_read_ms_prefill(run) == pytest.approx(2.2)


def test_dispatch_per_step_sums_lazy_and_decode_dispatches(ring):
    rec, clk = ring
    lazy_step(rec, clk, [0.2, 0.3], 0.5)       # 1.0
    lazy_step(rec, clk, [], 0.6)               # 0.6
    lazy_step(rec, clk, [0.4, 0.4, 0.4], 0.5)  # 1.7
    run = run_of([(1.0, False), (2.0, False), (3.0, False)])
    assert cr.dispatch_ms_per_step(run) == pytest.approx(1.0)
    # a lazy prefill has no dispatch under its engine/prefill
    assert cr.dispatch_ms_prefill(run) is None


def test_nothing_to_read_returns_none(ring, monkeypatch):
    rec, clk = ring
    gpt_step(rec, clk, decode=(0.3, (4.0, 0.2)), phases=False,
             prefills=[(cr.BATCH_PROGRAM, 0.5, (30.0, 0.2))])
    run = run_of([(1.0, False)])
    run["trace"] = trace_of(jit_serve_decode=[3.7],
                            jit_serve_prefill_1024=[28.0])
    for name in NEW:
        assert read(name)(run) is None
    # a program that keeps no ring at all
    from apex_tpu.observability import spans

    monkeypatch.delattr(spans, "process_recorder")
    for name in NEW:
        assert read(name)(run) is None


def read(name):
    from benchmark.run import read_metric

    return lambda run: read_metric(name, run)


def test_manifest_entries():
    assert len(NEW) == 6
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-6:] == list(NEW)
    for m in NEW.values():
        assert (m["source"], m["layer"], m["unit"], m["better"]) == (
            "program_span", "serving host loop", "ms", "lower")
        assert len(m["workloads"]) == 1
        assert m["moves"] == ("serve.itl_p95_ms" if m["name"].endswith(".chat")
                              else "serve.tokens_per_s")


#: per cell, the names a CPU rehearsal prints: its trace has no programs
#: line, so the exposed reads (which subtract a program's device time)
#: are read on the chip only
REHEARSED = {
    "gpt2-large.chat-steady": {"engine.dispatch_ms.chat"},
    "gpt2-large.long-prompt-batch": {"engine.dispatch_ms.batch"},
    "ling-3.0-flash-vl.long-answer-batch": {
        "engine.dispatch_ms_per_step.answers"},
    "falcon-h1-34b.short-chat-saturated": {
        "engine.dispatch_ms_per_step.h1chat"},
}


@pytest.mark.parametrize("cell", sorted(REHEARSED))
def test_traced_rehearsal_prints_the_new_names(cell):
    line, _ = rehearse(cell, "--trace", "1")
    got = {n for n in line["rehearsal"] if n in NEW}
    assert got == REHEARSED[cell]
    assert all(line["rehearsal"][n] > 0 for n in got)
    assert {n for n, m in NEW.items() if cell in m["workloads"]} >= got
