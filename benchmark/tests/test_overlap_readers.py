"""The ``sched.decode_overlap_share.*`` readers on a hand-filled process ring:
the share of the window's decode launches that went out with the call before
them unread, read-only decode phases left out, nothing to read on a program
that records no ``overlapped`` flag; their manifest entries; and the chat
cell's traced rehearsal prints its share."""

import pytest

from benchmark.run import read_metric
from benchmark.tests.test_cells import BENCH, rehearse
from benchmark.tests.test_span_readers import Clock, run_of

NAMES = ("sched.decode_overlap_share.chat", "sched.decode_overlap_share.batch")


@pytest.fixture
def ring(monkeypatch):
    from apex_tpu.observability import spans

    clk = Clock()
    rec = spans.SpanRecorder(256, clock=clk)
    monkeypatch.setattr(spans, "_PROCESS", rec)
    return rec, clk


def step(rec, clk, launches, *, read_alone=False):
    """One serve/step: a read-only engine/decode (the call in flight read on
    its own) if asked, then one engine/decode per launch, each carrying its
    ``overlapped`` flag (None: a program from before the flag)."""
    with rec.phase("serve/step"):
        if read_alone:
            with rec.phase("engine/decode", iter=0):
                clk.t += 1e-3
        for flag in launches:
            args = {} if flag is None else {"overlapped": flag}
            with rec.phase("engine/decode", iter=1, batch=1, **args):
                with rec.phase("engine/dispatch", program="decode"):
                    clk.t += 1e-3


@pytest.mark.parametrize("name", NAMES)
def test_share_of_launches_with_the_call_before_unread(ring, name):
    rec, clk = ring
    step(rec, clk, [True])                       # warm-up: before the drive
    step(rec, clk, [False])                      # 1: the chain starts
    step(rec, clk, [True], read_alone=True)      # 2
    step(rec, clk, [True])                       # 3: traced: left out
    step(rec, clk, [True, False])                # 4
    step(rec, clk, [False])                      # 5: past the window
    run = run_of([(1.0, False), (2.0, False), (3.0, True), (4.0, False),
                  (11.0, False)])
    assert read_metric(name, run) == pytest.approx(100.0 * 2 / 4)


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_without_the_flag(ring, name):
    rec, clk = ring
    step(rec, clk, [None], read_alone=True)
    step(rec, clk, [None])
    assert read_metric(name, run_of([(1.0, False), (2.0, False)])) is None
    assert read_metric(name, run_of([])) is None


def test_manifest_entries():
    got = {m["name"]: m for m in BENCH["per_layer"] if m["name"] in NAMES}
    assert list(got) == list(NAMES)
    for name, cell, moves in zip(
            NAMES, ("gpt2-large.chat-steady", "gpt2-large.long-prompt-batch"),
            ("serve.itl_p95_ms", "serve.tokens_per_s")):
        m = got[name]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            "%", "higher", "program_span", "serving host loop")
        assert m["moves"] == moves and m["workloads"] == [cell]


def test_chat_rehearsal_prints_its_share():
    line, _ = rehearse("gpt2-large.chat-steady", "--trace", "1")
    assert line["correct"] is True
    assert 0 < line["rehearsal"][NAMES[0]] <= 100
