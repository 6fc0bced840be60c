"""Arithmetic the ``sched.*`` per-layer readers share: the host's share of
a serving step, from the phases the program itself records.

The serving host loop writes one span per named phase into a bounded ring
the process always keeps (``apex_tpu.observability.spans.process_recorder``;
the vocabulary is in docs/serving.md, "Host phases"): ``serve/step`` around
every ``sched.step()``, under it ``serve/admit`` (with ``engine/stage`` and
``engine/prefill``), ``serve/chunks``, ``serve/batch``, ``engine/stage``,
``engine/decode``, ``serve/retire``, ``serve/publish``.  A span carries an
``id`` and its ``parent``.

Nothing is handed to a reader, so it lines the ring up with the run by
count: the driver appends one tuple to ``facts["steps"]`` per
``sched.step()`` and steps nowhere after its loop, so the ring's last
``len(steps)`` ``serve/step`` spans are the drive's steps, one to one.  The
steps read are those inside the window with the profiler off (its host
cost is not the program's).  A program from before the phases has no ring:
the readers then find nothing to read and return None.  A ring that holds
fewer ``serve/step`` spans than the run has steps has dropped some, and the
reader raises with both counts.
"""

from __future__ import annotations

import statistics

STEP = "serve/step"
#: the phases in which the host waits on the device (dispatch of the
#: compiled call through the first host read)
DEVICE_WAIT = ("engine/prefill", "engine/decode")


def _ms(e):
    return 1e3 * (e["t1"] - e["t0"])


def ring_spans():
    """The process ring's phases, oldest first; None where the program
    keeps no such ring."""
    try:
        from apex_tpu.observability.spans import process_recorder
    except ImportError:
        return None
    return [e for e in process_recorder().snapshot() if "id" in e]


class Steps:
    """The drive's steps that the metrics read, each with the phases
    under it."""

    def __init__(self, run):
        spans = ring_spans()
        drive = run["facts"]["steps"]
        self.steps = []
        self._children = {}
        if spans is None or not drive:
            return
        top = [e for e in spans if e["name"] == STEP]
        if len(top) < len(drive):
            raise RuntimeError(
                f"the process ring holds {len(top)} {STEP} spans and the "
                f"run took {len(drive)} steps: the ring dropped the rest"
            )
        for e in spans:
            self._children.setdefault(e["parent"], []).append(e)
        window = run["facts"]["window_s"]
        self.steps = [
            e for e, d in zip(top[len(top) - len(drive):], drive)
            if d[0] <= window and not d[5]
        ]

    def children(self, span, name=None):
        return [c for c in self._children.get(span["id"], ())
                if name is None or c["name"] == name]

    def under(self, span, names):
        """Every span below ``span``, at any depth, named in ``names``."""
        out, todo = [], list(self._children.get(span["id"], ()))
        while todo:
            c = todo.pop()
            if c["name"] in names:
                out.append(c)
            todo += self._children.get(c["id"], ())
        return out

    def host_ms(self, span):
        """A span's duration less the device waits under it."""
        return _ms(span) - sum(_ms(c) for c in self.under(span, DEVICE_WAIT))

    def decoded(self):
        return [s for s in self.steps if self.children(s, "engine/decode")]


def _median(xs):
    xs = list(xs)
    return statistics.median(xs) if xs else None


def host_ms_per_step(run):
    """Median host milliseconds of a step: ``serve/step`` less the
    ``engine/prefill`` and ``engine/decode`` spans under it."""
    st = Steps(run)
    return _median(st.host_ms(s) for s in st.steps)


def host_ms_per_decode_step(run):
    """The same over the steps that ran a decode."""
    st = Steps(run)
    return _median(st.host_ms(s) for s in st.decoded())


def _phase_ms_per_decode_step(run, names):
    st = Steps(run)
    return _median(
        sum(_ms(c) for c in st.children(s) if c["name"] in names)
        for s in st.decoded()
    )


def stage_ms_per_step(run):
    """``serve/batch`` plus the decode's own ``engine/stage`` (the one
    directly under the step; a prefill's lies under its admission)."""
    return _phase_ms_per_decode_step(run, ("serve/batch", "engine/stage"))


def retire_ms_per_step(run):
    return _phase_ms_per_decode_step(run, ("serve/retire",))


def publish_ms_per_step(run):
    return _phase_ms_per_decode_step(run, ("serve/publish",))


def admit_host_ms(run):
    """Median over the steps' ``serve/admit`` spans: duration less the
    ``engine/prefill`` under it."""
    st = Steps(run)
    return _median(
        st.host_ms(a) for s in st.steps for a in st.children(s, "serve/admit")
    )

