"""Arithmetic the ``engine.dispatch_ms*`` and ``engine.exposed_read_ms*``
readers share: a compiled serving call's host wait, split into its launch
and each of its device-to-host reads.

Under every ``engine/prefill`` / ``engine/decode`` phase the program
records ``engine/dispatch {program}`` (the compiled call: the launch and
the packed vector's one transfer) and one ``engine/read {program, what}``
a host read (``what`` = ``tokens`` or ``finite``); a lazy prefill's
dispatch lies under its ``engine/stage`` and its reads under the
``engine/prefill`` that resolves it (docs/serving.md, "Host phases").
The steps read are those of ``span_readers.Steps``: the drive's steps in
the window with the profiler off.  A program from before these phases
records none of them: the readers then return None.
"""

from __future__ import annotations

import statistics

from benchmark import readers
from benchmark.readers import decode_calls
from benchmark.span_readers import Steps
from benchmark.trace import reduce

DISPATCH = "engine/dispatch"
READ = "engine/read"
#: the batch cell's prompts (512-960 tokens) all but always take this bucket
BATCH_BUCKET = 1024
BATCH_PROGRAM = f"prefill_{BATCH_BUCKET}"


def _ms(e):
    return 1e3 * (e["t1"] - e["t0"])


def _median(xs):
    xs = list(xs)
    return statistics.median(xs) if xs else None


def _calls(st, name, program=None):
    """The window's ``name`` calls (``engine/decode`` or
    ``engine/prefill``), each with its dispatch and reads, read at once
    under it; ``program`` keeps the calls of one program."""
    out = []
    for s in st.steps:
        for c in st.under(s, (name,)):
            dispatch = st.children(c, DISPATCH)
            reads = st.children(c, READ)
            if not dispatch or (
                    program is not None
                    and dispatch[0]["args"]["program"] != program):
                continue
            out.append((dispatch[0], reads))
    return out


def dispatch_ms(run, name):
    """Median milliseconds of the ``engine/dispatch`` under a ``name``
    call."""
    return _median(_ms(d) for d, _ in _calls(Steps(run), name))


def exposed_read_ms(run, name, program, device_ms):
    """Median over a program's calls of the summed ``engine/read`` spans,
    less ``device_ms``, the program's median device time in the traced
    window: what the host waits beyond the program itself (the program's
    start after the dispatch returns, each read's trip back)."""
    reads = _median(
        sum(_ms(r) for r in rs)
        for _, rs in _calls(Steps(run), name, program) if rs
    )
    if reads is None or device_ms is None:
        return None
    return reads - device_ms


def _device_ms(calls):
    return 1e3 * statistics.median(d for _, d in calls) if calls else None


def dispatch_ms_decode(run):
    return dispatch_ms(run, "engine/decode")


def dispatch_ms_prefill(run):
    return dispatch_ms(run, "engine/prefill")


def exposed_read_ms_decode(run):
    """Less ``jit_serve_decode``'s median device time (the number
    ``engine.decode_step_ms.*`` reads)."""
    return exposed_read_ms(run, "engine/decode", "decode",
                           _device_ms(decode_calls(run)))


def exposed_read_ms_prefill(run):
    """The ``prefill_1024`` calls, less ``jit_serve_prefill_1024``'s
    median device time."""
    t = run.get("trace")
    module = f"{readers.PREFILL_PROGRAM}{BATCH_BUCKET}"
    return exposed_read_ms(
        run, "engine/prefill", BATCH_PROGRAM,
        _device_ms(reduce.module_calls(t, module) if t else []),
    )


def dispatch_ms_per_step(run):
    """Median over the window's steps of every ``engine/dispatch`` in the
    step summed (lazy prefills' under their ``engine/stage`` and the
    decode block's); None where no step recorded one."""
    st = Steps(run)
    per = [st.under(s, (DISPATCH,)) for s in st.steps]
    if not any(per):
        return None
    return _median(sum(_ms(d) for d in ds) for ds in per)
