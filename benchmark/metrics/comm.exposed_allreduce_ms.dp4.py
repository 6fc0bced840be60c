"""Device milliseconds per training step in which a chip waits on the
gradients' all-reduce with no computation beside it: the self time of the
all-reduce operations of the trace's operations line (``all-reduce``, and
the ``all-reduce-done`` that waits for an asynchronous one; not the
``all-reduce-start`` that only issues it), averaged over the chips, per
step of the training program (``jit_chunk_fn``).  The operations line of a
TPU core runs one operation at a time, so what an all-reduce holds there
is time no computation overlaps.  Zero when the program issues no
all-reduce (one chip); None without a trace."""

from benchmark.trace import reduce

WAITS = ("all-reduce", "all-reduce-done")


def read(run):
    t = run.get("trace")
    if not t:
        return None
    calls = reduce.module_calls(t, "jit_chunk_fn")
    steps = len(calls) * run["facts"]["chunk"]
    if not steps:
        return None
    s = sum(v for k, v in reduce.op_seconds(t).items()
            if k.split(" ")[1:2] and k.split(" ")[1] in WAITS)
    return 1e3 * s / steps
