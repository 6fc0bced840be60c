"""Device milliseconds per training step inside the Pallas LayerNorm
kernels (operations named ``%layer_norm_fwd*`` / ``%layer_norm_bwd*`` in
the trace).  A time, not a roofline share: on v5e XLA keeps some of these
kernels' operands in VMEM, so a share of the HBM roofline reads above
100 % (PERF.md, Open questions)."""

from benchmark.trace import reduce


def read(run):
    t = run.get("trace")
    if not t:
        return None
    s = sum(v for k, v in reduce.op_seconds(t).items()
            if k.startswith("%layer_norm_"))
    calls = reduce.module_calls(t, "jit_chunk_fn")
    steps = len(calls) * run["facts"]["chunk"]
    return 1e3 * s / steps if s and steps else None
