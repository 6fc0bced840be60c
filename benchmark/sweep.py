"""The knee sweep of an open-loop serving cell: one engine, the cell's mix
offered at each of a few fixed rates for a short window, one line a rate.

    python3 benchmark/sweep.py --workload gpt2-large.chat-steady --rates 4,8,12,16 --seconds 20

Run once, on the chip, when a cell is defined: the cell's own rate is four
fifths of the highest rate here that ends with no backlog (PERF.md has
the table).  The benchmark itself never searches for a rate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run as harness  # noqa: E402


def main(argv=None):
    from benchmark.drivers import gpt_serve as drv

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rehearse", type=int, default=0)
    a = ap.parse_args(argv)
    _, ctx, _ = harness.open_cell(
        a.workload, seed=a.seed, seconds=a.seconds, rehearse=bool(a.rehearse))
    traffic = ctx.traffic
    prog = drv.build(ctx)
    for rate in (float(r) for r in a.rates.split(",")):
        ctx.traffic = dict(traffic, rate_per_s=rate)
        res = drv.drive(ctx, prog)
        e2e, f, attempted, failed = drv.summarize(ctx, prog, res)
        print(json.dumps({
            "rate_per_s": rate, "offered": f["offered"], "attempted": attempted,
            "failed": failed, "shed": len(prog["sched"].shed),
            "completed_in_window": f["requests_in_window"],
            "in_flight_at_close": f["in_flight_at_close"],
            "drain_s": f["drain_s"],
            "ttft_p50_ms": f["ttft_p50_ms"], "ttft_p95_ms": e2e["serve.ttft_p95_ms"],
            "itl_p50_ms": f["itl_p50_ms"], "itl_p95_ms": e2e["serve.itl_p95_ms"],
            "tokens_per_s": e2e["serve.tokens_per_s"],
            "generator_late_p95_ms": f["generator_late_p95_ms"],
        }), flush=True)


if __name__ == "__main__":
    main()
