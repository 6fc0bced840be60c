"""Readings the limits of `correct` are set from, at a cell's own size.

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3 [--controls 3]
        [--seconds 8] [--rehearse 1]

One process.  For every seed it drives the cell's timed path (a short
window) and prints the numbers `correct` compares: the *lower* readings.
For the first ``--controls`` seeds it also prints the *upper* readings:
the control (the reference itself in the precision below the
configuration's, fp8, put in the program's place) and, for a training
cell, the faults planted in the reference (half of the batch left out; the
update's direction taken without LAMB's second moment).  bf16 readings of
the reference are printed for information.  A training cell's every
reading is also put through the harness's own `correct` at the limits the
traffic file holds (``"correct"`` in the line).  PERF.md records what was
read and the limits set from it; the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run as harness  # noqa: E402


def train_cell(ctx, controls: bool):
    from benchmark.drivers import bert_recipe as drv

    steer = drv.drive(ctx)
    ref = drv.reference(ctx, steer)
    numbers, look = drv.compare(steer.snapshot, ref)
    out = {"program": numbers, "look": look,
           "losses": steer.snapshot["losses"], "ref_losses": ref["losses"]}
    # each reading goes through the harness's own `correct`, at the limits
    # the traffic file holds now
    verdict = {"program": harness.judge(drv.checks_of(numbers, ctx.traffic), 0)}
    if controls:
        for name, kw in (("control_fp8", {"prec": "fp8"}),
                         ("reference_bf16", {"prec": "bf16"}),
                         ("fault_half_batch", {"fault": "half_batch"}),
                         ("fault_lamb_no_v", {"fault": "lamb_no_v"})):
            other = drv.reference(ctx, steer, **kw)
            out[name], out[name + "_look"] = drv.compare(other, ref)
            verdict[name] = harness.judge(
                drv.checks_of(out[name], ctx.traffic), 0)
    out["correct"] = verdict
    return out


def serve_cell(ctx, controls: bool):
    from benchmark.drivers import gpt_serve as drv

    prog = drv.build(ctx)
    res = drv.drive(ctx, prog)
    faults, leaked = drv.counts(prog)
    sample = [(list(lv.req.prompt), list(lv.req.tokens)) for lv in
              drv.sample_served(ctx, res["ended"], ctx.traffic["check_requests"])]
    weights = drv.to_reference(prog["params"])
    prog.clear()
    gc.collect()
    gap, scale, n = drv.served_token_gaps(ctx.config, weights, sample)
    out = {"program": {"served_token_gap": gap}, "ref_logit_scale": scale,
           "checked_tokens": n, "checked_requests": len(sample),
           "fault_counters": faults, "pages_leaked": leaked,
           "ended": len(res["ended"]), "unfinished": len(res["unfinished"])}
    if controls:
        for name in ("fp8", "bf16"):
            g, _, _ = drv.served_token_gaps(ctx.config, weights, sample,
                                            control=name)
            out[("control_" if name == "fp8" else "reference_") + name] = {
                "served_token_gap": g}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rehearse", type=int, default=0)
    a = ap.parse_args(argv)
    for i, seed in enumerate(int(s) for s in a.seeds.split(",")):
        _, ctx, _ = harness.open_cell(
            a.workload, first=i == 0, seed=seed, seconds=a.seconds,
            rehearse=bool(a.rehearse),
        )
        fn = train_cell if ctx.traffic["kind"] == "train" else serve_cell
        out = fn(ctx, i < a.controls)
        out.update(seed=seed, workload=a.workload)
        print(json.dumps(out), flush=True)
        gc.collect()


if __name__ == "__main__":
    main()
