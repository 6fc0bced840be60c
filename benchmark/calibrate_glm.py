"""Readings the limits of the GLM cell's `correct` are set from, at the
cell's own size (``benchmark/calibrate.py``'s serving half, for this
driver).

    python3 benchmark/calibrate_glm.py --workload <cell> --seeds 1,2,3
        [--controls 1] [--seconds 8] [--plant <fault>] [--check <n>]
        [--rehearse 1]

One process.  For every seed it drives the cell's timed path over a short
window (every request offered is drained and counts) and prints the *lower*
readings (``glm_serve.readings``: the logits at a checked prompt's end, the
served tokens that are not the reference's best, each request's own
beside them).  For the first ``--controls`` seeds it also prints them on
the same prompts and served tokens for the reference itself computed in
bf16 (the configuration's precision: has to pass) and in fp8 (the precision
below: has to fail).  ``--plant`` breaks the timed path as the driver's
faults do (``glm_serve.PLANTS``): the readings then have to fail.  PERF.md
records what was read and the limits set from it.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run as harness  # noqa: E402


def serve_cell(ctx, controls: bool):
    from benchmark.drivers import glm_serve as drv
    from benchmark.drivers import gpt_serve as base

    prog = drv.build(ctx)
    with drv._as_gpt_serve(ctx) as seen:
        prog["step_log"].clear()
        res = base.drive(seen, prog)
    faults, leaked = base.counts(prog)
    slots = prog["sched"].slots_in_use()
    sample = drv.checked(ctx, prog, res)
    weights = drv.to_reference(prog["params"], ctx.config)
    prog.clear()
    gc.collect()
    numbers, detail = drv.readings(ctx.config, weights, sample)
    out = {"program": dict(numbers, requests=detail),
           "checked_prompt_tokens": [len(p) for p, _, _ in sample],
           "fault_counters": faults, "pages_leaked": leaked,
           "slots_leaked": slots, "ended": len(res["ended"]),
           "unfinished": len(res["unfinished"])}
    if controls:
        for name in ("fp8", "bf16"):
            numbers, detail = drv.readings(ctx.config, weights, sample,
                                           control=name)
            out[("reference_" if name == "bf16" else "control_") + name] = \
                dict(numbers, requests=detail)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--plant", default=None)
    ap.add_argument("--check", type=int, default=None,
                    help="requests checked (default: the traffic's)")
    ap.add_argument("--rehearse", type=int, default=0)
    a = ap.parse_args(argv)
    for i, seed in enumerate(int(s) for s in a.seeds.split(",")):
        _, ctx, _ = harness.open_cell(
            a.workload, first=i == 0, seed=seed, seconds=a.seconds,
            rehearse=bool(a.rehearse), planted=a.plant,
        )
        if a.check:
            ctx.traffic["check_requests"] = a.check
        out = serve_cell(ctx, i < a.controls)
        out.update(seed=seed, workload=a.workload, planted=a.plant)
        print(json.dumps(out), flush=True)
        gc.collect()


if __name__ == "__main__":
    main()
