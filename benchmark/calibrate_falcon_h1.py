"""Readings the limit of `served_token_gap` is set from, for a Falcon-H1 cell at
its own size (``benchmark/calibrate.py``'s serving half, for this driver).

    python3 benchmark/calibrate_falcon_h1.py --workload <cell> --seeds 1,2,3
        [--controls 3] [--seconds 8] [--rehearse 1]

One process.  For every seed it drives the cell's timed path over a short
window (every request offered is drained and counts) and prints the *lower*
reading: the widest gap by which a served token's logit lies below the
reference's best.  For the first ``--controls`` seeds it also prints, on the
same prompts and served tokens, the gap of the token that the reference
itself puts first when computed in bf16 (the configuration's precision:
has to pass) and in fp8 (the precision below: has to fail), and with f32
products but the state-space state rounded to bf16 after every token
(``control_bf16_state``: why the cache holds that state in f32).  PERF.md
records what was read and the limit set from it.
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
    from benchmark.drivers import gpt_serve as base
    from benchmark.drivers import falcon_h1_serve as drv

    prog = drv.build(ctx)
    with drv._as_gpt_serve(ctx) as seen:
        prog["step_log"].clear()
        res = base.drive(seen, prog)
    faults, leaked = base.counts(prog)
    slots = prog["sched"].slots_in_use()
    sample = [(list(lv.req.prompt), list(lv.req.tokens)) for lv in
              base.sample_served(ctx, res["ended"],
                                 ctx.traffic["check_requests"])]
    weights = drv.to_reference(prog["params"], ctx.config)
    prog.clear()
    gc.collect()
    gap, scale, n = drv.served_token_gaps(ctx.config, weights, sample)
    out = {"program": {"served_token_gap": gap}, "ref_logit_scale": scale,
           "checked_tokens": n, "checked_requests": len(sample),
           "fault_counters": faults, "pages_leaked": leaked,
           "slots_leaked": slots, "ended": len(res["ended"]),
           "unfinished": len(res["unfinished"])}
    if controls:
        for name in ("fp8", "bf16", "bf16_state"):
            g, _, _ = drv.served_token_gaps(ctx.config, weights, sample,
                                            control=name)
            out[("reference_" if name == "bf16" else "control_") + name] = {
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
        out = serve_cell(ctx, i < a.controls)
        out.update(seed=seed, workload=a.workload)
        print(json.dumps(out), flush=True)
        gc.collect()


if __name__ == "__main__":
    main()
