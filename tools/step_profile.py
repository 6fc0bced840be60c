"""Step-time attribution + roofline for a real training step.

The "where do the milliseconds go" tool (docs/observability.md,
"Attribution & roofline"): builds a target's ACTUAL compiled step,
profiles a few steady-state executions, and decomposes the step two
ways that must agree —

- the compiled cost model (exact FLOPs/bytes per fused op, bucketed
  matmul / attention / norm-elementwise / collective / other through
  ``analysis/hlo.py``), and
- the measured profiler trace (exact time per op + the host-stall no
  kernel accounts for),

then prints compute/collective/host-stall fractions (summing to 1), a
per-bucket roofline (achieved FLOP/s vs the ``meter.py`` peak table,
arithmetic intensity, compute- vs bandwidth-bound verdict), the MFU
consistency pin against a live :class:`StepMeter` on the same run
(one denominator by design — the pin fails only if a second peak/FLOP
model sneaks in), and the trace-vs-host clock skew diagnostic.
The fractions land on the observability board, where the watchdog's
``CollectiveFractionRule`` / ``HostStallRule`` judge them — the tool
runs that judgment and prints any events.

Usage::

    python tools/step_profile.py --target resilient            # the CI target
    python tools/step_profile.py --target resilient --steps 12 \
        --json profile.json --metrics-out attr.jsonl
    python tools/step_profile.py --hlo bert_step.hlo           # cost model only
                                                               # (bench --hlo-out)

Exit code 0; the machine-readable artifact (``--json``) carries the
device, the fractions, bucket shares, roofline rows, and the MFU
agreement — what the verify_tier1.sh PERF pass and
``tests_tpu/test_step_profile.py`` assert on.

A measurement tool: it runs on whatever device JAX hands it.  On one
with no entry in the ``meter.py`` peak table (the CPU) the trace ->
attribution -> fractions path still runs, and everything that needs a
peak — the roofline, the MFU, the cost model's time shares — reads
"not measured": a roofline against an assumed chip is not a
measurement.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NOT_MEASURED = "not measured"


def _load_resilient_module():
    """Import the example script as a module (same loader as
    tools/graph_lint.py — the example lives outside the package tree
    on purpose)."""
    import importlib.util

    path = os.path.join(
        REPO, "examples", "simple", "resilient", "train_resilient.py"
    )
    spec = importlib.util.spec_from_file_location("train_resilient", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def profile_resilient(args):
    """Build the resilient example's real step, profile ``--steps``
    steady-state executions, and attribute them from both sources."""
    import jax

    from apex_tpu import observability as obs
    from apex_tpu.observability import attribution as A
    from apex_tpu.observability.meter import UnknownDeviceError

    mod = _load_resilient_module()
    t = mod.build_training(accum=args.accum, wire=args.wire)
    state, batch_fn = t["state"], t["batch_fn"]
    compute_grads, apply_update = t["compute_grads"], t["apply_update"]

    # -- source (a): the compiled cost model (AOT texts of BOTH
    # programs the step dispatches) --------------------------------------
    batch = batch_fn(0)
    grads_args = (state["params"], state["scaler"], batch)
    hlo_grads = compute_grads.lower(*grads_args).compile().as_text()
    loss, scaled = compute_grads(*grads_args)
    hlo_update = apply_update.lower(
        scaled, state, loss
    ).compile().as_text()
    try:
        cost = A.attribute_cost_model([hlo_grads, hlo_update])
        hlo_map, cost_weights = cost.bucket_map(), cost.bucket_fractions()
    except UnknownDeviceError as e:
        # the op -> bucket join needs no peak; the cost model's times do
        print(f"[step_profile] {e}: roofline and MFU {NOT_MEASURED}",
              file=sys.stderr)
        cost, cost_weights = None, None
        hlo_map = A.hlo_bucket_map([hlo_grads, hlo_update])
    if args.hlo_out:
        with open(args.hlo_out, "w") as f:
            f.write(hlo_grads)
            f.write("\n")
            f.write(hlo_update)

    # -- measured run: warmup outside the trace, then K metered steps ----
    # ONE peak/FLOP numerator (the cost model counts one device's
    # program; each chip executes it) but TWO independent clocks: the
    # meter times steps with host perf_counter ticks, the roofline
    # divides by the profiler window's span — MFU agreement is then a
    # real cross-check that the trace covers the same milliseconds the
    # wall clock paid, not an algebraic identity.
    meter = obs.StepMeter(
        tokens_per_step=t["rows"],
        flops_per_step=cost.total_flops if cost is not None else 0.0,
        peak_flops=cost.peak_flops if cost is not None else None,
    )
    state, _ = apply_update(scaled, state, loss)  # warmup apply too

    def one_step(state, step):
        loss, scaled = compute_grads(
            state["params"], state["scaler"], batch_fn(step)
        )
        new_state, verdict = apply_update(scaled, state, loss)
        float(loss)  # device->host sync: the honest step boundary
        return new_state

    trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="step_profile_")
    meter.tick()  # arm the clock
    with jax.profiler.trace(trace_dir):
        for step in range(args.steps):
            state = one_step(state, step)
            meter.tick()

    trace = A.load_trace_dir(trace_dir)
    measured = A.attribute_trace(
        trace, hlo_map=hlo_map, cost_weights=cost_weights,
    )
    # the trace's own per-step clock (median same-op period): the
    # independent measurement the MFU cross-check compares against the
    # meter's host perf_counter ticks
    trace_step_s = A.trace_step_period(trace, hlo_map=hlo_map)
    return cost, measured, meter, trace_dir, trace_step_s


def profile_hlo(args):
    """Cost-model-only attribution of an optimized-HLO dump (e.g.
    ``bench.py --hlo-out``): exact FLOPs/bytes and estimated shares,
    no measured time and no host view."""
    from apex_tpu.observability import attribution as A

    texts = []
    for path in args.hlo:
        with open(path) as f:
            texts.append(f.read())
    return A.attribute_cost_model(texts), None, None, None


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="step-time attribution + roofline "
        "(docs/observability.md)"
    )
    ap.add_argument("--target", choices=["resilient"], default=None)
    ap.add_argument("--hlo", nargs="+", metavar="FILE", default=None,
                    help="attribute optimized-HLO dump(s) instead of "
                    "profiling a target (cost model only)")
    ap.add_argument("--steps", type=int, default=8,
                    help="steady-state steps to profile (default 8)")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--wire", default="f32",
                    choices=["f32", "bf16", "int8"])
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profile here (default: a temp dir)")
    ap.add_argument("--hlo-out", metavar="FILE", default=None,
                    help="also write the compiled step's HLO text")
    ap.add_argument("--json", metavar="FILE", default=None,
                    help="write the full report as one JSON object")
    ap.add_argument("--metrics-out", metavar="FILE", default=None,
                    help="append the attribution fractions as "
                    "bench-schema JSONL (the observability sink)")
    args = ap.parse_args(argv)
    if bool(args.target) == bool(args.hlo):
        ap.error("exactly one of --target / --hlo is required")

    import jax

    from apex_tpu import observability as obs
    from apex_tpu.observability import attribution as A
    from apex_tpu.observability.meter import UnknownDeviceError

    d0 = jax.devices()[0]
    device = {"platform": d0.platform, "kind": d0.device_kind}
    if args.target:
        cost, measured, meter, trace_dir, trace_step_s = \
            profile_resilient(args)
    else:
        try:
            cost, measured, meter, trace_dir = profile_hlo(args)
        except UnknownDeviceError as e:
            sys.exit(f"step_profile: --hlo is the cost model alone, and {e}")
        trace_step_s = 0.0

    src = measured if measured is not None else cost
    fractions = src.fractions()
    frac_sum = sum(fractions.values())
    print(
        "step fractions (%s on %s): compute=%.3f collective=%.3f "
        "host_stall=%.3f  (sum=%.3f)"
        % (
            measured.source if measured is not None else "cost model",
            device["kind"],
            fractions["compute"], fractions["collective"],
            fractions["host_stall"], frac_sum,
        )
    )
    cost_fr = cost.fractions() if cost is not None else NOT_MEASURED
    if measured is not None and cost is not None:
        print(
            "cost-model cross-check: collective=%.3f (measured %.3f); "
            "host stall is invisible to the compiled program"
            % (cost_fr["collective"], fractions["collective"])
        )

    # roofline step time = the meter's: ONE denominator by design (the
    # satellite contract — StepMeter MFU, bench headlines, and the
    # roofline must never tell contradictory utilization stories), so
    # the MFU agreement below is a consistency PIN: it fails only if a
    # second denominator sneaks back in (a diverging peak table, a
    # different FLOP model), which is exactly the drift it guards.
    step_time = meter.step_time if meter is not None else cost.est_step_time
    if cost is not None:
        rows = A.roofline_report(
            cost, step_time_s=step_time, measured=measured
        )
        print()
        print(A.render_roofline(rows))
        roofline_mfu = rows[-1].pct_peak
        meter_mfu = meter.mfu if meter is not None else roofline_mfu
        agreement = (
            abs(roofline_mfu - meter_mfu) / meter_mfu
            if meter_mfu > 0 else 0.0
        )
        print(
            "\nMFU: roofline=%.4f meter=%.4f (delta %.2f%%; one "
            "denominator by design: observability.meter)"
            % (roofline_mfu, meter_mfu, 100 * agreement)
        )
        roofline = [r._asdict() for r in rows]
        mfu = {"roofline": roofline_mfu, "meter": meter_mfu,
               "agreement": agreement}
    else:
        print(f"\nroofline: {NOT_MEASURED}\nMFU: {NOT_MEASURED}")
        roofline = mfu = NOT_MEASURED
    # the genuinely independent comparison, as a diagnostic: the
    # trace's own per-step clock (median same-op period) vs the host
    # ticks.  Large skew is NOT an error — an async runtime batching
    # executions behind a host-bound loop produces exactly this, and
    # the host_stall fraction above already quantifies it.
    if trace_step_s > 0 and meter is not None and meter.step_time > 0:
        skew = abs(trace_step_s - meter.step_time) / meter.step_time
        print(
            "clock skew: trace step %.3f ms vs host step %.3f ms "
            "(%.1f%% — execution pacing vs dispatch pacing)"
            % (trace_step_s * 1e3, meter.step_time * 1e3, 100 * skew)
        )

    # publish -> board (the watchdog rules' source) + optional JSONL
    reporter = None
    if args.metrics_out:
        reporter = obs.Reporter([obs.JSONLSink(args.metrics_out)])
    A.publish_attribution(src, reporter=reporter, step=0)
    if reporter is not None:
        reporter.close()

    # judge the fractions the way a live run would
    wd = obs.Watchdog(
        rules=[obs.CollectiveFractionRule(), obs.HostStallRule()],
        attribution=src, check_every=1,
    )
    events = wd.check(0)
    for ev in events:
        print(f"[health/{ev.severity}] {ev.rule}: {ev.message}")
    if not events:
        print("watchdog: collective/host-stall fractions within floors")

    if args.json:
        payload = {
            "target": args.target or "hlo",
            "device": device,
            "source": measured.source if measured is not None else "cost-model",
            "fractions": fractions,
            "fraction_sum": frac_sum,
            "cost_fractions": cost_fr,
            "bucket_fractions": src.bucket_fractions(),
            "cost_buckets": (
                cost.buckets if cost is not None else NOT_MEASURED
            ),
            "step_time_ms": step_time * 1e3,
            "trace_step_ms": trace_step_s * 1e3,
            "roofline": roofline,
            "mfu": mfu,
            "health_events": [ev._asdict() for ev in events],
            "trace_dir": trace_dir,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"[step_profile] wrote {args.json}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
