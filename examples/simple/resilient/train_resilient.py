"""Minimal resilient training loop — survives NaN bursts and preemption.

A tiny linear-regression job wrapped in the full resilience stack:
guarded amp steps (NaN/spike skip), step-numbered checkpoints with retry,
SIGTERM-safe shutdown, and auto-resume.  Run it, kill it (``kill -TERM``
or let chaos do it), run it again — it continues where it stopped::

    python train_resilient.py --steps 200 --dir /tmp/resilient_demo

    # with injected faults (deterministic; the x1 save fault heals on retry):
    APEX_TPU_CHAOS="grads:nan@7,8;checkpoint_save:raise:x1@5;preemption@42" \
        python train_resilient.py --steps 200 --dir /tmp/resilient_demo

Gradient accumulation rides the DDP comm layer (``docs/comm.md``):
``--accum K`` splits each optimizer step into K microbatches whose grads
accumulate LOCALLY (``DistributedDataParallel.no_sync`` semantics —
Apex's ``delay_allreduce``), paying ONE gradient sync on the boundary;
``--wire int8`` makes that boundary sync quantized.  The loss runs
through a ``shard_map`` over the dp mesh, so the same script spans
1..N devices (``XLA_FLAGS=--xla_force_host_platform_device_count=4`` to
try a 4-way mesh on CPU)::

    python train_resilient.py --steps 100 --accum 4 --wire int8

``--metrics-out out.jsonl`` turns on the full observability pipe
(``docs/observability.md``): device metrics (loss, grad norm, scaler
scale, skip counts) accumulate INSIDE the jitted update and are fetched
on a cadence, a ``StepMeter`` adds wall-clock step time / tokens/s /
MFU, a ``GoodputAccountant`` rides the ``run_resilient`` observer
events, and everything lands in the bench-schema JSONL.  The final
``train/goodput`` line carries the exact skip/rollback/retry counts of
the run (``GoodputAccountant.snapshot()``), so a chaos drill is
checkable from the artifact alone.  ``APEX_TPU_TRACE_STEPS="N+K"`` arms
a profile window of steps N..N+K-1 with no further flags.

Crash forensics and health monitoring are ON BY DEFAULT:

- a ``FlightRecorder`` (``--flight N[:DIR]``, default ring of 64 into
  ``<--dir>/flight/``; ``--flight 0`` disables) keeps the last steps'
  guard/scaler/loss state and dumps ``flight_<ts>.json`` atomically
  when the run dies — skip-budget exhaustion, an unhandled exception,
  SIGTERM.  Render it with ``tools/flight_view.py``.
- a health ``Watchdog`` (``--no-health`` disables) evaluates the
  default rule set (goodput/MFU floors, loss spikes, NaN-storm rate,
  stale fetches, hung steps — plus per-host stragglers when a
  multi-device mesh feeds the fleet aggregator) and prints each
  ``HealthEvent``, mirrors it into the flight recorder and — with
  ``--metrics-out`` — the JSONL.
"""

import argparse
import os
import sys

sys.path.insert(
    0, os.path.abspath(os.path.join(os.path.dirname(__file__), "../../.."))
)

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import amp
from apex_tpu import observability as obs
from apex_tpu.optimizers import fused_adam
from apex_tpu.resilience import (
    GradGuard,
    ObserverFanout,
    chaos,
    run_resilient,
)
from apex_tpu.train import TrainConfig, Trainer


def build_training(accum=1, wire="f32", fetch_every=8):
    """Construct the example's full training program — mesh, toy data,
    guarded/metered state, and the two jitted step functions — on top
    of the composable trainer (``apex_tpu.train``, docs/training.md):
    the example proves the COMPOSED path end to end, not a bespoke one.
    ``Trainer.build_guarded`` owns the mesh, the DDP comm engine
    (``wire``/accum boundary sync), the guarded-amp update, the
    in-step metric fold, and the declared sharding/collective plans.

    Shared by :func:`main` and ``tools/graph_lint.py --target
    resilient``: the CI lint gate audits EXACTLY the compiled programs
    this example dispatches, not a lookalike.  Returns a dict with the
    jitted ``compute_grads(params, scaler_state, batch)`` and
    ``apply_update(scaled, state, loss)``, plus the pieces main() (or a
    linter) needs to drive or trace them: ``state``, ``batch_fn``,
    ``registry``, ``mesh``/``dp``/``rows``, and the raw
    ``tx``/``scaler``/``guard``/``ddp``/``x_all``/``y_all``.
    """
    dp = len(jax.devices())  # all devices -> the trainer's dp axis
    micro = 64  # rows per microbatch, per replica
    rows = micro * dp * accum  # rows consumed per optimizer step
    if rows > 4096:  # the toy dataset below
        raise SystemExit(
            f"--accum {accum} x dp={dp} needs {rows} rows per step "
            "but the toy dataset has 4096; lower --accum or the mesh size"
        )

    rs = np.random.RandomState(0)
    x_all = jnp.asarray(rs.randn(4096, 8), jnp.float32)
    w_true = jnp.asarray(rs.randn(8, 4), jnp.float32)
    y_all = x_all @ w_true

    params = {"w": jnp.zeros((8, 4), jnp.float32)}
    tx = fused_adam(1e-2)
    scaler = amp.DynamicLossScaler(init_scale=2.0**10)
    guard = GradGuard(spike_factor=20.0, warmup_steps=5)

    # -- observability ------------------------------------------------------
    # The registry (and its slot in the checkpointed state) exists
    # UNCONDITIONALLY so the checkpoint tree structure never depends on
    # the --metrics-out flag: a run interrupted without telemetry can
    # resume with it (and vice versa) on the same --dir.  Only the
    # reporting side — meter, goodput ledger, sinks — is gated.
    registry = obs.MetricRegistry(fetch_every=fetch_every)
    registry.gauge("train/loss", unit="mse")
    registry.counter("guard/skipped")
    for name in ("guard/found_inf", "guard/spike", "guard/grad_norm",
                 "guard/norm_ema", "guard/consecutive_skips",
                 "guard/total_skips", "guard/budget_left",
                 "amp/loss_scale", "amp/growth_tracker",
                 "amp/hysteresis"):
        registry.gauge(name)

    # -- the composed trainer ----------------------------------------------
    # A 1D dp mesh, replicated params (the DDP contract), the comm
    # engine's wire format on the accumulation-boundary sync.  The
    # guarded two-phase shape keeps the gradient tree on the host
    # between the two programs — the chaos `grads` site needs it there.
    trainer = Trainer(TrainConfig(
        mesh={"dp": dp},
        rules=[(r".*", jax.sharding.PartitionSpec())],
        wire=wire,
        update_sharding="replicate",  # the guard wants the full tree
    ))
    g = trainer.build_guarded(
        lambda p, b: jnp.mean((b[0] @ p["w"] - b[1]) ** 2),
        params,
        tx=tx, scaler=scaler, guard=guard,
        registry=registry, accum=accum,
    )

    def batch_fn(step):
        span = x_all.shape[0] - rows  # 0 when one step eats the dataset
        lo = (step * rows) % span if span > 0 else 0
        shape = (accum, micro * dp)
        return (
            x_all[lo: lo + rows].reshape(*shape, 8),
            y_all[lo: lo + rows].reshape(*shape, 4),
        )

    return {
        "mesh": g.mesh, "dp": dp, "micro": micro, "rows": rows,
        "x_all": x_all, "y_all": y_all,
        "state": g.state, "registry": registry,
        "tx": tx, "scaler": scaler, "guard": guard, "ddp": g.ddp,
        "trainer": trainer,
        "compute_grads": g.compute_grads, "apply_update": g.apply_update,
        "batch_fn": batch_fn,
        "shard_rules": g.shard_rules,
        "expect_sharding": g.expect_sharding,
        "expect_plan": g.expect_plan,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--dir", default="/tmp/apex_tpu_resilient_demo")
    ap.add_argument("--save-every", type=int, default=10)
    ap.add_argument("--accum", type=int, default=1,
                    help="microbatches accumulated locally per optimizer "
                    "step (one gradient sync on the boundary)")
    ap.add_argument("--wire", default="f32",
                    choices=["f32", "bf16", "int8"],
                    help="wire format of the boundary gradient sync "
                    "(docs/comm.md; tiny leaves stay on the exact psum)")
    ap.add_argument("--ckpt-engine", default="async",
                    choices=["async", "sync"],
                    help="checkpoint save engine (docs/goodput.md): "
                    "async = zero-stall host snapshot + background "
                    "write (default); sync = orbax manager inline")
    ap.add_argument("--metrics-out", default=None,
                    help="JSONL telemetry path — turns on the full "
                    "observability pipe (docs/observability.md)")
    ap.add_argument("--fetch-every", type=int, default=8,
                    help="device->host metric fetch cadence in steps")
    ap.add_argument("--report-every", type=int, default=10,
                    help="steps between JSONL telemetry reports")
    ap.add_argument("--flight", default=None, metavar="N[:DIR]",
                    help="flight-recorder ring size (+ optional dump "
                    "dir; default 64 into <--dir>/flight; 0 disables; "
                    "APEX_TPU_FLIGHT overrides)")
    ap.add_argument("--no-health", action="store_true",
                    help="disable the health watchdog (on by default: "
                    "goodput/MFU floors, loss spike, NaN rate, stale "
                    "fetch, hung step, straggler)")
    ap.add_argument("--ops-port", type=int, default=None, metavar="PORT",
                    help="serve live OpenMetrics at /metrics while the "
                    "run trains (0 = OS-assigned; APEX_TPU_OPS_PORT is "
                    "the default; docs/observability.md 'Live ops plane')")
    args = ap.parse_args()
    if args.ops_port is None:
        from apex_tpu.observability.ometrics import ops_port_from_env

        args.ops_port = ops_port_from_env()

    t = build_training(
        accum=args.accum, wire=args.wire, fetch_every=args.fetch_every
    )
    dp, rows = t["dp"], t["rows"]
    x_all, y_all = t["x_all"], t["y_all"]
    state, registry = t["state"], t["registry"]
    compute_grads, apply_update = t["compute_grads"], t["apply_update"]
    batch_fn = t["batch_fn"]
    print(f"devices: dp={dp}, accum={args.accum}, wire={args.wire}")

    # meter + goodput ledger run unconditionally (cheap, host-side) so
    # the flight recorder and watchdog see them with or without a JSONL
    # reporter; only the report fan-out is gated on --metrics-out
    n_params = sum(
        p.size for p in jax.tree_util.tree_leaves(state["params"])
    )
    meter = obs.StepMeter(
        tokens_per_step=rows,
        flops_per_step=obs.transformer_train_flops(n_params, rows),
        devices=t["mesh"].devices.flat,
    )
    goodput = obs.GoodputAccountant()
    reporter = None
    if args.metrics_out:
        reporter = obs.Reporter(
            [obs.JSONLSink(args.metrics_out)],
            registry=registry, meter=meter, goodput=goodput,
        )
    tracer = obs.TraceScheduler()  # armed by APEX_TPU_TRACE_STEPS, else no-op

    # live ops plane: scrape the registry + board while the run trains
    # (the memstats collect hook publishes HBM watermarks per scrape —
    # real memory_stats() on TPU, silently absent on the CPU backend)
    ops = None
    if args.ops_port is not None:
        mem_provider = obs.memstats.default_provider()
        monitor = (
            obs.MemStatsMonitor(mem_provider)
            if mem_provider is not None else None
        )
        ops = obs.OpsServer(
            registries=[registry],
            collect=monitor.sample if monitor is not None else None,
            port=args.ops_port,
        ).start()
        print(f"ops: live OpenMetrics at {ops.url}")

    # flight recorder: env > --flight > default ring of 64.  Resolved
    # to ONE spec before from_env so APEX_TPU_FLIGHT=0 genuinely
    # disables (an `or`-chain over recorders would fall through a
    # disabled env spec into the default and arm anyway).
    from apex_tpu.observability.flight import ENV_FLIGHT

    spec = os.environ.get(ENV_FLIGHT) or args.flight or "64"
    flight = obs.FlightRecorder.from_env(
        spec,
        directory=os.path.join(args.dir, "flight"),
        run={"example": "train_resilient", "steps": args.steps,
             "accum": args.accum, "wire": args.wire, "dp": dp},
    )
    if flight is not None:
        flight.attach(registry=registry, meter=meter, goodput=goodput)

    # fleet aggregation feeds the straggler rule on a multi-device mesh
    # (one jitted all-gather on the fetch cadence, docs/observability.md)
    fleet = None
    if dp > 1:
        fleet = obs.FleetAggregator(
            ("train/step_time_ms", "train/mfu", "train/loss"),
            mesh=t["mesh"], every=args.fetch_every,
        )

    watchdog = None
    if not args.no_health:
        watchdog = obs.Watchdog(
            registry=registry, meter=meter, goodput=goodput, fleet=fleet,
            reporter=reporter, flight=flight,
            on_unhealthy=lambda ev: print(
                f"  [health/{ev.severity}] {ev.rule}: {ev.message}"
            ),
            check_every=max(1, args.fetch_every // 2),
        )

    def step_fn(state, batch):
        step = int(state["guard"].step)
        tracer.on_step(step)
        loss, scaled = compute_grads(state["params"], state["scaler"], batch)
        # chaos GRADS site: poisons the tree on scheduled steps, no-op else
        scaled = chaos.corrupt_tree(scaled, step)
        new_state, verdict = apply_update(scaled, state, loss)
        registry.observe(step, new_state["metrics"])
        meter.tick()
        if fleet is not None:
            fleet.observe(step, {**registry.values(), **meter.summary()})
        if reporter is not None and step % args.report_every == 0:
            reporter.report(step)
        if bool(verdict.skipped):
            print(f"  step skipped (found_inf={float(verdict.found_inf)}, "
                  f"spike={bool(verdict.spike)})")
        return new_state, {"skipped": verdict.skipped, "loss": loss}

    result = None
    try:
        result = run_resilient(
            step_fn,
            state,
            batch_fn,
            directory=args.dir,
            num_steps=args.steps,
            save_interval_steps=args.save_every,
            max_to_keep=3,
            rollback_after=5,
            observer=ObserverFanout([goodput, watchdog]),
            flight=flight,
            checkpoint=args.ckpt_engine,
        )
    finally:
        # even a raising run (e.g. max_rollbacks exhausted) must close
        # an armed trace window and land its final telemetry — those
        # are exactly the artifacts needed to debug the failure
        tracer.stop()
        # a captured window gets attributed on the way out: the
        # compute/collective/host-stall split lands on the board (the
        # watchdog fraction rules' source) and — with --metrics-out —
        # in the JSONL (docs/observability.md "Attribution & roofline")
        if tracer.log_dir and os.path.isdir(tracer.log_dir):
            try:
                from apex_tpu.observability import attribution as attr

                meas = attr.attribute_trace_dir(tracer.log_dir)
                fr = attr.publish_attribution(meas, reporter=reporter)
                print(
                    "trace attribution (steps %s..%s): compute=%.3f "
                    "collective=%.3f host_stall=%.3f "
                    "(tools/step_profile.py adds the roofline)"
                    % (tracer.start, tracer.end, fr["compute"],
                       fr["collective"], fr["host_stall"])
                )
            except Exception as e:  # the postmortem must not eat the run
                print(f"trace attribution failed: {e}", file=sys.stderr)
        if ops is not None:
            ops.stop()
        if reporter is not None:
            registry.fetch()  # drain the async buffers for the report
            final_step = (
                max(result.last_step, 0) if result is not None
                else meter.steps
            )
            reporter.report(final_step)
            # The consolidated goodput line: value + the EXACT event
            # counts of this invocation (they match RunResult by
            # construction — the accountant saw every on_step /
            # on_rollback the runner counted).
            snap = goodput.snapshot()
            reporter.sinks[0].write(obs.bench_record(
                "train/goodput", snap["goodput"],
                "fraction (productive/executed)", None,
                step=final_step, accepted=snap["accepted"],
                skipped=snap["skipped"], discarded=snap["discarded"],
                rollbacks=snap["rollbacks"], retries=snap["retries"],
                resumes=snap["resumes"], preempted=snap["preempted"],
            ))
            reporter.close()
    print(
        f"done: last_step={result.last_step} resumed_from={result.resumed_from} "
        f"steps_run={result.steps_run} skipped={result.skipped_steps} "
        f"rollbacks={result.rollbacks} preempted={result.preempted}"
    )
    saves = obs.board.get("goodput/ckpt/saves")
    if saves:
        # the async engine's ledger (docs/goodput.md): the only step-path
        # cost is the snapshot — stall_frac is the <1% acceptance number
        print(
            "ckpt: engine=%s saves=%d writes=%d stall_frac=%.5f "
            "last_write=%.1fms"
            % (args.ckpt_engine, saves,
               obs.board.get("goodput/ckpt/writes", 0),
               obs.board.get("goodput/ckpt/stall_frac", 0.0),
               obs.board.get("goodput/ckpt/last_write_ms", 0.0))
        )
    final_loss = float(
        jnp.mean((x_all @ result.state["params"]["w"] - y_all) ** 2)
    )
    print(f"final loss: {final_loss:.6f}")


if __name__ == "__main__":
    main()
