"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: loads the cell's configuration and traffic by the names in
``BENCHMARK.json``, hands them to the configuration's driver (set-up, warm-up,
the measured window, the comparison with the plain reference), reads the
per-layer metrics with one small reader each, and prints one JSON object as
the last line of standard output.  It fails, with no such line, off a TPU, on
a ``device_kind`` the peak table does not hold, with another number of chips
than the cell asks for, or when anything compiled inside the window.

``--rehearse 1`` is for the CPU at tiny size (the configuration's and the
traffic's ``rehearsal`` overrides): it runs the same control flow and prints
its readings under ``rehearsal``, never under a metric's name.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


class Context:
    """What a driver gets: the cell's data, the arguments, and the
    harness's counters."""

    def __init__(self, cell, config, traffic, *, seed, seconds, trace=False,
                 rehearse=False, planted=None):
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.rehearse = rehearse
        self.planted = planted
        self.cell = cell
        self.chips = cell["chips"]
        self.config = config
        self.traffic = traffic
        self.peaks = None
        self.t_process = T_PROCESS
        self._compiles = 0
        self._cache_misses = 0

    def watch_compiles(self):
        import jax

        def on_duration(name, *_a, **_k):
            if name == "/jax/core/compile/backend_compile_duration":
                self._compiles += 1

        def on_event(name, *_a, **_k):
            if name == "/jax/compilation_cache/cache_misses":
                self._cache_misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def compiles(self) -> int:
        """Programs handed to the backend compiler so far (a hit in the
        persistent cache counts: a new program is a new program)."""
        return self._compiles

    def memory_peak_bytes(self) -> int:
        import jax

        return max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in jax.devices()
        )


def _overlay(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _overlay(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def load_cell(name: str, rehearse: bool):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"BENCHMARK.json has no workload {name!r}")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    if rehearse:
        config = _overlay(config, config.get("rehearsal", {}))
        traffic = _overlay(traffic, traffic.get("rehearsal", {}))
    return bench, cell, config, traffic


def reports(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def device_entry(ctx):
    """The device as JAX reports it; refuses what the cell cannot be
    measured on."""
    import jax

    devs = jax.devices()
    d = devs[0]
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if ctx.rehearse:
        return {"platform": d.platform, "kind": d.device_kind,
                "count": len(devs)}, None
    if d.platform != "tpu":
        raise SystemExit(f"no accelerator: JAX platform is {d.platform!r}")
    if d.device_kind not in table:
        raise SystemExit(
            f"device_kind {d.device_kind!r} is not in benchmark/peaks.json"
        )
    if len(devs) != ctx.chips:
        raise SystemExit(
            f"cell {ctx.cell['name']} asks for {ctx.chips} chip(s), JAX "
            f"sees {len(devs)} (the programs take every visible device)"
        )
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}, table[d.device_kind]


def open_cell(workload, *, first=True, **kw):
    """The cell's data as a `Context`, the compile cache on and the device
    looked at (once a process: ``first``).  Returns (manifest,
    context, device entry)."""
    bench, cell, config, traffic = load_cell(workload, kw.get("rehearse", False))
    ctx = Context(cell, config, traffic, **kw)
    if first and not ctx.rehearse:
        # JAX's persistent compilation cache, before JAX is imported: where
        # the machine says (JAX_COMPILATION_CACHE_DIR), else at the fixed
        # path inside the checkout that the program's own entry points use.
        # The program reads the same variable and then sets no directory of
        # its own.
        os.environ.setdefault(
            "JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
        os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    device, ctx.peaks = device_entry(ctx)
    if first:
        ctx.watch_compiles()
    return bench, ctx, device


def read_metric(name: str, run):
    """A per-layer metric's own reader: benchmark/metrics/<name>.py."""
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"),
        os.path.join(HERE, "metrics", name + ".py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def judge(checks: dict, failed: int) -> bool:
    """`correct`: every number compared is within its limit, something was
    compared, and nothing failed."""
    return bool(checks) and failed == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    ap.add_argument("--plant", default=None,
                    help="break the timed path underneath (tests; rehearsal only)")
    args = ap.parse_args(argv)
    if args.plant and not args.rehearse:
        raise SystemExit("--plant is for --rehearse 1 only")

    bench, ctx, device = open_cell(
        args.workload, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), rehearse=bool(args.rehearse),
        planted=args.plant,
    )
    cell = ctx.cell
    driver = importlib.import_module("benchmark.drivers." + ctx.config["driver"])
    run = driver.run(ctx)
    run["peaks"] = ctx.peaks
    run["chips"] = ctx.chips

    device["memory_peak_bytes"] = run["memory_peak_bytes"]
    values = dict(run["end_to_end"], setup_s=run["setup_s"])
    out = {}
    breakdown = None
    if ctx.trace:
        from benchmark.trace import reduce

        trace = None
        if run.get("trace_dir"):
            try:
                trace = reduce.load(reduce.find_xplane(run["trace_dir"]))
            finally:
                shutil.rmtree(run["trace_dir"], ignore_errors=True)
        run["trace"] = trace
        busy = reduce.busy_seconds(trace) if trace else None
        if not ctx.rehearse:
            if not busy:
                raise SystemExit("the traced window holds no device operation")
            device["busy_s"] = busy
            device["window_s"] = run["trace_window_s"]
        if trace:
            breakdown = {
                "device_ops": [[n, s] for n, s in reduce.top_ops(trace)],
                "idle_gaps": [[n, s] for n, s in reduce.idle_gaps(trace)],
            }
        for m in bench["per_layer"]:
            if reports(m, cell["name"]):
                v = read_metric(m["name"], run)
                if v is not None:
                    out[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in bench["end_to_end"]:
            if reports(m, cell["name"]):
                out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    checks = run["checks"]
    line = {"correct": judge(checks, run["failed"]), "attempted": run["attempted"],
            "failed": run["failed"]}
    if ctx.rehearse:
        line["metrics"] = {}
        line["rehearsal"] = {k: v["value"] for k, v in out.items()}
    else:
        line["metrics"] = out
    line["device"] = device
    if breakdown:
        line["breakdown"] = breakdown
    # for the reader of a run (the driver ignores these): set-up's cache
    # misses and the driver's own side readings
    line["cache_misses"] = ctx._cache_misses
    line["info"] = run.get("info", {})
    line["checks"] = checks
    sys.stdout.flush()
    for k, c in checks.items():
        print(f"check {k}: value {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
