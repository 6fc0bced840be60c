"""Unified step telemetry — the shared reporting spine of apex_tpu.

One subsystem every layer reports into, so "what is my MFU, step time,
comm volume, and goodput right now" is a query, not an archaeology
session over bench logs:

- :mod:`apex_tpu.observability.metrics` —
  :class:`~apex_tpu.observability.metrics.MetricRegistry`: device-side
  counters/gauges accumulated INSIDE the jitted step and fetched
  asynchronously on a cadence (no per-step host sync; <1% step-time
  overhead, asserted in tests), plus the host-side
  :data:`~apex_tpu.observability.metrics.board` that
  ``apex_tpu.parallel.comm`` publishes wire-byte/collective gauges to.
- :mod:`apex_tpu.observability.meter` —
  :class:`~apex_tpu.observability.meter.StepMeter` (wall-clock step
  time, tokens/s, model-FLOPs MFU — the same FLOP/peak model as
  ``bench.py``) and :class:`~apex_tpu.observability.meter.
  GoodputAccountant` (productive vs. skipped/rolled-back/replayed
  steps, fed by ``run_resilient`` observer events).
- :mod:`apex_tpu.observability.export` — JSONL (bench.py line schema),
  CSV, and TensorBoard-event sinks behind one
  :class:`~apex_tpu.observability.export.Reporter` ``report()`` API.
- :mod:`apex_tpu.observability.trace` — ``annotate`` / ``trace`` plus
  :class:`~apex_tpu.observability.trace.TraceScheduler`: "profile
  steps N..N+K to this dir" via ``APEX_TPU_TRACE_STEPS``, no script
  edits.
- :mod:`apex_tpu.observability.spans` —
  :class:`~apex_tpu.observability.spans.SpanRecorder`: ring-buffered
  structured spans with monotonic timestamps anchored once to wall
  clock — per-request serve lifecycles (``queued → admitted →
  prefill → decode[i] → done|shed(reason)``) with engine-iteration
  correlation ids, per-step train spans from the ``run_resilient``
  observer protocol, health events and profiler-window markers —
  merged into one Perfetto timeline by
  :class:`~apex_tpu.observability.export.TimelineSink` /
  ``tools/timeline.py``.  ``SpanRecorder.phase()`` is the host-span
  primitive (ring entry + profiler ``TraceAnnotation``);
  :func:`~apex_tpu.observability.spans.process_recorder` is the ring
  the serving host loop always writes.
- :mod:`apex_tpu.observability.flight` —
  :class:`~apex_tpu.observability.flight.FlightRecorder`: a ring
  buffer of the last N steps' telemetry + event log, dumped
  atomically to ``flight_<ts>.json`` on crash / skip-budget
  exhaustion / SIGTERM (armed by ``APEX_TPU_FLIGHT=N[:DIR]`` or
  ``run_resilient(flight=...)``); ``tools/flight_view.py`` renders
  the postmortem.
- :mod:`apex_tpu.observability.fleet` —
  :class:`~apex_tpu.observability.fleet.FleetAggregator`: every
  host's metric row gathered through ONE jitted collective on the
  registry's cadence (no per-step host sync) into per-host columns
  + min/median/max rollups on host 0's board.
- :mod:`apex_tpu.observability.health` —
  :class:`~apex_tpu.observability.health.Watchdog`: declarative
  rules (straggler z-score, MFU/goodput floors, loss spike, NaN
  rate, stale fetch, hung step, comm/host-stall fraction floors)
  emitting structured
  :class:`~apex_tpu.observability.health.HealthEvent` s to the
  sinks/flight recorder, with ``on_unhealthy`` escalation (e.g.
  arm a trace window — alert→profile in one run).
- :mod:`apex_tpu.observability.ometrics` — the live ops plane: a
  dependency-free OpenMetrics exporter over the registry/board key
  vocabulary (validated injective name mapping), host-side
  :class:`~apex_tpu.observability.ometrics.Histogram` s, and a stdlib
  ``http.server`` :class:`~apex_tpu.observability.ometrics.OpsServer`
  serving ``GET /metrics`` from cached values (never a blocking
  fetch) — armed by ``--ops-port`` / ``APEX_TPU_OPS_PORT``.
- :mod:`apex_tpu.observability.slo` — declarative SLOs (TTFT latency,
  goodput, shed rate) with Google-SRE multi-window multi-burn-rate
  alerting; a firing is a normal
  :class:`~apex_tpu.observability.health.HealthEvent`, so an SLO page
  lands on the same merged timeline as the request spans that blew
  the budget.
- :mod:`apex_tpu.observability.canary` — canary analysis for fleet
  deploys: golden-probe model fingerprints (seeded probe prompts,
  greedy streams + prefill-logits bytes hashed blake2b — a single
  flipped weight bit flips the digest) and
  :class:`~apex_tpu.observability.canary.CanaryAnalyzer` statistical
  drift verdicts (one-sided Mann–Whitney U / exact binomial tails
  with a min-sample honesty floor), driving the fleet's canary-gated
  rolling updates with auto-halt + rollback
  (``tools/canary_drill.py``).
- :mod:`apex_tpu.observability.memstats` — live device-memory
  watermarks (``device.memory_stats()`` behind a provider interface,
  fake provider on CPU) cross-checked against the static analyzer's
  peak-HBM predictions (drift names the program), with an
  OOM-forensics hook that drains the watermark history into the
  flight recorder on allocation failure.
- :mod:`apex_tpu.observability.attribution` — step-time attribution
  and roofline analysis: the compiled cost model (per-op FLOPs/bytes
  bucketed matmul/attention/norm-elementwise/collective/other via
  ``analysis/hlo.py``) cross-checked against measured profiler trace
  windows, reduced to compute/collective/host-stall fractions and a
  per-bucket roofline (``tools/step_profile.py``,
  ``tools/bench_diff.py`` ride it).

See ``docs/observability.md`` for the full tour.
"""

from apex_tpu.observability.fleet import (  # noqa: F401
    FleetAggregator,
    FleetView,
)
from apex_tpu.observability.flight import (  # noqa: F401
    FlightRecorder,
    parse_flight_spec,
)
from apex_tpu.observability.health import (  # noqa: F401
    CheckpointStallRule,
    CollectiveFractionRule,
    HealthEvent,
    HostStallRule,
    InputStallRule,
    MemoryBudgetRule,
    QueueDepthRule,
    QueueWaitFractionRule,
    ServeFaultRule,
    SpecAcceptanceRule,
    TTFTRule,
    Watchdog,
    default_rules,
    goodput_rules,
    serve_rules,
)
from apex_tpu.observability.canary import (  # noqa: F401
    CanaryAnalyzer,
    CanaryConfig,
    CanaryController,
    CanaryVerdict,
    GoldenProbeSet,
    binom_tail,
    fingerprint_distance,
    mann_whitney_p,
    model_fingerprint,
)
from apex_tpu.observability.spans import (  # noqa: F401
    SpanRecorder,
    monotonic_to_epoch,
    process_recorder,
    wall_clock_anchor,
)
from apex_tpu.observability.attribution import (  # noqa: F401
    CostAttribution,
    TraceAttribution,
    attribute_cost_model,
    attribute_trace,
    attribute_trace_dir,
    hlo_bucket_map,
    publish_attribution,
    roofline_report,
)
from apex_tpu.observability.export import (  # noqa: F401
    CSVSink,
    JSONLSink,
    Reporter,
    TensorBoardSink,
    TimelineSink,
    bench_record,
)
from apex_tpu.observability.meter import (  # noqa: F401
    BUCKETS,
    GoodputAccountant,
    StepMeter,
    categorize_op,
    UnknownDeviceError,
    chip_peak_flops,
    peak_flops_for,
    peak_hbm_bandwidth_for,
    total_peak_flops,
    transformer_train_flops,
)
from apex_tpu.observability.metrics import (  # noqa: F401
    Board,
    MetricRegistry,
    board,
)
from apex_tpu.observability.locks import (  # noqa: F401
    TrackedLock,
    lock_order_graph,
    reset_sanitizer,
    sanitizer_report,
)
from apex_tpu.observability.locks import arm as locksan_arm  # noqa: F401
from apex_tpu.observability.locks import armed as locksan_armed  # noqa: F401
from apex_tpu.observability.locks import (  # noqa: F401
    attach_flight as locksan_attach_flight,
)
from apex_tpu.observability.ometrics import (  # noqa: F401
    Histogram,
    OpsServer,
    metric_name,
    parse_exposition,
)
from apex_tpu.observability.slo import (  # noqa: F401
    SLO,
    BurnRateTracker,
    CounterRatioSLO,
    LatencySLO,
    SLORule,
    Window,
    fleet_slo_rules,
    serve_slo_rules,
)
from apex_tpu.observability.memstats import (  # noqa: F401
    DeviceMemoryProvider,
    FakeMemoryProvider,
    MemStatsMonitor,
    MemStatsRule,
    oom_forensics,
)
# NOTE: the trace() context manager is deliberately NOT re-exported
# here — it would shadow the `apex_tpu.observability.trace` SUBMODULE
# attribute on the package.  Reach it as `observability.trace.trace`
# or via the long-standing `apex_tpu.utils.trace` alias.
from apex_tpu.observability import trace  # noqa: F401
from apex_tpu.observability.trace import (  # noqa: F401
    TraceScheduler,
    annotate,
)

__all__ = [
    "MetricRegistry",
    "Board",
    "board",
    "FlightRecorder",
    "parse_flight_spec",
    "FleetAggregator",
    "FleetView",
    "Watchdog",
    "HealthEvent",
    "default_rules",
    "goodput_rules",
    "serve_rules",
    "CheckpointStallRule",
    "CollectiveFractionRule",
    "HostStallRule",
    "InputStallRule",
    "MemoryBudgetRule",
    "TTFTRule",
    "QueueDepthRule",
    "QueueWaitFractionRule",
    "ServeFaultRule",
    "SpecAcceptanceRule",
    "SpanRecorder",
    "process_recorder",
    "wall_clock_anchor",
    "monotonic_to_epoch",
    "CanaryAnalyzer",
    "CanaryConfig",
    "CanaryController",
    "CanaryVerdict",
    "GoldenProbeSet",
    "model_fingerprint",
    "fingerprint_distance",
    "mann_whitney_p",
    "binom_tail",
    "TrackedLock",
    "lock_order_graph",
    "sanitizer_report",
    "reset_sanitizer",
    "locksan_arm",
    "locksan_armed",
    "locksan_attach_flight",
    "OpsServer",
    "Histogram",
    "metric_name",
    "parse_exposition",
    "SLO",
    "CounterRatioSLO",
    "LatencySLO",
    "BurnRateTracker",
    "SLORule",
    "Window",
    "serve_slo_rules",
    "fleet_slo_rules",
    "MemStatsMonitor",
    "MemStatsRule",
    "DeviceMemoryProvider",
    "FakeMemoryProvider",
    "oom_forensics",
    "StepMeter",
    "GoodputAccountant",
    "BUCKETS",
    "categorize_op",
    "UnknownDeviceError",
    "chip_peak_flops",
    "peak_flops_for",
    "peak_hbm_bandwidth_for",
    "total_peak_flops",
    "transformer_train_flops",
    "CostAttribution",
    "TraceAttribution",
    "attribute_cost_model",
    "attribute_trace",
    "attribute_trace_dir",
    "hlo_bucket_map",
    "publish_attribution",
    "roofline_report",
    "Reporter",
    "JSONLSink",
    "CSVSink",
    "TensorBoardSink",
    "TimelineSink",
    "bench_record",
    "TraceScheduler",
    "annotate",
    "trace",  # the submodule (holding the trace() context manager)
]
