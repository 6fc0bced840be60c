"""Step meters: wall-clock step time, tokens/s, MFU, and goodput.

:class:`StepMeter` answers "how fast is this run right now" from the
host side — mark each completed step with :meth:`StepMeter.tick` and
read step time (median over a sliding window, robust to a single
stalled dispatch), tokens/s, and model-FLOPs utilization.  The FLOP/peak model is the SAME one ``bench.py`` /
``tools/mfu_sweep.py`` use for the headline (per-chip dense bf16 peak
by device kind; 6·N·T for transformer training), moved here so live
telemetry and the benchmark artifacts can never disagree on the
denominator.

:class:`GoodputAccountant` answers "how much of that speed is real
progress".  It is fed by :func:`apex_tpu.resilience.run_resilient`'s
``observer`` events (accepted/skipped steps, rollbacks with their
discarded work, checkpoint retries, resume replay) and reduces them to
one number::

    goodput = (accepted - discarded_by_rollback) / executed_steps

which is exactly the "productive steps / all steps paid for" ratio a
capacity dashboard wants.  See ``docs/observability.md``.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional

__all__ = [
    "PEAK_BF16_FLOPS",
    "PEAK_HBM_GBPS",
    "PEAK_ICI_GBPS",
    "BUCKETS",
    "VMEM_BYTES",
    "UnknownDeviceError",
    "peak_flops_for",
    "peak_hbm_bandwidth_for",
    "peak_ici_bandwidth_for",
    "vmem_bytes_for",
    "categorize_op",
    "chip_peak_flops",
    "total_peak_flops",
    "transformer_train_flops",
    "StepMeter",
    "GoodputAccountant",
    "percentile",
]


def percentile(sorted_vals, q: float) -> float:
    """Nearest-rank percentile over an already-sorted sequence — the
    ONE implementation behind the serving TTFT/latency statistics on
    every surface (scheduler gauges, ``tools/serve_bench.py``
    artifacts), so the two can never disagree on the same data.
    Returns NaN on an empty sequence ("no measurement", the bench
    schema's null)."""
    if not sorted_vals:
        return float("nan")
    i = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[i]

#: Per-chip dense bf16 peak FLOP/s by device kind (public specs) — the
#: single source bench.py's MFU headline, live telemetry, and the
#: roofline (``observability.attribution``) share.
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v5": 459e12,
    "TPU v4": 275e12,
    "TPU v6 lite": 918e12,  # v6e (Trillium)
}

#: Per-chip HBM bandwidth (bytes/s, public specs) — the roofline's
#: bandwidth ceiling and the ridge-point denominator.
PEAK_HBM_GBPS = {
    "TPU v5 lite": 819e9,  # v5e
    "TPU v5e": 819e9,
    "TPU v5p": 2765e9,
    "TPU v5": 2765e9,
    "TPU v4": 1228e9,
    "TPU v6 lite": 1640e9,  # v6e (Trillium)
}

#: Per-chip ICI bandwidth (bytes/s per link direction, public specs) —
#: the cost model's collective-time denominator.
PEAK_ICI_GBPS = {
    "TPU v5 lite": 200e9,  # v5e: 4x 100 GB/s links bidir, ~200 usable
    "TPU v5e": 200e9,
    "TPU v5p": 600e9,
    "TPU v5": 600e9,
    "TPU v4": 300e9,
    "TPU v6 lite": 400e9,
}

class UnknownDeviceError(LookupError):
    """A device kind with no entry in the peak tables.  There is no
    default peak: a utilization against an assumed chip is not a
    measurement.  Callers off the chip name the chip they model
    (``device_kind="TPU v5 lite"``), pass the peak explicitly, or
    report "not measured"."""


def _lookup(table: Dict[str, float], device_kind: str, what: str) -> float:
    try:
        return table[device_kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no {what} on record for device kind {device_kind!r} "
            f"(known: {', '.join(sorted(table))})"
        ) from None


def peak_flops_for(device_kind: str) -> float:
    """Dense bf16 peak FLOP/s for a device-kind STRING — the one
    denominator StepMeter MFU, bench.py headlines, and the roofline
    share.  Exact lookup; :class:`UnknownDeviceError` otherwise."""
    return _lookup(PEAK_BF16_FLOPS, device_kind, "bf16 peak FLOP/s")


def peak_hbm_bandwidth_for(device_kind: str) -> float:
    """HBM bytes/s for a device-kind string (roofline ceiling)."""
    return _lookup(PEAK_HBM_GBPS, device_kind, "HBM bandwidth")


def peak_ici_bandwidth_for(device_kind: str) -> float:
    """Interconnect bytes/s for a device-kind string (cost-model
    collective-time denominator)."""
    return _lookup(PEAK_ICI_GBPS, device_kind, "ICI bandwidth")


def chip_peak_flops(device) -> float:
    """Dense bf16 peak FLOP/s of one device object (delegates to
    :func:`peak_flops_for` on its ``device_kind``)."""
    return peak_flops_for(device.device_kind)


#: Per-core VMEM bytes by device kind — the kernel static analyzer's
#: (``apex_tpu.analysis.kernels``) overflow budget, kept in the same
#: home as the FLOP/bandwidth peaks so every cost model shares one
#: hardware table.  TPU generations to date all carry ~16 MiB of
#: vector memory per core (the pallas guide's "~16 MB/core").
VMEM_BYTES = {
    "TPU v5 lite": 16 * 1024 * 1024,  # v5e
    "TPU v5e": 16 * 1024 * 1024,
    "TPU v5p": 16 * 1024 * 1024,
    "TPU v5": 16 * 1024 * 1024,
    "TPU v4": 16 * 1024 * 1024,
    "TPU v6 lite": 32 * 1024 * 1024,  # v6e (Trillium)
}


def vmem_bytes_for(device_kind: str) -> int:
    """Per-core VMEM budget for a device-kind string (the
    kernel-vmem-overflow gate's denominator)."""
    return int(_lookup(VMEM_BYTES, device_kind, "VMEM size"))


# ---------------------------------------------------------------------------
# the bucket model: one op-category vocabulary for attribution/roofline
# ---------------------------------------------------------------------------

#: The op-category buckets step-time attribution decomposes into — the
#: shared vocabulary of the cost model, the trace parser, the roofline
#: table, and the watchdog's fraction rules.
BUCKETS = ("matmul", "attention", "norm_elementwise", "collective", "other")

_ATTENTION_HINTS = (
    "attention", "attn", "flash", "mha", "multihead", "softmax_xent",
)
#: "conv_general"/"convolution" (jax's conv_general_dilated), never a
#: bare "conv": dtype casts print as convert/convert_element_type and
#: must fall through to the elementwise branch, not inflate matmul
_MATMUL_HINTS = (
    "dot_general", "einsum", "conv_general", "convolution", "conv2d",
    "matmul", "dense", "gemm", "dot",
)
_NORM_ELEMENTWISE_HINTS = (
    "norm", "softmax", "gelu", "relu", "tanh", "sigmoid", "logistic",
    "dropout", "bias", "residual", "add", "mul", "rope", "rotary",
    "scale", "mean", "var", "rsqrt", "exp", "erf",
)
_ELEMENTWISE_OPCODES = frozenset((
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "power", "negate", "abs", "compare", "select", "clamp", "convert",
    "exponential", "log", "tanh", "logistic", "sqrt", "rsqrt", "sine",
    "cosine", "erf", "reduce", "reduce-window", "map", "broadcast",
    "iota", "floor", "ceil", "sign", "and", "or", "xor", "not",
))


def categorize_op(opcode: str, op_name: str = "") -> str:
    """Bucket one op into :data:`BUCKETS` from its HLO opcode and
    ``op_name`` metadata (the jax source path — named scopes land
    there, so a ``dot`` inside ``named_scope("flash_attention")``
    buckets as attention, which is what a roofline wants: the
    attention bucket owns its matmuls).

    Priority: collective > attention > matmul > norm-elementwise >
    other.  Works on trace-event names too: pass the event name as
    ``op_name`` with its leading token as ``opcode`` (fused kernels
    print like ``add_multiply_fusion.78``, carrying their content in
    the name).
    """
    opcode = (opcode or "").lower()
    name = (op_name or "").lower()
    if opcode.startswith(
        ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
         "collective-permute", "collective-broadcast")
    ) or any(
        k in name
        for k in ("all-reduce", "all_reduce", "all-gather", "all_gather",
                  "reduce-scatter", "reduce_scatter", "all-to-all",
                  "all_to_all", "collective-permute", "psum")
    ):
        return "collective"
    if any(k in name for k in _ATTENTION_HINTS):
        return "attention"
    if opcode in ("dot", "convolution") or any(
        k in name for k in _MATMUL_HINTS
    ):
        return "matmul"
    if opcode in _ELEMENTWISE_OPCODES or any(
        k in name for k in _NORM_ELEMENTWISE_HINTS
    ):
        return "norm_elementwise"
    return "other"


def total_peak_flops(devices) -> float:
    """Summed peak over ``devices`` — the devices the metered program is
    placed on (a mesh's, an array's), never "whatever is visible": a
    one-chip program on a four-chip host has one chip's peak."""
    return sum(chip_peak_flops(d) for d in devices)


def transformer_train_flops(n_params: int, tokens: int) -> float:
    """The 6·N·T training-FLOPs model (BASELINE.md's MFU contract)."""
    return 6.0 * float(n_params) * float(tokens)


class StepMeter:
    """Wall-clock step meter: tick once per completed step.

    The first :meth:`tick` only arms the clock (it closes no interval);
    step time is the median of the last ``window`` intervals, so a
    single stalled dispatch does not poison the rate.  The MFU
    denominator is ``peak_flops`` or, failing that, the table peak of
    ``devices`` — the devices the step is placed on.  With neither, or
    with a device kind the table does not know (CPU), MFU is not
    measured: :attr:`mfu` reads 0.0 and :meth:`summary` leaves
    ``train/mfu`` out.
    """

    def __init__(
        self,
        *,
        tokens_per_step: float = 0.0,
        flops_per_step: float = 0.0,
        peak_flops: Optional[float] = None,
        devices=None,
        window: int = 32,
        clock: Callable[[], float] = time.perf_counter,
    ):
        if window < 1:
            raise ValueError("window must be >= 1")
        self.tokens_per_step = float(tokens_per_step)
        self.flops_per_step = float(flops_per_step)
        if peak_flops is None and devices is not None:
            try:
                peak_flops = total_peak_flops(devices)
            except UnknownDeviceError:
                peak_flops = None  # MFU not measured on this device
        self.peak_flops = peak_flops
        self._window = window
        self._clock = clock
        self._last: Optional[float] = None
        self._times: list = []
        self.steps = 0  # completed (timed) intervals

    def tick(self) -> Optional[float]:
        """Mark a step boundary; returns the closed interval in seconds
        (None on the arming call)."""
        now = self._clock()
        if self._last is None:
            self._last = now
            return None
        dt = now - self._last
        self._last = now
        self._times.append(dt)
        if len(self._times) > self._window:
            self._times.pop(0)
        self.steps += 1
        return dt

    @property
    def step_time(self) -> float:
        """Median step seconds over the window (0.0 before any tick)."""
        if not self._times:
            return 0.0
        s = sorted(self._times)
        return s[len(s) // 2]

    @property
    def tokens_per_sec(self) -> float:
        t = self.step_time
        return self.tokens_per_step / t if t > 0 else 0.0

    @property
    def mfu(self) -> float:
        t = self.step_time
        if t <= 0 or self.flops_per_step <= 0 or not self.peak_flops:
            return 0.0
        return self.flops_per_step / (t * self.peak_flops)

    def summary(self) -> Dict[str, float]:
        out = {
            "train/step": float(self.steps),
            "train/step_time_ms": self.step_time * 1e3,
            "train/tokens_per_sec": self.tokens_per_sec,
        }
        if self.peak_flops:
            out["train/mfu"] = self.mfu
        return out


class GoodputAccountant:
    """Productive-work ledger over ``run_resilient`` observer events.

    Implements the observer protocol (every method optional on other
    observers): ``on_step`` / ``on_rollback`` / ``on_retry`` /
    ``on_resume`` / ``on_preempt``.  Counting rules:

    - an accepted step is *provisionally* productive;
    - a skipped step is executed-but-wasted;
    - a rollback discards the accepted-but-unsaved steps behind it —
      ``run_resilient`` passes the exact count (it tracks accepted
      steps against actual save results); when an older caller omits
      it, the fallback ``(step - anchor) - skips`` over-charges spans
      containing skip streaks broken by accepted steps, never
      under-charges;
    - a resume after restart only bumps ``resumes`` — work before the
      restart was paid for by a previous process, so charging it here
      would double-count across the job's lifetime.
    """

    def __init__(self):
        self.accepted = 0
        self.skipped = 0
        self.discarded = 0  # accepted steps a rollback threw away
        self.rollbacks = 0
        self.retries = 0
        self.resumes = 0
        self.preempted = False

    # -- observer protocol -------------------------------------------------
    def on_step(self, step: int, skipped: bool, info=None) -> None:
        if skipped:
            self.skipped += 1
        else:
            self.accepted += 1

    def on_rollback(
        self,
        step: int,
        anchor: int,
        skips: int = 0,
        discarded: Optional[int] = None,
    ) -> None:
        self.rollbacks += 1
        if discarded is None:
            # legacy fallback: the replay span minus the final skip
            # streak (an upper bound when the span holds earlier,
            # broken skip streaks)
            discarded = max(0, (step - anchor) - skips)
        self.discarded += discarded

    def on_retry(self, what: str = "", attempt: int = 0, error=None) -> None:
        self.retries += 1

    def on_resume(self, step: int) -> None:
        self.resumes += 1

    def on_preempt(self, step: int) -> None:
        self.preempted = True

    # -- ledger ------------------------------------------------------------
    @property
    def executed(self) -> int:
        return self.accepted + self.skipped

    @property
    def productive(self) -> int:
        return max(0, self.accepted - self.discarded)

    def goodput(self) -> float:
        """Productive fraction of executed steps (1.0 before any work —
        an idle job has wasted nothing yet)."""
        if self.executed == 0:
            return 1.0
        return self.productive / self.executed

    def snapshot(self) -> Dict[str, Any]:
        """The full ledger as plain values — monotonic event counts +
        the derived fractions.  The stable read API for consumers that
        would otherwise reach into fields (the flight recorder's dump,
        fleet aggregation rows, the resilient example's final goodput
        line): one place to keep key names honest."""
        return {
            "accepted": self.accepted,
            "skipped": self.skipped,
            "discarded": self.discarded,
            "rollbacks": self.rollbacks,
            "retries": self.retries,
            "resumes": self.resumes,
            "preempted": self.preempted,
            "executed": self.executed,
            "productive": self.productive,
            "goodput": self.goodput(),
        }

    def summary(self) -> Dict[str, float]:
        return {
            "train/goodput": self.goodput(),
            "train/steps_accepted": float(self.accepted),
            "train/steps_skipped": float(self.skipped),
            "train/steps_discarded": float(self.discarded),
            "train/rollbacks": float(self.rollbacks),
            "train/retries": float(self.retries),
            "train/resumes": float(self.resumes),
        }
