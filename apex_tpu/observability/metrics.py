"""Device-side metric registry — step telemetry without host syncs.

The reference stack's training scripts print loss/grad-norm by pulling
device scalars to the host every step — a forced ``device→host`` sync
that serializes dispatch.  :class:`MetricRegistry` splits the problem
the functional-JAX way:

- **inside the jitted step** the metrics live in a small pytree of f32
  scalars threaded through the step like any other state
  (``state = registry.update(state, {...})``).  Counters add, gauges
  replace, ``min``/``max`` fold — a handful of scalar ops fused into
  the step program, far below the <1% overhead budget
  (``tests/test_observability.py`` asserts it).
- **on the host** :meth:`MetricRegistry.observe` is called once per
  step with the *device* state.  It only stashes the array references
  (JAX dispatch is async — holding an array does not sync).  Every
  ``fetch_every`` steps it starts an **async** device→host copy of the
  newest state and materializes the copy started one cadence earlier,
  so a value is at most ``2 * fetch_every`` steps stale and the host
  never blocks on the device between fetches.

**Host-side event counters** — things a Python loop counts between
two compiled programs (the serving scheduler's tokens, admissions,
sheds; the fleet router's ledger) — never touch the device:
:meth:`MetricRegistry.host_init` gives a dict of Python floats,
:meth:`MetricRegistry.host_update` folds into it in place by the same
declared kinds, and :meth:`MetricRegistry.observe` takes that dict like
any other state.  ``update`` outside a jit would launch a transfer and
a tiny program per value (0.4-0.6 ms each on the chip's host, PERF.md).

Host-side-only values (wall-clock timings, static config) go on the
module-level :data:`board` — a plain gauge dictionary with no device
involvement — which ``apex_tpu.parallel.comm`` uses to publish the
wire-byte/collective-count plan of every gradient sync at trace time.

See ``docs/observability.md``.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, Mapping, Optional

import jax
import jax.numpy as jnp

__all__ = ["MetricRegistry", "Board", "board"]

_KINDS = ("counter", "gauge", "min", "max")


class MetricRegistry:
    """Declare metrics, accumulate them in-jit, fetch them on a cadence.

    >>> reg = MetricRegistry(fetch_every=32)
    >>> reg.gauge("train/loss")
    >>> reg.counter("train/skips")
    >>> state = reg.init()                      # pytree of f32 scalars
    >>> # ... inside the jitted step:
    >>> #   state = reg.update(state, {"train/loss": loss, ...})
    >>> # ... on the host, once per step:
    >>> #   reg.observe(step, state)
    >>> reg.fetch()                             # force-drain at the end
    >>> reg.values()                            # {name: float}

    ``update`` raises ``KeyError`` on an undeclared name — a typo'd
    metric must fail at trace time, not vanish silently.

    ``update`` is for jitted steps.  Events counted by host code are
    folded in Python — ``state = reg.host_init()``, then
    ``reg.host_update(state, {"serve/tokens_out": 1})`` — and handed to
    ``observe`` the same way.
    """

    def __init__(self, *, fetch_every: int = 32):
        if fetch_every < 1:
            raise ValueError("fetch_every must be >= 1")
        from apex_tpu.observability.ometrics import ExportNamespace

        self.fetch_every = fetch_every
        self._kinds: Dict[str, str] = {}
        self._units: Dict[str, str] = {}
        # every declared key must round-trip through the OpenMetrics
        # name mapping without collisions — a key an --ops-port scrape
        # cannot represent fails HERE, at declare time
        self._export = ExportNamespace()
        self._values: Dict[str, float] = {}
        self._fetched_step: Optional[int] = None
        # double buffer: _pending is the newest observed device state,
        # _inflight the one whose async host copy is already running
        self._pending = None  # (step, state)
        self._inflight = None  # (step, state)
        self._timings: Dict[str, Dict[str, float]] = {}

    # -- declaration -------------------------------------------------------
    def _declare(self, name: str, kind: str, unit: str) -> None:
        assert kind in _KINDS
        prev = self._kinds.get(name)
        if prev is not None and prev != kind:
            raise ValueError(
                f"metric {name!r} already declared as {prev!r}"
            )
        # ValueError on an exporter-illegal key or a post-mangling
        # collision with an existing key (idempotent on re-declares)
        self._export.declare(name, kind)
        self._kinds[name] = kind
        self._units[name] = unit

    def counter(self, name: str, unit: str = "count") -> None:
        """A monotonically accumulating value (``update`` adds)."""
        self._declare(name, "counter", unit)

    def gauge(self, name: str, unit: str = "") -> None:
        """A point-in-time value (``update`` replaces)."""
        self._declare(name, "gauge", unit)

    def minimum(self, name: str, unit: str = "") -> None:
        self._declare(name, "min", unit)

    def maximum(self, name: str, unit: str = "") -> None:
        self._declare(name, "max", unit)

    def unit(self, name: str) -> str:
        return self._units.get(name, "")

    def kind(self, name: str) -> str:
        """``"counter" | "gauge" | "min" | "max"`` for a declared
        metric (the OpenMetrics exporter's type source)."""
        return self._kinds[name]

    @property
    def names(self):
        return tuple(self._kinds)

    def _declared_kind(self, name: str) -> str:
        kind = self._kinds.get(name)
        if kind is None:
            raise KeyError(
                f"metric {name!r} not declared on this registry "
                f"(have {sorted(self._kinds)})"
            )
        return kind

    # -- device side -------------------------------------------------------
    def init(self) -> Dict[str, jax.Array]:
        """Fresh device state: one f32 scalar per declared metric
        (``min``/``max`` seed at ±inf)."""
        out = {}
        for name, kind in self._kinds.items():
            if kind == "min":
                out[name] = jnp.asarray(jnp.inf, jnp.float32)
            elif kind == "max":
                out[name] = jnp.asarray(-jnp.inf, jnp.float32)
            else:
                out[name] = jnp.zeros((), jnp.float32)
        return out

    def update(
        self, state: Mapping[str, Any], values: Mapping[str, Any]
    ) -> Dict[str, jax.Array]:
        """Fold ``values`` into ``state`` — call INSIDE the jitted step.

        Everything is cast to an f32 scalar; booleans count as 0/1 so a
        skip flag feeds a counter directly.
        """
        out = dict(state)
        for name, value in values.items():
            kind = self._declared_kind(name)
            v = jnp.asarray(value, jnp.float32)
            if kind == "counter":
                out[name] = out[name] + v
            elif kind == "min":
                out[name] = jnp.minimum(out[name], v)
            elif kind == "max":
                out[name] = jnp.maximum(out[name], v)
            else:
                out[name] = v
        return out

    # -- host-side event counters ------------------------------------------
    def host_init(self) -> Dict[str, float]:
        """Fresh HOST state: one Python float per declared metric
        (``min``/``max`` seed at ±inf), for :meth:`host_update`."""
        seeds = {"min": float("inf"), "max": float("-inf")}
        return {
            name: seeds.get(kind, 0.0) for name, kind in self._kinds.items()
        }

    def host_update(
        self, state: Dict[str, float], values: Mapping[str, Any]
    ) -> None:
        """Fold ``values`` into a :meth:`host_init` state IN PLACE, by
        the declared kinds — plain Python arithmetic, no device contact
        (:meth:`observe` copies the dict it is handed)."""
        for name, value in values.items():
            kind = self._declared_kind(name)
            v = float(value)
            if kind == "counter":
                state[name] += v
            elif kind == "min":
                state[name] = min(state[name], v)
            elif kind == "max":
                state[name] = max(state[name], v)
            else:
                state[name] = v

    # -- host side ---------------------------------------------------------
    def observe(self, step: int, state: Mapping[str, Any]) -> None:
        """Stash the step's state; fetch on the cadence.

        Called once per step with CONCRETE arrays (outside jit) or with
        a :meth:`host_update` dict of Python floats.  Cheap on
        off-cadence steps: one dict copy, no device contact.
        """
        self._pending = (int(step), dict(state))
        if step % self.fetch_every == 0:
            self._rotate()

    def _rotate(self) -> None:
        if self._inflight is not None:
            self._materialize(self._inflight)
            self._inflight = None
        if self._pending is not None:
            step, state = self._pending
            for v in state.values():
                copy = getattr(v, "copy_to_host_async", None)
                if copy is not None:
                    copy()
            self._inflight = (step, state)
            self._pending = None

    def _materialize(self, stash) -> None:
        step, state = stash
        for name, v in state.items():
            self._values[name] = float(v)
        self._fetched_step = step

    def fetch(self) -> Dict[str, float]:
        """Force-drain both buffers (blocks) and return the values —
        call at checkpoints / shutdown, not per step.

        The pending stash (the NEWEST observed state) is flushed in a
        ``finally``: even when materializing the in-flight copy raises
        (a device buffer poisoned by the failure being debugged), the
        newest values still land — the flight recorder's last frame
        must never be one cadence stale because an OLDER fetch died.
        """
        inflight, self._inflight = self._inflight, None
        pending, self._pending = self._pending, None
        try:
            if inflight is not None:
                self._materialize(inflight)
        finally:
            if pending is not None:
                self._materialize(pending)
        return dict(self._values)

    def close(self) -> Dict[str, float]:
        """Best-effort drain for exception paths: like :meth:`fetch`
        but NEVER raises — per-value failures keep the previous value
        so a partially poisoned state still yields its healthy scalars
        (the dump path of :class:`~apex_tpu.observability.flight.
        FlightRecorder` relies on this)."""
        for stash in (self._inflight, self._pending):
            if stash is None:
                continue
            step, state = stash
            landed = False
            for name, v in state.items():
                try:
                    self._values[name] = float(v)
                    landed = True
                except Exception:
                    pass
            # only claim the stash's freshness if something from it
            # actually materialized — a fully poisoned stash must not
            # stamp cadence-old values with the crash step in the dump
            if landed:
                self._fetched_step = step
        self._inflight = self._pending = None
        return dict(self._values)

    def values(self) -> Dict[str, float]:
        """Latest fetched values (no device contact; possibly stale by
        up to ``2 * fetch_every`` steps)."""
        return dict(self._values)

    @property
    def fetched_step(self) -> Optional[int]:
        """The step the current :meth:`values` were captured at."""
        return self._fetched_step

    # -- host-side timings -------------------------------------------------
    @contextlib.contextmanager
    def timing(self, name: str):
        """Host-side duration stat: ``with reg.timing("io/save"): ...``
        accumulates {count, total_s, last_s} — wall clock, never device
        time (use :mod:`apex_tpu.observability.trace` for that)."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            rec = self._timings.setdefault(
                name, {"count": 0.0, "total_s": 0.0, "last_s": 0.0}
            )
            rec["count"] += 1.0
            rec["total_s"] += dt
            rec["last_s"] = dt

    def timings(self) -> Dict[str, Dict[str, float]]:
        return {k: dict(v) for k, v in self._timings.items()}


class Board:
    """Host-side gauge board: module-level, no device state.

    The escape hatch for values produced where no registry is in scope
    — ``apex_tpu.parallel.comm`` publishes each gradient sync's planned
    wire bytes / collective count here at trace time.  Values are plain
    Python scalars or short strings.
    """

    def __init__(self):
        self._values: Dict[str, Any] = {}

    def set(self, name: str, value) -> None:
        self._values[name] = value

    def get(self, name: str, default=None):
        return self._values.get(name, default)

    def snapshot(self) -> Dict[str, Any]:
        return dict(self._values)

    def clear(self) -> None:
        self._values.clear()


#: The process-wide board (cleared by tests via ``board.clear()``).
board = Board()
