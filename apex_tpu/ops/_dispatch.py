"""Kernel dispatch policy: Pallas TPU kernels vs XLA-fused jnp.

The reference gates its CUDA extensions at import time (``setup.py`` build
flags + per-feature try-import probes).  Here every op has two
implementations with identical numerics:

- a **jnp path** — plain JAX the XLA compiler fuses; always available, the
  correctness reference, and what CPU tests exercise;
- a **Pallas path** — a hand-tiled TPU kernel used where fusion *structure*
  matters (row reductions, attention); selected automatically on TPU
  backends, or forced via :func:`set_use_pallas` (with ``interpret=True``
  under non-TPU backends so kernel math is testable on CPU).
"""

from __future__ import annotations

import os
from typing import Optional

import jax

_FORCE: Optional[bool] = None


def set_use_pallas(value: Optional[bool]) -> None:
    """Force (True/False) or restore auto (None) Pallas kernel selection."""
    global _FORCE
    _FORCE = value


def forced() -> Optional[bool]:
    """The current force state (None = auto) — lets ops apply shape
    heuristics only in auto mode while tests can still pin a path."""
    return _FORCE


def use_pallas() -> bool:
    if _FORCE is not None:
        return _FORCE
    if os.environ.get("APEX_TPU_DISABLE_PALLAS", "").lower() in ("1", "true", "yes"):
        return False
    return jax.default_backend() == "tpu"


def pallas_interpret() -> bool:
    """Interpret mode: needed whenever the backend is not a real TPU."""
    return jax.default_backend() != "tpu"


# --------------------------------------------------------------------------
# Trace-time path triage: the pallas and jnp paths draw DIFFERENT
# dropout streams by documented contract, so when a shape or backend
# change silently flips the dispatch, reproducibility debugging needs to
# see which path a call actually took.
# --------------------------------------------------------------------------

_PATH_LOG: dict = {}


def record_path(op: str, path: str) -> None:
    """Record which implementation ``op`` selected ("pallas" | "jnp").

    Called by the dispatching ops at TRACE time — a cached jit execution
    does not re-trace and therefore does not re-record; the log answers
    "which path did the most recent trace of this op take", which is the
    question cross-backend reproducibility triage asks."""
    _PATH_LOG[op] = path


def last_paths() -> dict:
    """op name -> "pallas" | "jnp" for every op traced since import (or
    the last :func:`clear_paths`)."""
    return dict(_PATH_LOG)


def clear_paths() -> None:
    _PATH_LOG.clear()
