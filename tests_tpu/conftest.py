"""On-chip kernel parity suite.

Unlike ``tests/`` (which pins the CPU backend and exercises Pallas kernels
in *interpret* mode), this directory runs against the REAL TPU backend so
the **Mosaic-compiled** kernels are what gets checked: a tiling/dtype/OOB
divergence between compiled and interpret mode surfaces here, not as a
silent numerics bug in the benchmark.

Run on a TPU host:   python -m pytest tests_tpu/ -q

Without a TPU backend a run that asked for this directory FAILS (a failed
TPU init must not read "N skipped", rc 0); in a combined repo-root run the
cases skip visibly and ``tests/`` carries on.
"""

import os

import jax
import pytest


def pytest_collection_modifyitems(config, items):
    here = os.path.dirname(os.path.abspath(__file__))
    # only mark THIS directory's items: in a combined repo-root run this
    # hook also receives tests/ items, which must keep running on CPU
    ours = [
        i for i in items
        if str(getattr(i, "fspath", "")).startswith(here)
    ]
    if not ours:
        return
    backend = jax.default_backend()
    if backend == "tpu":
        return
    reason = (
        f"compiled-Pallas parity needs the real TPU backend (got "
        f"{backend!r}; tests/ covers interpret mode on CPU)"
    )
    if len(ours) == len(items):
        pytest.exit(f"tests_tpu/: {reason}", returncode=1)
    skip = pytest.mark.skip(reason=reason)
    for item in ours:
        item.add_marker(skip)


@pytest.fixture(autouse=True)
def _restore_dispatch():
    from apex_tpu.ops import _dispatch

    yield
    _dispatch.set_use_pallas(None)
