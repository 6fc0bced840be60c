"""On-disk kernel tuning cache — measured tile winners as an artifact.

The ROADMAP called the source-level ``_TUNED_TILES`` /
``_TUNED_BLOCK_ROWS`` tables "half-implemented": committing sweep
winners required editing kernel source, so a bench run on a new shape
could never feed the next run's dispatch.  This module makes the
winners a real artifact:

- ``APEX_TPU_TUNE_CACHE=/path/to/cache.json`` is loaded ONCE on first
  lookup (trace time — the kernel entry points take tile sizes as
  static args, so dispatch never pays the file read twice);
- :func:`flash_tiles` / :func:`layer_norm_block_rows` are consulted by
  ``flash_attention._tuned_tile`` and ``layer_norm._block_rows``
  BEFORE their source tables, falling back source-table → heuristic
  exactly as before when no entry matches;
- ``tools/attn_tune.py --cache-out`` persists sweep winners with
  :func:`update_flash` (merge-write: one file accumulates shapes
  across runs).

Schema (JSON, one object)::

    {"version": 1,
     "flash_attention": [
        {"sq": 16384, "d": 128, "causal": true,
         "dtype": "bfloat16" | null,      # null = any dtype
         "backend": "TPU v5 lite" | null, # null = any; prefix-matched
         "tiles": {"fwd": [1024, 1024],
                   "bwd": [1024, 1024],
                   "bwd_dq": [1024, 1024]}}],
     "layer_norm": [
        {"hidden": 4096, "backend": null, "block_rows": 64}]}

Entries are keyed by (shape, dtype, causal, backend); ``backend`` is
matched by prefix against the local device kind (``"TPU v5"`` matches
``"TPU v5 lite"``) so one cache file can serve a heterogeneous fleet,
and ``null`` fields are wildcards.  The FIRST matching entry wins —
write more-specific entries above generic ones.  A malformed cache
file warns once and is ignored (dispatch must never break on a stale
artifact).
"""

from __future__ import annotations

import json
import os
import warnings
from typing import Optional, Tuple

__all__ = [
    "ENV_VAR",
    "flash_tiles",
    "layer_norm_block_rows",
    "load",
    "update_flash",
    "update_layer_norm",
    "reset",
]

ENV_VAR = "APEX_TPU_TUNE_CACHE"

#: (path, parsed dict) of the last successful load — cleared by
#: :func:`reset` (tests) and re-checked when the env var changes.
_CACHE: Optional[tuple] = None


def reset() -> None:
    """Forget the loaded cache (next lookup re-reads the env/file)."""
    global _CACHE
    _CACHE = None


def _backend_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


def load(path: Optional[str] = None) -> dict:
    """Parse ``path`` (default: ``$APEX_TPU_TUNE_CACHE``); ``{}`` when
    unset, missing, or malformed (malformed warns once per load)."""
    path = path or os.environ.get(ENV_VAR)
    if not path or not os.path.exists(path):
        return {}
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
        if not isinstance(data, dict):
            raise ValueError("cache root must be a JSON object")
        return data
    except (ValueError, OSError) as e:
        warnings.warn(
            f"ignoring malformed tuning cache {path!r}: {e}", stacklevel=2
        )
        return {}


def _cached() -> dict:
    global _CACHE
    path = os.environ.get(ENV_VAR) or ""
    if _CACHE is None or _CACHE[0] != path:
        _CACHE = (path, load(path or None))
    return _CACHE[1]


def _match(entry: dict, *, dtype: Optional[str], backend: str) -> bool:
    want_dtype = entry.get("dtype")
    if want_dtype is not None and dtype is not None and want_dtype != dtype:
        return False
    want_backend = entry.get("backend")
    if want_backend is not None and not backend.startswith(want_backend):
        return False
    return True


def flash_tiles(
    mode: str, sq: int, d: int, causal: bool, dtype=None,
) -> Optional[Tuple[int, int]]:
    """Cached (block_q, block_k) for a flash-attention call, or None.

    ``mode`` ∈ {"fwd", "bwd", "bwd_dq"} — the same keys as
    ``flash_attention._TUNED_TILES``.  ``dtype`` may be a jax dtype or
    name string; None skips the dtype filter.
    """
    entries = _cached().get("flash_attention")
    if not entries:
        return None
    if dtype is None:
        dtype_name = None
    else:
        try:  # normalizes np dtypes, jnp scalar TYPES, and strings alike
            import numpy as np

            dtype_name = np.dtype(dtype).name
        except (TypeError, ImportError):
            dtype_name = getattr(dtype, "name", None) or str(dtype)
    backend = _backend_kind()
    for entry in entries:
        if not isinstance(entry, dict):
            continue
        if entry.get("sq") != sq or entry.get("d") != d:
            continue
        if bool(entry.get("causal")) != bool(causal):
            continue
        if not _match(entry, dtype=dtype_name, backend=backend):
            continue
        pair = (entry.get("tiles") or {}).get(mode)
        if (
            isinstance(pair, (list, tuple)) and len(pair) == 2
            and all(isinstance(x, int) and x > 0 for x in pair)
        ):
            return (pair[0], pair[1])
    return None


def layer_norm_block_rows(hidden: int) -> Optional[int]:
    """Cached row-block size for a fused layer-norm call, or None."""
    entries = _cached().get("layer_norm")
    if not entries:
        return None
    backend = _backend_kind()
    for entry in entries:
        if not isinstance(entry, dict) or entry.get("hidden") != hidden:
            continue
        if not _match(entry, dtype=None, backend=backend):
            continue
        br = entry.get("block_rows")
        if isinstance(br, int) and br > 0:
            return br
    return None


def _merge_write(
    path: str, section: str, key_fields: tuple, entry: dict, merge=None,
):
    data = load(path) if os.path.exists(path) else {}
    data.setdefault("version", 1)
    entries = [e for e in data.get(section, []) if isinstance(e, dict)]
    kept = []
    for e in entries:
        if any(e.get(k) != entry.get(k) for k in key_fields):
            kept.append(e)
        elif merge is not None:
            # fold the displaced same-key entry into the new one (a
            # fwd-sweep winner must survive the bwd sweep's write)
            entry = merge(e, entry)
    data[section] = [entry] + kept
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    reset()


def update_flash(
    path: str, *, sq: int, d: int, causal: bool, tiles: dict,
    dtype: Optional[str] = None, backend: Optional[str] = None,
) -> None:
    """Merge one flash-attention winner into the cache at ``path``
    (atomic tmp+replace).  An existing entry with the same
    (sq, d, causal, dtype, backend) key keeps the tile MODES the new
    write doesn't carry — a fwd sweep and a later bwd sweep accumulate
    into one entry instead of clobbering each other."""

    def merge(old: dict, new: dict) -> dict:
        merged = dict(old.get("tiles") or {})
        merged.update(new["tiles"])
        return {**new, "tiles": merged}

    _merge_write(
        path, "flash_attention",
        ("sq", "d", "causal", "dtype", "backend"),
        {
            "sq": int(sq), "d": int(d), "causal": bool(causal),
            "dtype": dtype, "backend": backend,
            "tiles": {
                m: [int(p[0]), int(p[1])] for m, p in tiles.items() if p
            },
        },
        merge=merge,
    )


def update_layer_norm(
    path: str, *, hidden: int, block_rows: int,
    backend: Optional[str] = None,
) -> None:
    """Merge one layer-norm winner into the cache at ``path``."""
    _merge_write(
        path, "layer_norm", ("hidden", "backend"),
        {
            "hidden": int(hidden), "backend": backend,
            "block_rows": int(block_rows),
        },
    )
