"""Shared utilities (profiling/tracing hooks).

The hooks now live in :mod:`apex_tpu.observability.trace`; this package
keeps re-exporting them (``apex_tpu.utils.trace`` is used throughout
bench.py and the tools) so callers need not care where they moved.
"""

from apex_tpu.observability.trace import annotate, trace

__all__ = ["annotate", "trace"]
