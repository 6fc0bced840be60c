"""Static analysis of Pallas kernels — lint + cost model, no compile.

The graph passes (:mod:`apex_tpu.analysis.passes`) see a compiled step
in which every Pallas kernel is one opaque custom-call; this module
analyzes the kernels THEMSELVES, from the
:class:`~apex_tpu.ops.pallas.introspect.KernelSpec` records the kernel
modules export off their own call plans
(``flash_attention.kernel_specs`` / ``layer_norm.kernel_specs`` /
``decode_attention.kernel_specs``).  Nothing traces or compiles: a
config is judged in microseconds, which is what lets
``tools/attn_tune.py --prune`` reject most of a sweep grid before the
hardware sees it.

Five passes, same :class:`~apex_tpu.analysis.findings.Finding`
currency as every other pass:

- **VMEM footprint** (``kernel-vmem-overflow``) — double-buffered
  input/output blocks + scratch + declared in-kernel intermediates at
  true dtype widths, gated against the backend's VMEM
  (:func:`apex_tpu.observability.meter.vmem_bytes_for`).
- **tiling alignment** (``kernel-tile-misaligned``) — block dims vs
  the (sublane, 128-lane) tile quantum for the operand dtype (a dim
  covering its whole array axis is exempt: Mosaic lowers untiled
  full-extent trailing dims), ragged tails (these kernels have no
  partial-tile masking, so a non-dividing block silently mis-indexes),
  and MXU-feeding extents that aren't 128 multiples (a 96-wide score
  tile wastes a quarter of every systolic pass).
- **grid coverage / race** (``kernel-grid-oob``,
  ``kernel-block-race``) — the REAL index maps evaluated over the
  grid: block offsets out of range, and two grid cells that differ
  along a *parallel* axis writing the same output block (revisits
  along the sequential "arbitrary" axes are the kernels' documented
  accumulate-in-scratch pattern, not a race).
- **causal dead tiles** (``kernel-dead-tiles``) — reuses
  ``_causal_block_live``'s math to report the wasted-FLOP fraction a
  config pays on partially-masked tiles (a naive whole-seq tile wastes
  ~50% of its MXU work on the masked triangle).
- **roofline verdict** — static FLOPs and HBM bytes (the byte model
  replays Pallas's pipeline: a block is re-fetched exactly when its
  index-map output changes across the row-major grid walk) give
  arithmetic intensity against :mod:`~apex_tpu.observability.meter`'s
  shared peak table, a compute/memory/grid bound verdict, and a
  predicted ceiling — the ranking signal the tuner prunes with.

Absolute predicted TFLOP/s are optimistic (the model has no
software-pipeline stalls); the *ranking* across tile configs is what
is validated against the recorded v5e sweep
(``tests/data/attn_sweep_r05.json``).
"""

from __future__ import annotations

import itertools
import math
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from apex_tpu.analysis.findings import (
    ERROR,
    Finding,
    Report,
    make_finding,
)
from apex_tpu.ops.pallas.introspect import (
    BlockArg,
    KernelSpec,
    buffer_bytes,
    dtype_width,
)

__all__ = [
    "KERNEL_PASSES",
    "analyze",
    "analyze_default_kernels",
    "default_kernel_specs",
    "dead_tile_stats",
    "predict_config",
    "publish_kernel_report",
    "roofline",
    "vmem_footprint",
]

_LANES = 128
#: minimum sublane count by dtype width (the pallas guide's tile table)
_SUBLANE = {1: 32, 2: 16, 4: 8, 8: 8}

#: fixed cost per grid step (DMA issue, accumulator init/flush, causal
#: offset bookkeeping).  Calibrated on the recorded v5e sweeps: at the
#: mha shape a (128, 128) causal grid is ~33k tiles whose fixed cost
#: dominates, and the model must reproduce the measured ordering
#: (large tiles win at both recorded shapes) — see
#: tests/test_kernel_analysis.py::test_prune_recorded_sweep.
_GRID_STEP_SECONDS = 3e-7

#: full-grid index-map evaluation cap; beyond it the coverage/byte
#: passes sample axis extremes / probe dependence instead of walking
#: every cell (a (128, 128)-tile long-context grid is 131k cells)
_COVERAGE_CELL_CAP = 32768

KERNEL_PASSES = (
    "kernel-vmem", "kernel-tiling", "kernel-coverage", "kernel-dead-tiles",
)


# ---------------------------------------------------------------------------
# VMEM footprint model
# ---------------------------------------------------------------------------


def vmem_footprint(spec: KernelSpec) -> Dict[str, int]:
    """Per-config VMEM bytes: ``block_bytes`` (input/output blocks,
    x2 for the pipeline's double buffering), ``scratch_bytes``,
    ``intermediate_bytes`` (declared in-kernel values — e.g. the f32
    score tile), and their ``total_bytes``.

    ``block_bytes + scratch_bytes`` is the part reconstructable from
    the pallas_call arguments alone — the model-vs-interpret agreement
    test pins it against a captured real call; intermediates ride only
    the overflow gate."""
    blocks = 2 * sum(a.block_bytes() for a in spec.blocked())
    scratch = sum(buffer_bytes(s, dt) for s, dt in spec.scratch)
    inter = sum(buffer_bytes(s, dt) for s, dt in spec.intermediates)
    return {
        "block_bytes": blocks,
        "scratch_bytes": scratch,
        "intermediate_bytes": inter,
        "total_bytes": blocks + scratch + inter,
    }


def _vmem_pass(spec: KernelSpec, budget: int) -> List[Finding]:
    fp = vmem_footprint(spec)
    if fp["total_bytes"] <= budget:
        return []
    return [make_finding(
        "kernel-vmem-overflow",
        path=spec.name,
        message=(
            f"config needs ~{fp['total_bytes'] / (1 << 20):.1f} MiB VMEM "
            f"(blocks x2 {fp['block_bytes'] / (1 << 20):.1f} + scratch "
            f"{fp['scratch_bytes'] / (1 << 20):.1f} + intermediates "
            f"{fp['intermediate_bytes'] / (1 << 20):.1f}) against a "
            f"{budget / (1 << 20):.1f} MiB budget"
        ),
    )]


# ---------------------------------------------------------------------------
# Tiling-alignment lint
# ---------------------------------------------------------------------------


def _tiling_pass(spec: KernelSpec) -> List[Finding]:
    out: List[Finding] = []
    for arg in spec.blocked():
        block, shape = arg.block, arg.shape
        width = dtype_width(arg.dtype)
        sublane = _SUBLANE.get(width, 8)
        # ragged tails: the kernels have no partial-tile masking
        for dim, (b, s) in enumerate(zip(block, shape)):
            if b <= 0:
                out.append(make_finding(
                    "kernel-tile-misaligned",
                    path=f"{spec.name}/{arg.name}",
                    message=f"block dim {dim} is {b}",
                ))
            elif s % b:
                out.append(make_finding(
                    "kernel-tile-misaligned",
                    path=f"{spec.name}/{arg.name}",
                    message=(
                        f"block dim {dim} ({b}) does not divide the "
                        f"array axis ({s}) — these kernels have no "
                        f"partial-tile masking, the ragged tail would "
                        f"read/write out of range"
                    ),
                ))
        # (sublane, lane) quantum on the last two dims; a block covering
        # its WHOLE axis is exempt (Mosaic lowers untiled full-extent
        # dims — how d=64 heads stay 64 instead of lane-padding)
        if len(block) >= 1:
            last_b, last_s = block[-1], shape[-1]
            if last_b != last_s and last_b % _LANES:
                out.append(make_finding(
                    "kernel-tile-misaligned",
                    path=f"{spec.name}/{arg.name}",
                    message=(
                        f"trailing block dim {last_b} is neither the "
                        f"full axis ({last_s}) nor a {_LANES}-lane "
                        f"multiple"
                    ),
                ))
        if len(block) >= 2:
            sub_b, sub_s = block[-2], shape[-2]
            if sub_b != sub_s and sub_b % sublane:
                out.append(make_finding(
                    "kernel-tile-misaligned",
                    path=f"{spec.name}/{arg.name}",
                    message=(
                        f"sublane block dim {sub_b} is neither the full "
                        f"axis ({sub_s}) nor a multiple of the "
                        f"{arg.dtype} sublane quantum ({sublane})"
                    ),
                ))
    # MXU utilization: contraction extents the exporter declares
    for name, extent in (spec.meta.get("matmul_dims") or {}).items():
        if name == "head_dim":
            # the head dim covers its whole (caller-padded) axis by the
            # _pad_head_dim contract; only a broken pad is a finding
            if extent % 8:
                out.append(make_finding(
                    "kernel-tile-misaligned",
                    path=f"{spec.name}/{name}",
                    message=(
                        f"head dim {extent} is not sublane-aligned — "
                        f"the caller-side _pad_head_dim contract is "
                        f"broken"
                    ),
                ))
            continue
        if extent % _LANES:
            out.append(make_finding(
                "kernel-tile-misaligned",
                path=f"{spec.name}/{name}",
                severity="warning",
                message=(
                    f"MXU contraction extent {name}={extent} is not a "
                    f"{_LANES} multiple — the 128x128 systolic array "
                    f"pads every pass to the next tile and the "
                    f"remainder lanes do dead work"
                ),
            ))
    return out


# ---------------------------------------------------------------------------
# Grid coverage / race
# ---------------------------------------------------------------------------


def _grid_cells(grid: Tuple[int, ...]) -> Iterable[Tuple[int, ...]]:
    """Every cell when the grid is small; otherwise the axis-extreme
    lattice {0, mid, max}^n (the kernels' affine-ish index maps take
    their extrema at axis extremes)."""
    total = 1
    for g in grid:
        total *= g
    if total <= _COVERAGE_CELL_CAP:
        yield from np.ndindex(*grid)
        return
    axes = [sorted({0, g // 2, g - 1}) for g in grid]
    yield from itertools.product(*axes)


def _eval_map(arg: BlockArg, cell) -> Optional[Tuple[int, ...]]:
    idx = arg.index_map(*cell)
    if not isinstance(idx, tuple):
        idx = (idx,)
    return tuple(int(x) for x in idx)


def _coverage_pass(spec: KernelSpec) -> List[Finding]:
    out: List[Finding] = []
    sem = spec.dimension_semantics or ()
    parallel_axes = [i for i, s in enumerate(sem) if s == "parallel"]
    cells = list(_grid_cells(spec.grid))
    for arg in spec.blocked():
        nblocks = [
            max(1, -(-s // b)) for s, b in zip(arg.shape, arg.block)
        ]
        oob_reported = False
        writers: Dict[Tuple[int, ...], Tuple[int, ...]] = {}
        raced = False
        is_output = arg in spec.outputs
        for cell in cells:
            try:
                idx = _eval_map(arg, cell)
            except Exception as e:  # a map that cannot evaluate IS a bug
                out.append(make_finding(
                    "kernel-grid-oob",
                    path=f"{spec.name}/{arg.name}",
                    message=(
                        f"index map failed at grid cell {cell}: "
                        f"{type(e).__name__}: {e}"
                    ),
                ))
                oob_reported = True
                break
            if len(idx) != len(arg.block):
                out.append(make_finding(
                    "kernel-grid-oob",
                    path=f"{spec.name}/{arg.name}",
                    message=(
                        f"index map returns rank {len(idx)} for a rank "
                        f"{len(arg.block)} block"
                    ),
                ))
                oob_reported = True
                break
            if not oob_reported and any(
                i < 0 or i >= nb for i, nb in zip(idx, nblocks)
            ):
                out.append(make_finding(
                    "kernel-grid-oob",
                    path=f"{spec.name}/{arg.name}",
                    message=(
                        f"grid cell {cell} maps to block offset {idx} "
                        f"outside the {tuple(nblocks)} block grid of "
                        f"shape {arg.shape}"
                    ),
                ))
                oob_reported = True
            if is_output and not raced:
                pcoord = tuple(cell[a] for a in parallel_axes)
                prev = writers.get(idx)
                if prev is None:
                    writers[idx] = pcoord
                elif prev != pcoord:
                    out.append(make_finding(
                        "kernel-block-race",
                        path=f"{spec.name}/{arg.name}",
                        message=(
                            f"grid cells at parallel coordinates "
                            f"{prev} and {pcoord} both write output "
                            f"block {idx} — parallel grid dims carry "
                            f"no accumulation semantics, the second "
                            f"write clobbers the first in an "
                            f"unspecified order"
                        ),
                    ))
                    raced = True
    return out


# ---------------------------------------------------------------------------
# Causal dead-tile accounting
# ---------------------------------------------------------------------------


def dead_tile_stats(spec: KernelSpec) -> Optional[Dict[str, float]]:
    """Live/dead tile counts and the wasted-FLOP fraction of the live
    tiles under the causal mask (``None`` for non-causal specs).

    Reuses ``_causal_block_live``'s liveness rule, so the accounting
    and the kernels' ``pl.when`` skip can never disagree."""
    if not spec.causal:
        return None
    from apex_tpu.ops.pallas.flash_attention import _causal_block_live

    c = spec.causal
    bq, bk, offset = c["bq"], c["bk"], c["offset"]
    nq = spec.grid[c["q_axis"]]
    nk = spec.grid[c["k_axis"]]
    include = bool(c.get("include_fully_masked"))

    i = np.arange(nq)[:, None]
    j = np.arange(nk)[None, :]
    live = np.asarray(_causal_block_live(i, j, bq, bk, offset, include))
    live_tiles = int(live.sum())

    # unmasked (= productive) elements per tile: rows r of tile i see
    # clip(r + offset + 1 - j*bk, 0, bk) columns of tile j
    rows = np.arange(bq)[None, None, :]
    allowed = np.clip(
        i[:, :, None] * bq + rows + offset + 1 - (j * bk)[:, :, None],
        0, bk,
    ).sum(axis=-1)
    unmasked = float((allowed * live).sum())
    executed = float(live_tiles) * bq * bk
    waste = 0.0 if executed == 0 else max(0.0, 1.0 - unmasked / executed)
    return {
        "total_tiles": float(nq * nk),
        "live_tiles": float(live_tiles),
        "dead_tiles": float(nq * nk - live_tiles),
        "waste_fraction": waste,
    }


def _dead_tile_pass(
    spec: KernelSpec, threshold: float
) -> Tuple[List[Finding], Optional[Dict[str, float]]]:
    stats = dead_tile_stats(spec)
    if stats is None or stats["waste_fraction"] <= threshold:
        return [], stats
    return [make_finding(
        "kernel-dead-tiles",
        path=spec.name,
        message=(
            f"{stats['waste_fraction']:.0%} of the live tiles' FLOPs "
            f"fall on causally-masked elements at this tile shape "
            f"({int(stats['live_tiles'])}/{int(stats['total_tiles'])} "
            f"tiles live) — above the {threshold:.0%} bound"
        ),
    )], stats


# ---------------------------------------------------------------------------
# Compile-free roofline / cost model
# ---------------------------------------------------------------------------


def _live_cells(spec: KernelSpec) -> float:
    """Grid cells that execute their compute body (causal dead tiles
    are ``pl.when``-skipped; every cell still pays DMA + grid cost)."""
    total = float(spec.cells())
    stats = dead_tile_stats(spec)
    if stats is None or stats["total_tiles"] == 0:
        return total
    return total * stats["live_tiles"] / stats["total_tiles"]


def _fetch_count(arg: BlockArg, grid: Tuple[int, ...]) -> int:
    """How many times the pipeline re-fetches this operand's block over
    the row-major grid walk — exact (simulated) on small grids, else
    the dependence-probe bound: a map depending on axes up to ``a``
    re-fetches once per distinct prefix, i.e. ``prod(grid[:a+1])``."""
    total = 1
    for g in grid:
        total *= g
    if total <= _COVERAGE_CELL_CAP:
        fetches, prev = 0, None
        for cell in np.ndindex(*grid):
            idx = _eval_map(arg, cell)
            if idx != prev:
                fetches += 1
                prev = idx
        return fetches
    base = tuple(0 for _ in grid)
    ref = _eval_map(arg, base)
    deepest = -1
    for a, g in enumerate(grid):
        if g <= 1:
            continue
        probe = list(base)
        probe[a] = g - 1
        if _eval_map(arg, tuple(probe)) != ref:
            deepest = a
    count = 1
    for g in grid[: deepest + 1]:
        count *= g
    return count


def roofline(
    spec: KernelSpec, device_kind: Optional[str] = None
) -> Dict[str, float]:
    """Static FLOPs/bytes → arithmetic intensity, ceiling, bound
    verdict, and a predicted time/TFLOP/s for this config, against
    :mod:`apex_tpu.observability.meter`'s shared peak table."""
    from apex_tpu.observability import meter

    kind = device_kind if device_kind is not None else _local_device_kind()
    peak_flops = meter.peak_flops_for(kind)
    peak_bw = meter.peak_hbm_bandwidth_for(kind)

    flops = spec.flops_per_cell * _live_cells(spec)
    bytes_moved = sum(
        _fetch_count(a, spec.grid) * a.block_bytes()
        for a in spec.blocked()
    )
    compute_s = flops / peak_flops
    memory_s = bytes_moved / peak_bw
    grid_s = spec.cells() * _GRID_STEP_SECONDS
    time_s = max(compute_s, memory_s) + grid_s
    ai = flops / bytes_moved if bytes_moved else math.inf
    bound = "grid"
    if grid_s < max(compute_s, memory_s):
        bound = "compute" if compute_s >= memory_s else "memory"
    return {
        "flops": flops,
        "bytes": float(bytes_moved),
        "arithmetic_intensity": ai,
        "ceiling_tflops": min(peak_flops, ai * peak_bw) / 1e12,
        "predicted_time_s": time_s,
        "predicted_tflops": (flops / time_s / 1e12) if time_s else 0.0,
        "bound": bound,
        "grid_cells": float(spec.cells()),
    }


def _local_device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


def _local_vmem_budget(device_kind: Optional[str]) -> int:
    from apex_tpu.observability import meter

    kind = device_kind if device_kind is not None else _local_device_kind()
    return meter.vmem_bytes_for(kind)


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------


def analyze(
    specs,
    *,
    device_kind: Optional[str] = None,
    vmem_budget: Optional[int] = None,
    dead_tile_threshold: float = 0.25,
    name: str = "",
) -> Report:
    """Run every kernel pass over one :class:`KernelSpec` (or a
    sequence — e.g. the fwd+dkdv+dq triple of one flash config) and
    return a :class:`~apex_tpu.analysis.findings.Report` whose
    ``sections["kernels"]`` carries the per-kernel VMEM footprint,
    roofline verdict, and dead-tile accounting."""
    import time as _time

    if isinstance(specs, KernelSpec):
        specs = [specs]
    specs = list(specs)
    budget = (
        vmem_budget if vmem_budget is not None
        else _local_vmem_budget(device_kind)
    )
    report = Report(
        target=name or "+".join(s.name for s in specs),
        rules_run=KERNEL_PASSES,
    )
    kernels_section: List[dict] = []
    timings = {p: 0.0 for p in KERNEL_PASSES}
    for spec in specs:
        entry = {
            "name": spec.name,
            "grid": list(spec.grid),
            "vmem": vmem_footprint(spec),
            "vmem_budget_bytes": budget,
        }
        for pass_name, fn in (
            ("kernel-vmem", lambda s: _vmem_pass(s, budget)),
            ("kernel-tiling", _tiling_pass),
            ("kernel-coverage", _coverage_pass),
        ):
            t0 = _time.perf_counter()
            report.extend(fn(spec))
            timings[pass_name] += (_time.perf_counter() - t0) * 1e3
        t0 = _time.perf_counter()
        findings, stats = _dead_tile_pass(spec, dead_tile_threshold)
        report.extend(findings)
        timings["kernel-dead-tiles"] += (_time.perf_counter() - t0) * 1e3
        if stats is not None:
            entry["dead_tiles"] = stats
        entry["roofline"] = roofline(spec, device_kind)
        kernels_section.append(entry)
    report.pass_timings.update(timings)
    report.sections["kernels"] = kernels_section
    return report


def predict_config(
    specs: Sequence[KernelSpec],
    *,
    device_kind: Optional[str] = None,
    vmem_budget: Optional[int] = None,
) -> Dict[str, object]:
    """One candidate config's verdict for the tuner: ``feasible``
    (no ERROR finding from the vmem/tiling/coverage passes),
    ``time_s``/``flops``/``tflops`` summed over the config's kernels
    (a step dispatches them back to back), and the report itself."""
    report = analyze(
        specs, device_kind=device_kind, vmem_budget=vmem_budget
    )
    time_s = flops = 0.0
    for entry in report.sections["kernels"]:
        time_s += entry["roofline"]["predicted_time_s"]
        flops += entry["roofline"]["flops"]
    return {
        "feasible": not report.errors(),
        "time_s": time_s,
        "flops": flops,
        "tflops": (flops / time_s / 1e12) if time_s else 0.0,
        "report": report,
    }


# ---------------------------------------------------------------------------
# The three shipped kernels at their default configs — the CI surface
# ---------------------------------------------------------------------------


def default_kernel_specs() -> List[Tuple[str, List[KernelSpec]]]:
    """(label, specs) for the shipped kernels at the configs the bench
    actually dispatches: flash attention at the long-context bench
    shape (tuned tiles resolve exactly as dispatch would), fused
    layer-norm at the BERT row/hidden shape, and paged decode at the
    ``ServeConfig`` pool defaults."""
    from apex_tpu.ops.pallas import decode_attention as da
    from apex_tpu.ops.pallas import flash_attention as fa
    from apex_tpu.ops.pallas import layer_norm as ln

    # bench.py --config long_attn: b=1 h=8 s=16384 d=128 causal
    flash = fa.kernel_specs(8, 16384, 16384, 128, causal=True)
    # tools/ln_tune.py's measurement shape: 16384 rows, BERT hidden
    norm = ln.kernel_specs(16384, 1024)
    # serve.ServeConfig defaults: page_size=16, num_pages=128,
    # max_batch=4, max_pages_per_seq=8; a 128-wide 8-head attention
    # (the walk the kernel's own page copies make: 8 pages a step)
    decode = da.kernel_specs(
        4, 8, 128, pool_pages=128, page=16, pages_per_seq=8,
    )
    return [
        ("flash_attention", flash),
        ("layer_norm", norm),
        ("decode_attention", decode),
    ]


def analyze_default_kernels(
    *,
    device_kind: Optional[str] = None,
    vmem_budget: Optional[int] = None,
    dead_tile_threshold: float = 0.25,
) -> Report:
    """Analyze all three shipped kernels at their default configs into
    one merged report — the ``tools/kernel_lint.py`` /
    ``verify_tier1.sh`` LINT / ``bench.py --lint`` surface."""
    merged: Optional[Report] = None
    kernels_section: List[dict] = []
    for label, specs in default_kernel_specs():
        rep = analyze(
            specs, device_kind=device_kind, vmem_budget=vmem_budget,
            dead_tile_threshold=dead_tile_threshold, name=label,
        )
        for entry in rep.sections["kernels"]:
            kernels_section.append({"config": label, **entry})
        if merged is None:
            merged = rep
        else:
            merged.merge(rep)
    assert merged is not None
    merged.target = "kernels"
    merged.sections["kernels"] = kernels_section
    return merged


def publish_kernel_report(report: Report) -> None:
    """Gauge the kernel verdicts onto the observability board
    (``analysis/kernels/...``) beside the graph-lint counts, so kernel
    regressions ride the same JSONL telemetry: per-kernel VMEM bytes,
    predicted TFLOP/s, dead-tile waste, plus the standard
    errors/warnings/rule counters from
    :func:`apex_tpu.analysis.publish_report`."""
    from apex_tpu.analysis import publish_report

    publish_report(report, prefix="analysis/kernels")
    try:
        from apex_tpu.observability.metrics import board
    except ImportError:  # pragma: no cover - partial install
        return
    for entry in report.sections.get("kernels", []):
        key = entry["name"]
        board.set(
            f"analysis/kernels/{key}/vmem_bytes",
            entry["vmem"]["total_bytes"],
        )
        board.set(
            f"analysis/kernels/{key}/predicted_tflops",
            round(entry["roofline"]["predicted_tflops"], 3),
        )
        if "dead_tiles" in entry:
            board.set(
                f"analysis/kernels/{key}/dead_tile_waste",
                round(entry["dead_tiles"]["waste_fraction"], 4),
            )
