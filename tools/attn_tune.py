"""On-chip flash-attention block-size tuner.

≙ the reference's hand-tuned per-shape kernel traits (fmha's fixed-seqlen
kernels / multihead_attn's launch configs).  The Pallas kernels take
``block_q``/``block_k``; ``_auto_block`` picks 512/256 heuristically.
This sweeps (block_q, block_k) on the real chip for the two bench-critical
shapes (BASELINE #4 mha and the long-context config) plus fwd-only and
fwd+bwd, prints TFLOP/s per cell, and flags where the heuristic loses.

``--prune`` runs the compile-free kernel analyzer
(``apex_tpu.analysis.kernels``) over every cell FIRST: infeasible
configs (VMEM overflow, tile misalignment, non-dividing blocks) and
cells the cost model predicts ``--prune-ratio``x slower than the best
predicted cell are dropped before paying their compile; the survivors
are ranked by predicted TFLOP/s.  ``--prune --dry-run`` prints the
KEEP/PRUNE table and exits without touching a device (the
verify_tier1.sh smoke).  The model's ranking is validated against the
recorded v5e sweeps (tests/data/attn_sweep_r05.json): every recorded
cell within 5% of the measured best survives pruning.

``--cache-out FILE`` persists each sweep's measured winner into the
on-disk tuning cache (``apex_tpu.ops.pallas.tune_cache`` schema) —
point ``APEX_TPU_TUNE_CACHE`` at the file and ``_tuned_tile`` consults
it at dispatch, no source edit needed.  Combined with ``--prune
--dry-run`` it instead persists the cost model's best PREDICTED cell
per sweep flavor — a device-free ranking artifact
(``tools/tune_cache_v5e.json`` is committed from exactly this) so the
next on-chip window starts one command from the model's pick; a real
measured sweep overwrites the predictions through the same merge
path.

Run (on a TPU host):  python tools/attn_tune.py [--shapes mha,long]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from apex_tpu.ops.pallas import flash_attention as fa

SHAPES = {
    # name: (batch, heads, sq, d, causal)
    "mha": (8, 16, 2048, 64, True),      # BASELINE #4 microbench shape
    "long": (1, 8, 16384, 128, True),    # bench.py --config long_attn
    "bert": (128, 16, 128, 64, False),   # headline phase-1 shape
    "tiny": (1, 2, 256, 64, True),       # CPU interpret-mode smoke
}
BLOCKS = [128, 256, 512, 1024]

# Any cell whose implied rate beats the chip's peak plus margin is a
# mis-timed cell, not a fast one — see the under-wait caveat below.
# Default assumes v5e (~197 TFLOP/s bf16) with ~1.27x margin for
# FLOP-count conventions; on other chips pass --peak-tflops (e.g. 459
# for v5p), matching tools/comm_structure.py's knob.
_PEAK_TFLOPS_BOUND = 250.0

# r5a measured: every kernel at the long shape wants the LARGEST swept
# tile (1024, 1024) — the optimum may sit beyond the default grid.
# --blocks 512,1024,2048 probes past it (the divisibility filter
# already drops tiles the seq doesn't divide; VMEM is the real bound:
# a (1024, 2048) f32 score tile is 8 MB).
#
# Known caveat from the 2026-08-01 v5e sweep, taken through a remote
# backend since retired and not re-checked on a directly attached chip:
# the COMBINED fwd+bwd sweep mis-timed at the mha shape (d=64) — 0.01 ms
# cells — while the long shape (d=128) timed sanely, and fwd-only and
# --bwd-only were sane at BOTH shapes.  Ruled out: trace-level DCE — the
# traced combined step's jaxpr carries all 3 pallas_calls (fwd, dkdv,
# dq) at the exact mha shape.  Two gates keep a mis-timed cell out of
# the winners: the absolute peak-TFLOP/s bound below, and the fwd-floor
# cross-check (a combined fwd+bwd cell must be STRICTLY slower than the
# same tile's fwd-only cell).


def _flops(b, h, sq, d, causal, bwd):
    # scores + PV matmuls, causal halves the live area; bwd ~2x fwd
    f = 2 * 2 * b * h * sq * sq * d * (0.5 if causal else 1.0)
    return f * (3.0 if bwd else 1.0)


def _time_scan(step, q, k, v, iters=8, trials=3):
    """Median per-iteration time with on-device serialization.

    Same discipline as ln_tune._time_scan / bench.py: a host clock
    around independent dispatches sees dispatch, not execution, so each
    scan iteration's q is data-dependent on the previous output —
    execution serializes on device and chunk_time/iters is honest.  ``step(q, k, v)`` must
    return a q-shaped tensor (o for fwd, dq for fwd+bwd).

    Sync discipline: each timed chunk ends with a device->host VALUE
    pull (float(sum)), not bare block_until_ready — the remote runtime
    has been observed returning early from block_until_ready for some
    program shapes (the r5 "0.01 ms cells", see module caveat), while
    fetching a value cannot complete before the producing execution
    has.  bench.py times the same way (its `last_sync` scalar).
    """

    @jax.jit
    def chunk(q):
        def body(carry, _):
            out = step(carry, k, v)
            return carry + out * jnp.asarray(1e-8, carry.dtype), None

        carry, _ = jax.lax.scan(body, q, None, length=iters)
        # f32 scalar alongside the carry: the value the host pulls to
        # prove the chunk executed (negligible: one pass over carry)
        return carry, jnp.sum(carry.astype(jnp.float32))

    carry, sync = chunk(q)
    float(sync)  # warmup/compile, synced
    times = []
    for _ in range(trials):
        t0 = time.perf_counter()
        carry, sync = chunk(carry)
        float(sync)  # device->host: the sync point
        times.append((time.perf_counter() - t0) / iters)
    times.sort()
    return times[len(times) // 2]


#: sweep flavor -> the kernel_specs modes whose predicted times the
#: prune model sums (what each sweep actually dispatches per cell)
_PRUNE_MODES = {
    "fwd": ("fwd",),
    "fwd+bwd": ("fwd", "dkdv", "dq"),
    "bwd-only": ("dkdv", "dq"),
    # the bwd-only PHASE-2 sweep varies the dq call's tiles alone
    # (dkdv pinned at its winner), so its prune must price the dq
    # kernel alone — a cell whose dkdv is slow can still hold the
    # best dq tile (the committed mha entry is exactly that shape)
    "dq-only": ("dq",),
}


def _prune_verdicts(name, sweep_mode, blocks, ratio, device_kind):
    """Model verdict per (bq, bk) cell: ("KEEP"|"PRUNE", prediction,
    reason).  ``sweep_mode`` keys :data:`_PRUNE_MODES` so the model
    prices exactly the kernels that sweep flavor times (a bwd-only
    sweep must not prune on a fwd prediction it never measures).
    Infeasible = any ERROR finding from the kernel passes;
    model-dominated = predicted time beyond ``ratio``x the best
    feasible cell's."""
    from apex_tpu.analysis import kernels as ka

    b, h, sq, d, causal = SHAPES[name]
    dk = fa.padded_head_dim(d)
    modes = _PRUNE_MODES[sweep_mode]
    preds = {}
    for bq in blocks:
        if bq > sq or sq % bq:
            continue
        for bk in blocks:
            if bk > sq or sq % bk:
                continue
            specs = fa.kernel_specs(
                b * h, sq, sq, dk, causal=causal, block_q=bq,
                block_k=bk, modes=modes,
            )
            preds[(bq, bk)] = ka.predict_config(
                specs, device_kind=device_kind
            )
    feasible = [p["time_s"] for p in preds.values() if p["feasible"]]
    best = min(feasible) if feasible else None
    verdicts = {}
    for cell, p in preds.items():
        if not p["feasible"]:
            verdicts[cell] = (
                "PRUNE", p,
                "infeasible: " + ",".join(p["report"].rule_ids()),
            )
        elif best is not None and p["time_s"] > ratio * best:
            verdicts[cell] = (
                "PRUNE", p,
                f"model-dominated ({p['time_s'] / best:.2f}x best "
                f"predicted)",
            )
        else:
            verdicts[cell] = ("KEEP", p, "")
    return verdicts


def _print_verdicts(name, mode, verdicts, ratio):
    kept = sum(1 for v, _, _ in verdicts.values() if v == "KEEP")
    print(f"\n== {name} {SHAPES[name]} {mode} — model prune "
          f"(ratio {ratio}x): keep {kept}/{len(verdicts)} ==")
    print(f"{'':>5} {'bq':>5} {'bk':>5} {'pred ms':>9} {'pred TF/s':>9}"
          "  reason")
    by_time = sorted(
        verdicts.items(), key=lambda kv: kv[1][1]["time_s"]
    )
    for (bq, bk), (verdict, p, reason) in by_time:
        print(f"{verdict:>5} {bq:5d} {bk:5d} {p['time_s'] * 1e3:9.2f} "
              f"{p['tflops']:9.1f}  {reason}")


def _grid_sweep(
    name, mode, make_step, flops, sq, d, q, k, v, floor=None, keep=None
):
    """Shared (bq, bk) grid driver: divisibility filter, timing,
    FAILED formatting, best tracking, auto-heuristic footer.
    ``make_step(bq, bk)`` returns a q-shaped-output step for
    :func:`_time_scan`.

    ``floor`` is the under-wait cross-check invariant:
    ``{(bq, bk): seconds}`` of a STRICTLY-CHEAPER sweep of the same
    shape (fwd-only vs this combined fwd+bwd).  A cell timing at or
    under its floor is physically impossible — it means the timing
    under-waited at a *plausible* sub-peak rate the absolute gate
    cannot catch — so it is flagged and excluded from winners.

    ``keep`` (from :func:`_prune_verdicts`) restricts the sweep to the
    model-approved cells — pruned cells print and skip, paying neither
    compile nor device time.

    Returns ``(best, times)`` where ``times`` maps every successfully
    timed cell (flagged ones included) to its seconds, so a fwd sweep's
    result can serve as the next sweep's floor.
    """
    print(f"\n== {name} {SHAPES[name]} {mode} ==")
    print(f"{'bq':>5} {'bk':>5} {'ms':>9} {'TFLOP/s':>9}")
    best = (None, 0.0)
    times = {}
    for bq in BLOCKS:
        if bq > sq or sq % bq:
            continue
        for bk in BLOCKS:
            if bk > sq or sq % bk:
                continue
            if keep is not None and (bq, bk) not in keep:
                print(f"{bq:5d} {bk:5d}   PRUNED  (model; --prune)")
                continue
            try:
                t = _time_scan(make_step(bq, bk), q, k, v)
            except Exception as e:
                print(f"{bq:5d} {bk:5d}   FAILED  {type(e).__name__}:"
                      f" {str(e)[:60]}")
                continue
            times[(bq, bk)] = t
            tflops = flops / t / 1e12
            # Plausibility gate for the remote runtime's under-wait
            # artifact (see module caveat): no real cell can beat the
            # chip's peak; an "impossible" rate means block_until_ready
            # returned early and the cell must not become a winner.
            if tflops > _PEAK_TFLOPS_BOUND:
                print(f"{bq:5d} {bk:5d} {t * 1e3:9.2f} {tflops:9.1f}"
                      "  IMPLAUSIBLE (under-wait; excluded)")
                continue
            if floor is not None and (bq, bk) in floor and t <= floor[(bq, bk)]:
                print(f"{bq:5d} {bk:5d} {t * 1e3:9.2f} {tflops:9.1f}"
                      f"  UNDER-WAIT (<= fwd-only {floor[(bq, bk)] * 1e3:.2f}"
                      " ms at this tile; excluded)")
                continue
            mark = ""
            if tflops > best[1]:
                best = ((bq, bk), tflops)
                mark = "  <-- best"
            print(f"{bq:5d} {bk:5d} {t * 1e3:9.2f} {tflops:9.1f}{mark}")
    auto = fa._auto_block(sq, d)
    print(f"auto heuristic picks ({auto}, {auto}); best {best[0]} "
          f"at {best[1]:.1f} TFLOP/s")
    return best, times


def _qkv(name):
    b, h, sq, d, causal = SHAPES[name]
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b * h, sq, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b * h, sq, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b * h, sq, d), jnp.bfloat16)
    return b, h, q, k, v, sq, d, causal, d ** -0.5


def sweep(name, bwd, floor=None, keep=None):
    b, h, q, k, v, sq, d, causal, scale = _qkv(name)
    flops = _flops(b, h, sq, d, causal, bwd)

    def make_step(bq, bk):
        if bwd:
            # fwd + the recomputation backward, kernels called directly
            # (the public custom_vjp sits a layer up).  ALL outputs are
            # folded into the q-shaped carry — returning dq alone lets
            # XLA DCE the entire dkdv pallas_call (two independent
            # side-effect-free calls) and the sweep would time only dq.
            def step(q, k, v):
                o, lse = fa.flash_fwd(
                    q, k, v, None, scale=scale, causal=causal,
                    block_q=bq, block_k=bk,
                )
                dq, dk, dv = fa.flash_bwd(
                    q, k, v, o, lse, 2.0 * o, None, scale=scale,
                    causal=causal, block_q=bq, block_k=bk,
                )
                return dq + (dk + dv) * jnp.asarray(1e-8, dq.dtype)
        else:
            def step(q, k, v):
                o, _ = fa.flash_fwd(
                    q, k, v, None, scale=scale, causal=causal,
                    block_q=bq, block_k=bk,
                )
                return o
        return step

    mode = "fwd+bwd" if bwd else "fwd"
    return _grid_sweep(
        name, mode, make_step, flops, sq, d, q, k, v, floor=floor,
        keep=keep,
    )


def sweep_bwd_only(name, keep=None, keep_dq=None):
    """Isolate the backward kernels (dkdv + dq pallas_calls, ~2/3 of a
    train step's attention time): time ``flash_bwd`` alone against
    constant precomputed (o, lse, do).  Values are garbage after the
    first carry feedback — timing-only, same shapes/FLOPs — but this
    splits the fwd+bwd sweep's confound: a (bq, bk) that wins fwd+bwd
    may be carrying a fwd win over a bwd loss."""
    b, h, q, k, v, sq, d, causal, scale = _qkv(name)
    o, lse = jax.jit(
        lambda q, k, v: fa.flash_fwd(
            q, k, v, None, scale=scale, causal=causal
        )
    )(q, k, v)
    o, lse = jax.block_until_ready((o, lse))
    flops = _flops(b, h, sq, d, causal, bwd=True) * 2.0 / 3.0  # bwd share

    def make_step(bq, bk):
        def step(q, k, v):
            dq, dk, dv = fa.flash_bwd(
                q, k, v, o, lse, 2.0 * o, None, scale=scale,
                causal=causal, block_q=bq, block_k=bk,
            )
            # fold dk/dv in: dq alone would DCE the dkdv pallas_call
            return dq + (dk + dv) * jnp.asarray(1e-8, dq.dtype)
        return step

    best, _ = _grid_sweep(
        name, "bwd-only", make_step, flops, sq, d, q, k, v, keep=keep
    )

    # Explicit config dict on EVERY path so consumers can't misread
    # which pair is which: apply as flash_bwd(block_q=.., block_k=..,
    # block_q_dq=.., block_k_dq=..).
    if best[0] is None:
        return {"dkdv": None, "dq": None, "tflops": 0.0}
    dkdv_bq, dkdv_bk = best[0]

    # phase 2: pin the dkdv tiles at the winner, sweep the dq call's
    # independent tiles (block_q_dq/block_k_dq) — the two kernels walk
    # the grid transposed, so their optima can differ
    def make_step_dq(bq, bk):
        def step(q, k, v):
            dq, dk, dv = fa.flash_bwd(
                q, k, v, o, lse, 2.0 * o, None, scale=scale,
                causal=causal, block_q=dkdv_bq, block_k=dkdv_bk,
                block_q_dq=bq, block_k_dq=bk,
            )
            return dq + (dk + dv) * jnp.asarray(1e-8, dq.dtype)
        return step

    best_dq, _ = _grid_sweep(
        name, f"bwd-only dq-tiles (dkdv pinned {dkdv_bq},{dkdv_bk})",
        make_step_dq, flops, sq, d, q, k, v,
        keep=keep_dq if keep_dq is not None else keep,
    )
    if best_dq[0] is None:
        # every phase-2 cell failed: the shared-tile phase-1 winner is
        # still a valid measured config — don't discard it
        return {"dkdv": best[0], "dq": best[0], "tflops": best[1]}
    return {"dkdv": best[0], "dq": best_dq[0], "tflops": best_dq[1]}


#: dry-run sweep flavor -> tuning-cache tile mode.  The combined
#: fwd+bwd (or bwd-only phase-1) sweep decides the shared bwd tile
#: pair; the dq-only phase decides the dq call's independent pair.
#: Only one of fwd+bwd / bwd-only appears per invocation, so the
#: shared "bwd" target never collides.
_CACHE_MODE = {
    "fwd": "fwd", "fwd+bwd": "bwd", "bwd-only": "bwd",
    "dq-only": "bwd_dq",
}


def _persist_predicted(cache_out, name, verdicts_by_mode, device_kind):
    """``--prune --dry-run --cache-out``: persist the cost model's best
    PREDICTED KEEP cell per sweep flavor.  No device was touched, so
    these are ranking artifacts, not measurements — but they make the
    next on-chip session one command (point ``APEX_TPU_TUNE_CACHE`` at
    the file) instead of a cold heuristic start, and a later measured
    sweep overwrites them through the same merge-write."""
    from apex_tpu.ops.pallas import tune_cache

    b, h, sq, d, causal = SHAPES[name]
    tiles = {}
    for sweep_mode, verdicts in verdicts_by_mode.items():
        kept = {
            cell: p for cell, (vd, p, _) in verdicts.items()
            if vd == "KEEP"
        }
        if kept:
            best = min(kept.items(), key=lambda cp: cp[1]["time_s"])
            tiles[_CACHE_MODE[sweep_mode]] = best[0]
    if not tiles:
        return
    tune_cache.update_flash(
        cache_out, sq=sq, d=fa.padded_head_dim(d), causal=causal,
        tiles=tiles, dtype="bfloat16", backend=device_kind,
    )
    print(f"[attn_tune] cached {name} PREDICTED winners {tiles} "
          f"-> {cache_out}")


def _persist_winner(cache_out, name, tiles):
    """Write a sweep's measured winner(s) into the on-disk tuning
    cache — the artifact ``_tuned_tile`` consults at dispatch."""
    from apex_tpu.ops.pallas import tune_cache

    b, h, sq, d, causal = SHAPES[name]
    tiles = {m: p for m, p in tiles.items() if p}
    if not tiles:
        return
    try:
        backend = jax.devices()[0].device_kind
    except Exception:
        backend = None
    tune_cache.update_flash(
        cache_out, sq=sq, d=fa.padded_head_dim(d), causal=causal,
        tiles=tiles, dtype="bfloat16", backend=backend,
    )
    print(f"[attn_tune] cached {name} winners {tiles} -> {cache_out}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="mha,long")
    ap.add_argument("--fwd-only", action="store_true")
    ap.add_argument("--bwd-only", action="store_true",
                    help="sweep flash_bwd alone (constant o/lse/do) to "
                         "decouple the backward tile choice from fwd")
    ap.add_argument("--blocks", default=None,
                    help="comma-separated tile grid override, e.g. "
                         "512,1024,2048 (default: 128,256,512,1024)")
    ap.add_argument("--peak-tflops", type=float, default=197.0,
                    help="chip peak bf16 TFLOP/s for the under-wait "
                         "plausibility gate (default v5e 197; v5p 459)")
    ap.add_argument("--prune", action="store_true",
                    help="drop infeasible/model-dominated cells via the "
                         "compile-free kernel analyzer before sweeping")
    ap.add_argument("--dry-run", action="store_true",
                    help="with --prune: print the KEEP/PRUNE table and "
                         "exit without touching a device")
    ap.add_argument("--prune-ratio", type=float, default=1.5,
                    help="prune cells predicted this many times slower "
                         "than the best predicted cell (default 1.5)")
    ap.add_argument("--device-kind", default="TPU v5 lite",
                    help="device-kind string for the prune model's "
                         "peak/VMEM tables (default v5e; the sweep "
                         "itself always times the local chip)")
    ap.add_argument("--cache-out", default=None, metavar="FILE",
                    help="persist measured winners into this tuning-"
                         "cache JSON (APEX_TPU_TUNE_CACHE schema)")
    args = ap.parse_args()
    if args.blocks:
        BLOCKS = [int(x) for x in args.blocks.split(",")]
    if args.dry_run and not args.prune:
        ap.error("--dry-run requires --prune")
    _PEAK_TFLOPS_BOUND = 1.27 * args.peak_tflops
    for name in args.shapes.split(","):
        keeps = {}
        verdicts_by_mode = {}
        if args.prune:
            if args.bwd_only:
                prune_sweeps = ["bwd-only", "dq-only"]
            elif args.fwd_only:
                prune_sweeps = ["fwd"]
            else:
                prune_sweeps = ["fwd", "fwd+bwd"]
            for sweep_mode in prune_sweeps:
                v = _prune_verdicts(
                    name, sweep_mode, BLOCKS, args.prune_ratio,
                    args.device_kind,
                )
                _print_verdicts(name, sweep_mode, v, args.prune_ratio)
                verdicts_by_mode[sweep_mode] = v
                keeps[sweep_mode] = {
                    c for c, (verdict, _, _) in v.items()
                    if verdict == "KEEP"
                }
        keep_fwd = keeps.get("fwd")
        keep_bwd = keeps.get("fwd+bwd") or keeps.get("bwd-only")
        if args.dry_run:
            if args.cache_out:
                _persist_predicted(
                    args.cache_out, name, verdicts_by_mode,
                    args.device_kind,
                )
            continue
        if args.bwd_only:
            result = sweep_bwd_only(
                name, keep=keep_bwd, keep_dq=keeps.get("dq-only")
            )
            if args.cache_out and result.get("dkdv"):
                _persist_winner(args.cache_out, name, {
                    "bwd": result["dkdv"], "bwd_dq": result["dq"],
                })
            continue
        best_fwd, fwd_times = sweep(name, bwd=False, keep=keep_fwd)
        if args.cache_out and best_fwd[0]:
            _persist_winner(args.cache_out, name, {"fwd": best_fwd[0]})
        if not args.fwd_only:
            # the fwd-only cells are the combined sweep's floor: a
            # fwd+bwd cell at most as slow as fwd alone is an under-wait
            best_bwd, _ = sweep(
                name, bwd=True, floor=fwd_times, keep=keep_bwd
            )
            if args.cache_out and best_bwd[0]:
                _persist_winner(
                    args.cache_out, name, {"bwd": best_bwd[0]}
                )
