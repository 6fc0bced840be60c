"""On-chip LayerNorm block-size tuner — fills _TUNED_BLOCK_ROWS.

The reference's FastLayerNorm (apex/contrib/csrc/layer_norm/
ln_kernel_traits.h) hardcodes tuned kernel traits per hidden size; the TPU
analog is the row-block size of the Pallas LN kernels.  This sweeps
block_rows per hidden size on the real chip (fwd and fwd+bwd), prints a
table, and emits the dict literal to paste into
apex_tpu/ops/pallas/layer_norm.py::_TUNED_BLOCK_ROWS.

Run (on a TPU host):  python tools/ln_tune.py [--rows 16384]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from apex_tpu.ops.pallas import layer_norm as ln

HIDDENS = [768, 1024, 1536, 2048, 3072, 4096, 5120, 6144, 8192]
BLOCKS = [8, 16, 32, 64, 128, 256]


def _time_scan(step, x, args, iters=24, trials=3):
    """Per-iteration time of ``step`` under a data-dependent lax.scan.

    A host clock around independent repeated calls sees dispatch, not
    execution; a scan whose carry feeds each iteration's input from the
    previous one forces serialized device execution, so
    chunk_time/iters is honest.
    """

    @jax.jit
    def chunk(x):
        def body(carry, _):
            out = step(carry, *args)
            return out[0], out[1]
        carry, last = jax.lax.scan(body, x, None, length=iters)
        # f32 scalar the host pulls to prove the chunk executed: the
        # remote runtime has been observed returning early from bare
        # block_until_ready (attn_tune's r5 under-wait caveat), while a
        # value fetch cannot complete before the producing execution.
        return carry, jnp.sum(last.astype(jnp.float32))

    carry, sync = chunk(x)
    float(sync)  # warmup/compile, synced
    times = []
    for _ in range(trials):
        t0 = time.perf_counter()
        carry, sync = chunk(carry)
        float(sync)  # device->host: the sync point
        times.append((time.perf_counter() - t0) / iters)
    times.sort()
    return times[len(times) // 2]


def tune(rows, dtype=jnp.bfloat16):
    best = {}
    print(f"rows={rows} dtype={dtype.__name__} backend={jax.default_backend()}")
    print(f"{'hidden':>7} " + " ".join(f"br={b:<4d}" for b in BLOCKS)
          + "  best (fwd+bwd us)")
    for hidden in HIDDENS:
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (rows, hidden), dtype)
        w = jnp.ones((hidden,), dtype)
        b = jnp.zeros((hidden,), dtype)
        times = []
        for br in BLOCKS:
            if br * hidden * 4 > 8_000_000:  # > ~8MB per VMEM buffer: skip
                times.append(float("inf"))
                continue
            try:
                g = jnp.ones_like(x)

                def step(x, w, b, g, _br=br):
                    """fwd+bwd; returns (dx, scalar) — dx feeds the next
                    scan iteration so device work serializes."""
                    y, mu, rstd = ln.layer_norm_fwd(
                        x, w, b, eps=1e-5, rms=False, block_rows=_br
                    )
                    dx, dw, db = ln.layer_norm_bwd(
                        x, w, b, mu, rstd, g, rms=False,
                        x_is_output=False, block_rows=_br,
                    )
                    # mix y in so neither pass can be DCE'd
                    return dx + y * 1e-6, jnp.sum(dw)

                t = _time_scan(step, x, (w, b, g))
                times.append(t)
            except Exception as e:
                print(f"  hidden={hidden} br={br} failed: {str(e)[:80]}")
                times.append(float("inf"))
        ibest = min(range(len(BLOCKS)), key=lambda i: times[i])
        best[hidden] = BLOCKS[ibest]
        cells = " ".join(
            f"{t * 1e6:7.0f}" if t != float("inf") else "      -"
            for t in times
        )
        print(f"{hidden:>7} {cells}  -> br={BLOCKS[ibest]}"
              f" ({times[ibest] * 1e6:.0f}us)")
    print("\n_TUNED_BLOCK_ROWS = {")
    for h, b in best.items():
        print(f"    {h}: {b},")
    print("}")
    return best


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=16384)
    args = ap.parse_args()
    tune(args.rows)
