"""One gradient-sync engine for DP all-reduce and ZeRO weight-update
sharding — wire format x chunking x verification, in one place.

Both :class:`apex_tpu.parallel.DistributedDataParallel` and the ZeRO
optimizers (:class:`apex_tpu.parallel.DistributedFusedAdam` /
``DistributedFusedLAMB``) call into this module, so the dominant
off-chip cost of the data-parallel step — gradient synchronization — is
tuned in exactly one place.  Three independent knobs:

**Wire format** (``wire="f32" | "bf16" | "int8"``).  ``f32`` is the
exact path (``psum`` / ``psum_scatter`` / ``all_gather``).  ``bf16``
halves wire bytes; ``int8`` is the blockwise-scaled code of EQuARX
(arXiv 2506.17615, generalized from ``parallel/quantized.py``): every
``block`` elements share one f32 ``max/127`` scale, and the scales'
raw bytes ride the same payload as the codes so each phase stays ONE
collective.  Whatever the wire, per-shard accumulation happens in f32
(codes are decoded before the sum), so only the wire — never the
reduction — loses precision.  Wire bytes: 4 / 2 / ~1.016 per element
(int8 pays 4 bytes per ``block`` for the scale).

**Chunking** (``chunks=K``).  The flat buffer is split into K
near-equal chunks synced in an unrolled loop, so XLA may schedule chunk
N's collective concurrently with chunk N-1's dequant / optimizer math
(the overlap the reference's bucketed NCCL pipeline builds by hand).
``K`` defaults to a bandwidth/latency heuristic seeded from the
``tools/comm_structure.py`` ICI model (v5e, 90 GB/s per chip on one
mesh axis): target ~4 MiB of wire per chunk, i.e. ~45 us of streaming —
two orders of magnitude above per-collective launch latency, so the
latency overhead of splitting stays in the noise while buffers >= 8 MiB
get at least two overlap windows.  ``APEX_TPU_COMM_CHUNKS`` overrides
everything (read at trace time — retrace to apply).

**Verification hooks**.  :func:`collective_summary` /
:func:`compiled_collectives` read every collective (count + bytes) out
of compiled HLO and :func:`ring_wire_bytes` turns them into per-chip
wire traffic under ring algorithms — so "exactly 2K collectives per
sync, ~1/4 the bytes" is a regression test (``tests/test_comm.py``),
not a docstring.  The parser itself lives with the static-analysis
subsystem (``apex_tpu/analysis/hlo.py``): ``tools/comm_structure.py``,
the ``analysis`` collective-consistency pass, and these hooks all read
compiled HLO through ONE implementation.

**Telemetry**.  Every sync publishes its plan — wire format, payload
bytes, collective count, chunk count — as gauges on the observability
board (``apex_tpu.observability.metrics.board``) at trace time, and
:func:`publish_collective_summary` pushes a parsed-HLO summary the same
way, so a live ``--metrics-out`` JSONL carries continuously measured
wire traffic next to MFU/goodput instead of a one-time HLO assertion
(``docs/observability.md``).

See ``docs/comm.md`` for the full model, tuning guidance, and when NOT
to quantize.
"""

from __future__ import annotations

import os
from typing import Any, Optional

import jax
import jax.numpy as jnp

from apex_tpu import parallel_state as ps

__all__ = [
    "WIRE_FORMATS",
    "DEFAULT_BLOCK",
    "sync_gradients",
    "reduce_scatter_flat",
    "all_gather_flat",
    "all_gather_rows",
    "resolve_chunks",
    "chunks_requested",
    "wire_bytes_per_element",
    "quantize_blocks",
    "dequantize_blocks",
    "pack_int8",
    "unpack_int8",
    "wire_payload_bytes",
    "sync_plan",
    "zero_plan",
    "collective_summary",
    "compiled_collectives",
    "ring_wire_bytes",
    "publish_collective_summary",
]

WIRE_FORMATS = ("f32", "bf16", "int8")

_QMAX = 127.0
DEFAULT_BLOCK = 256

#: Chunking heuristic target: ~4 MiB of wire per chunk = ~45 us at the
#: tools/comm_structure.py ICI model's 90 GB/s — bandwidth-dominated,
#: yet small enough that a >= 8 MiB sync gets overlap windows.
TARGET_CHUNK_BYTES = 4 << 20
_MAX_HEURISTIC_CHUNKS = 16
_MAX_CHUNKS = 64
ENV_CHUNKS = "APEX_TPU_COMM_CHUNKS"


def check_wire(wire: str) -> str:
    if wire not in WIRE_FORMATS:
        raise ValueError(
            f"wire must be one of {WIRE_FORMATS}, got {wire!r}"
        )
    return wire


def wire_bytes_per_element(wire: str, block: int = DEFAULT_BLOCK) -> float:
    """Wire bytes one f32 element costs under ``wire`` (int8 includes
    the amortized 4-byte/block scale)."""
    check_wire(wire)
    if wire == "f32":
        return 4.0
    if wire == "bf16":
        return 2.0
    return 1.0 + 4.0 / block


def chunks_requested(chunks: Optional[int]) -> bool:
    """True when chunking was explicitly asked for (arg or env) rather
    than left to the heuristic."""
    return chunks is not None or bool(os.environ.get(ENV_CHUNKS))


def resolve_chunks(wire_nbytes: int, chunks: Optional[int] = None) -> int:
    """Chunk count K: env ``APEX_TPU_COMM_CHUNKS`` > explicit ``chunks``
    > the bandwidth/latency heuristic (ceil(bytes / 4 MiB), capped at
    16).  Always >= 1."""
    env = os.environ.get(ENV_CHUNKS)
    if env:
        k = int(env)
    elif chunks is not None:
        k = int(chunks)
    else:
        k = min(
            -(-max(int(wire_nbytes), 1) // TARGET_CHUNK_BYTES),
            _MAX_HEURISTIC_CHUNKS,
        )
    return max(1, min(k, _MAX_CHUNKS))


def _chunk_bounds(n: int, k: int, align: int = 1):
    """Up to K near-equal (lo, hi) spans covering [0, n); interior edges
    round up to ``align`` (quantized wires align to ``block`` so only
    the final chunk can carry a padded tail block) and empty spans drop,
    so ragged sizes, k > n, and n < k*align are all safe — a buffer too
    small to fill K aligned chunks just gets fewer."""
    bounds, prev = [], 0
    for i in range(1, k + 1):
        edge = n if i == k else min(n, -(-((i * n) // k) // align) * align)
        if edge > prev:
            bounds.append((prev, edge))
        prev = max(prev, edge)
    return bounds


def _publish_stats(prefix: str, **stats) -> None:
    """Gauge the plan of a sync onto the observability board.

    Host-side and trace-time only (the values are static per compiled
    program): retracing republishes, steady-state steps never touch it.
    Import is deferred so the comm engine stays importable even if the
    observability package is stripped from a deployment.
    """
    try:
        from apex_tpu.observability.metrics import board
    except ImportError:  # pragma: no cover - partial install
        return
    for key, value in stats.items():
        board.set(f"{prefix}/{key}", value)


# ---------------------------------------------------------------------------
# blockwise int8 codec (generalized from parallel/quantized.py)
# ---------------------------------------------------------------------------


def _padded_len(n: int, block: int) -> int:
    return n + (-n) % block


def quantize_blocks(x, block: int = DEFAULT_BLOCK):
    """``x (..., n)`` f32 -> int8 codes ``(..., n_pad)`` + f32 scales
    ``(..., n_pad/block)`` with ``scale = max|block|/127``.

    Tail-safe: ``n`` need not divide ``block`` — the tail is zero-padded
    into its own block internally (padding zeros never raise a block
    max, so real elements keep their scale).  Zero-safe: an all-zero
    block gets scale 1.0 — never 0 or a subnormal — so the dequant path
    cannot produce NaN/Inf from ``0/0`` or overflow from ``x/tiny``.
    """
    n = x.shape[-1]
    pad = (-n) % block
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((*x.shape[:-1], pad), x.dtype)], axis=-1
        )
    xb = x.reshape(*x.shape[:-1], -1, block)
    m = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    scale = jnp.maximum(m / _QMAX, jnp.finfo(jnp.float32).tiny)
    scale = jnp.where(m > 0, scale, 1.0)
    q = jnp.clip(jnp.round(xb / scale), -_QMAX, _QMAX).astype(jnp.int8)
    return q.reshape(*x.shape[:-1], n + pad), scale[..., 0]


def dequantize_blocks(q, scale, block: int = DEFAULT_BLOCK,
                      n: Optional[int] = None):
    """Inverse of :func:`quantize_blocks`; ``n`` slices the zero-pad
    back off.  Dequantized values sit exactly on the int8 grid, so a
    second quantize/dequantize round-trip is bit-identical (the
    fixed-point property ``tests/test_quantized_allreduce.py`` pins)."""
    shape = q.shape
    xb = q.reshape(*shape[:-1], -1, block).astype(jnp.float32)
    out = (xb * scale[..., None]).reshape(shape)
    if n is not None and n != shape[-1]:
        out = out[..., :n]
    return out


def pack_int8(q, scale):
    """Append the f32 scales' raw bytes to the int8 codes so codes and
    scales ride ONE collective payload."""
    sbytes = jax.lax.bitcast_convert_type(
        scale.astype(jnp.float32), jnp.int8
    ).reshape(*q.shape[:-1], -1)
    return jnp.concatenate([q, sbytes], axis=-1)


def unpack_int8(payload, n: int, block: int = DEFAULT_BLOCK):
    """Split a packed payload back into (codes, scales) for ``n`` real
    elements quantized at ``block``."""
    n_pad = _padded_len(n, block)
    q, sbytes = payload[..., :n_pad], payload[..., n_pad:]
    scale = jax.lax.bitcast_convert_type(
        sbytes.reshape(*sbytes.shape[:-1], -1, 4), jnp.float32
    )
    return q, scale


def _encode(x, wire: str, block: int):
    """f32 ``(..., n)`` -> wire payload (same leading shape)."""
    if wire == "f32":
        return x
    if wire == "bf16":
        return x.astype(jnp.bfloat16)
    return pack_int8(*quantize_blocks(x, block))


def _decode(payload, wire: str, block: int, n: int):
    """Wire payload -> f32 ``(..., n)``."""
    if wire == "f32":
        return payload
    if wire == "bf16":
        return payload.astype(jnp.float32)
    q, scale = unpack_int8(payload, n, block)
    return dequantize_blocks(q, scale, block, n)


# ---------------------------------------------------------------------------
# flat-buffer collectives (the ZeRO building blocks)
# ---------------------------------------------------------------------------


def reduce_scatter_flat(
    flat,
    axis_name: str = ps.DATA_PARALLEL_AXIS,
    *,
    wire: str = "f32",
    chunks: Optional[int] = None,
    block: int = DEFAULT_BLOCK,
):
    """SUM-reduce a flat f32 buffer over ``axis_name`` and return my
    contiguous shard (``flat.size / world`` elements, f32).

    ``flat.size`` must divide the axis size.  ``wire="f32"`` lowers to
    ``psum_scatter``; quantized wires use one ``all_to_all`` of encoded
    payloads per chunk with f32 shard-local dequant-accumulate.  Call
    inside ``shard_map``.
    """
    check_wire(wire)
    world = jax.lax.axis_size(axis_name)
    n = flat.shape[0]
    if n == 0 or world == 1:
        return flat.astype(jnp.float32)
    if n % world:
        raise ValueError(f"flat size {n} not divisible by world {world}")
    shard = n // world
    k = min(
        resolve_chunks(int(n * wire_bytes_per_element(wire, block)), chunks),
        shard,
    )
    rows = flat.reshape(world, shard).astype(jnp.float32)
    bounds = _chunk_bounds(shard, k, 1 if wire == "f32" else block)
    _publish_stats(
        "comm/rs", wire=wire, world=world, elements=n,
        chunks=len(bounds), collectives=len(bounds),
        wire_bytes=int(n * wire_bytes_per_element(wire, block)),
    )
    outs = []
    with jax.named_scope(f"comm_rs_{wire}"):
        for lo, hi in bounds:
            seg = rows[:, lo:hi]  # row j = rank j's slice of this chunk
            if wire == "f32":
                outs.append(
                    jax.lax.psum_scatter(
                        seg.reshape(-1), axis_name,
                        scatter_dimension=0, tiled=True,
                    )
                )
            else:
                recv = jax.lax.all_to_all(
                    _encode(seg, wire, block), axis_name, 0, 0, tiled=False
                )
                outs.append(
                    jnp.sum(_decode(recv, wire, block, hi - lo), axis=0)
                )
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs)


def all_gather_flat(
    shard,
    axis_name: str = ps.DATA_PARALLEL_AXIS,
    *,
    wire: str = "f32",
    chunks: Optional[int] = None,
    block: int = DEFAULT_BLOCK,
):
    """All-gather per-rank contiguous shards back into the full flat f32
    buffer (``world * shard.size`` elements, rank-major).

    Quantized wires encode the local shard and every rank decodes the
    SAME payloads — including its own — so the gathered buffer is
    bit-identical across replicas (the invariant that keeps ZeRO params
    replicated).  Call inside ``shard_map``.
    """
    check_wire(wire)
    world = jax.lax.axis_size(axis_name)
    s = shard.shape[0]
    if s == 0 or world == 1:
        return shard.astype(jnp.float32)
    k = min(
        resolve_chunks(
            int(world * s * wire_bytes_per_element(wire, block)), chunks
        ),
        s,
    )
    shard = shard.astype(jnp.float32)
    bounds = _chunk_bounds(s, k, 1 if wire == "f32" else block)
    _publish_stats(
        "comm/ag", wire=wire, world=world, elements=world * s,
        chunks=len(bounds), collectives=len(bounds),
        wire_bytes=int(world * s * wire_bytes_per_element(wire, block)),
    )
    parts = []
    with jax.named_scope(f"comm_ag_{wire}"):
        for lo, hi in bounds:
            g = jax.lax.all_gather(
                _encode(shard[lo:hi], wire, block), axis_name,
                axis=0, tiled=False,
            )
            parts.append(_decode(g, wire, block, hi - lo))  # (world, cs)
    full = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    return full.reshape(-1)


def all_gather_rows(
    row,
    axis_name: str = ps.DATA_PARALLEL_AXIS,
    *,
    wire: str = "f32",
):
    """All-gather each participant's metrics row into a ``(world, n)``
    f32 matrix — the fleet-aggregation collective
    (:class:`apex_tpu.observability.fleet.FleetAggregator`).

    Call inside ``shard_map`` with one ``(n,)`` row per participant on
    ``axis_name``; every participant gets the identical matrix back
    (row ``j`` = participant ``j``'s values).  One collective per call
    — telemetry rows are tiny (tens of floats), so chunking would be
    pure launch overhead — riding the same engine as the gradient
    path, so it shows in ``collective_summary`` and the board gauges
    (``comm/fleet/*``) like any other wire traffic.
    """
    check_wire(wire)
    world = jax.lax.axis_size(axis_name)
    flat = row.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    _publish_stats(
        "comm/fleet", wire=wire, world=world, elements=world * n,
        collectives=1,
        wire_bytes=int(world * n * wire_bytes_per_element(wire)),
    )
    with jax.named_scope("comm_fleet_rows"):
        full = all_gather_flat(flat, axis_name, wire=wire, chunks=1)
    return full.reshape(world, n)


# ---------------------------------------------------------------------------
# tree-level gradient sync (the DDP entry point)
# ---------------------------------------------------------------------------


def sync_gradients(
    grads: Any,
    axis_name: str = ps.DATA_PARALLEL_AXIS,
    *,
    wire: str = "f32",
    chunks: Optional[int] = None,
    block: int = DEFAULT_BLOCK,
    min_size: int = 1024,
    gradient_average: bool = True,
    gradient_predivide_factor: Optional[float] = None,
):
    """Sync a gradient pytree over ``axis_name`` (call inside
    ``shard_map``) with the engine's wire/chunking knobs; a drop-in for
    :func:`apex_tpu.parallel.all_reduce_gradients` (same averaging /
    predivide semantics).

    ``wire="f32"`` with no chunking request is the exact per-leaf psum.
    Otherwise every leaf of >= ``min_size`` elements joins ONE flat
    bucket synced as a chunked reduce-scatter + all-gather (2K
    collectives total, independent of leaf count); leaves under
    ``min_size`` — biases, LN scales: latency-dominated and the most
    noise-sensitive — always ride the exact psum.
    """
    check_wire(wire)
    world = jax.lax.axis_size(axis_name)
    post = 1.0
    if gradient_average:
        post = (
            world / gradient_predivide_factor
            if gradient_predivide_factor is not None
            else world
        )

    def pre(g):
        # a numerical no-op inside the quantized path (constant scaling
        # commutes with max/127 quantization), but it keeps
        # half-precision INPUT grads from overflowing before the cast,
        # exactly as in all_reduce_gradients
        if gradient_predivide_factor is not None:
            return g / gradient_predivide_factor
        return g

    leaves, treedef = jax.tree_util.tree_flatten(grads)
    big = [
        i for i, l in enumerate(leaves)
        if l.size >= min_size and l.size > 0 and world > 1
    ]
    resolved = None
    if big:
        nbytes = int(
            sum(leaves[i].size for i in big)
            * wire_bytes_per_element(wire, block)
        )
        resolved = resolve_chunks(nbytes, chunks)
    bucketed = bool(big) and (
        wire != "f32" or (chunks_requested(chunks) and resolved > 1)
    )
    big_set = set(big) if bucketed else set()
    bucket_elems = sum(leaves[i].size for i in big_set)
    psum_bytes = sum(
        leaves[i].size * 4 for i in range(len(leaves)) if i not in big_set
    )
    _publish_stats(
        "comm/sync", wire=wire, world=world,
        bucket_elements=int(bucket_elems),
        chunks=int(resolved or 1),
        psum_leaves=len(leaves) - len(big_set),
        wire_bytes=int(
            bucket_elems * wire_bytes_per_element(wire, block) + psum_bytes
        ),
    )
    synced_by_idx = {}
    out = []
    with jax.named_scope(f"comm_sync_{wire}"):
        if bucketed:
            flat = jnp.concatenate(
                [pre(leaves[i]).reshape(-1).astype(jnp.float32)
                 for i in big]
            )
            n = flat.shape[0]
            padded = n + (-n) % world
            if padded != n:
                flat = jnp.concatenate(
                    [flat, jnp.zeros((padded - n,), jnp.float32)]
                )
            my_shard = reduce_scatter_flat(
                flat, axis_name, wire=wire, chunks=resolved, block=block
            )
            synced = all_gather_flat(
                my_shard, axis_name, wire=wire, chunks=resolved, block=block
            )[:n] / post
            offs = 0
            for i in big:
                sz = leaves[i].size
                synced_by_idx[i] = (
                    synced[offs:offs + sz]
                    .reshape(leaves[i].shape)
                    .astype(leaves[i].dtype)
                )
                offs += sz
        for i, l in enumerate(leaves):
            if i in synced_by_idx:
                out.append(synced_by_idx[i])
            else:
                out.append(jax.lax.psum(pre(l), axis_name) / post)
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# declared collective plans (the analysis reshard pass's intent)
#
# Each sync path above PROMISES a collective structure; these helpers
# write that promise down as the per-mesh-axis plan schema of
# apex_tpu.analysis.sharding.reshard_pass, mirroring the exact routing
# decisions (bucketing, chunk bounds, wire payloads) the traced code
# makes — so "the compiled step contains only the collectives the
# engine planned" is machine-checkable, not a docstring.
# ---------------------------------------------------------------------------


def wire_payload_bytes(n: int, wire: str, block: int = DEFAULT_BLOCK) -> int:
    """EXACT encoded payload bytes of ``n`` f32 elements under
    ``wire`` — including the int8 path's block zero-pad and packed f32
    scales (:func:`pack_int8`), so plan bounds match the compiled
    payload shapes byte-for-byte."""
    check_wire(wire)
    if wire == "f32":
        return n * 4
    if wire == "bf16":
        return n * 2
    n_pad = _padded_len(n, block)
    return n_pad + 4 * (n_pad // block)


def _wire_dtypes(wire: str):
    return {"f32": ["f32"], "bf16": ["bf16"], "int8": ["s8"]}[wire]


def _bound(estimate: int, slack: int = 1024):
    """[0, hi] byte bounds around an exact-model estimate: generous
    enough for layout padding / a stray scalar riding along, tight
    enough that a doubled sync or an un-encoded payload busts it."""
    return [0, int(estimate + max(slack, estimate // 4))]


def sync_plan(
    grads: Any,
    world: int,
    axis_name: str = ps.DATA_PARALLEL_AXIS,
    *,
    wire: str = "f32",
    chunks: Optional[int] = None,
    block: int = DEFAULT_BLOCK,
    min_size: int = 1024,
    extra_allreduce_bytes: int = 64,
) -> list:
    """The collective plan :func:`sync_gradients` promises for this
    gradient tree — a list of ``{"kind", "axis", "count", "bytes",
    "dtypes"}`` entries (``count`` None where XLA's combiner may
    legally merge).  ``extra_allreduce_bytes`` widens the exact-psum
    entry for the scalar all-reduces that ride the same axis in a real
    step (loss pmean, guard flags).

    Mirrors the routing in :func:`sync_gradients` exactly: same
    bucketing predicate, same :func:`resolve_chunks` /
    ``_chunk_bounds`` arithmetic, same wire payload model — change one
    without the other and the reshard pass fails, which is the point.
    """
    check_wire(wire)
    leaves = jax.tree_util.tree_leaves(grads)
    sizes = [int(getattr(l, "size", l)) for l in leaves]
    if world <= 1:
        return []
    big = [s for s in sizes if s >= min_size and s > 0]
    resolved = None
    if big:
        nbytes = int(sum(big) * wire_bytes_per_element(wire, block))
        resolved = resolve_chunks(nbytes, chunks)
    bucketed = bool(big) and (
        wire != "f32" or (chunks_requested(chunks) and resolved > 1)
    )
    entries = []
    psum_elems = sum(
        s for s in sizes if not (bucketed and s >= min_size and s > 0)
    )
    if bucketed:
        n = sum(big)
        padded = n + (-n) % world
        shard = padded // world
        align = 1 if wire == "f32" else block
        k = min(resolved, shard)
        bounds = _chunk_bounds(shard, k, align)
        count = len(bounds)
        if wire == "f32":
            # psum_scatter prints the SHARD as its result shape
            entries.append({
                "kind": "reduce-scatter", "axis": axis_name,
                "count": count, "bytes": _bound(shard * 4),
                "dtypes": _wire_dtypes(wire),
            })
        else:
            # encoded (world, chunk) payloads through all_to_all
            a2a = sum(
                world * wire_payload_bytes(hi - lo, wire, block)
                for lo, hi in bounds
            )
            entries.append({
                "kind": "all-to-all", "axis": axis_name,
                "count": count, "bytes": _bound(a2a),
                "dtypes": _wire_dtypes(wire),
            })
        ag = sum(
            world * wire_payload_bytes(hi - lo, wire, block)
            for lo, hi in bounds
        )
        entries.append({
            "kind": "all-gather", "axis": axis_name,
            "count": count, "bytes": _bound(ag),
            "dtypes": _wire_dtypes(wire),
        })
    if psum_elems or extra_allreduce_bytes:
        entries.append({
            "kind": "all-reduce", "axis": axis_name,
            "count": None,
            "bytes": _bound(psum_elems * 4 + extra_allreduce_bytes),
            "dtypes": ["f32"],
        })
    return entries


def zero_plan(
    n_elements: int,
    world: int,
    axis_name: str = ps.DATA_PARALLEL_AXIS,
    *,
    wire: str = "f32",
    param_wire: Optional[str] = None,
    chunks: Optional[int] = None,
    block: int = DEFAULT_BLOCK,
    extra_allreduce_bytes: int = 256,
) -> list:
    """The plan a ZeRO step (:meth:`_DistributedFusedBase
    .update_inside_shard_map`) promises for ``n_elements`` flat f32
    params: a chunked reduce-scatter of grads at ``wire``, a chunked
    all-gather of updated shards at ``param_wire or wire``, plus the
    small all-reduces of the loss pmean / LAMB per-tensor norms."""
    check_wire(wire)
    if world <= 1:
        return []
    padded = n_elements + (-n_elements) % world
    shard = padded // world
    entries = []

    def _one(w, gather: bool):
        align = 1 if w == "f32" else block
        # mirror reduce_scatter_flat/all_gather_flat's resolve inputs:
        # the scatter sizes the full padded buffer, the gather its
        # world x shard result
        n_for_chunks = world * shard if gather else padded
        k = min(resolve_chunks(
            int(n_for_chunks * wire_bytes_per_element(w, block)), chunks,
        ), shard)
        bounds = _chunk_bounds(shard, k, align)
        count = len(bounds)
        if gather or w != "f32":
            payload = sum(
                world * wire_payload_bytes(hi - lo, w, block)
                for lo, hi in bounds
            )
            kind = "all-gather" if gather else "all-to-all"
            return {
                "kind": kind, "axis": axis_name, "count": count,
                "bytes": _bound(payload), "dtypes": _wire_dtypes(w),
            }
        return {
            "kind": "reduce-scatter", "axis": axis_name, "count": count,
            "bytes": _bound(shard * 4), "dtypes": _wire_dtypes(w),
        }

    entries.append(_one(wire, gather=False))
    entries.append(_one(param_wire or wire, gather=True))
    entries.append({
        "kind": "all-reduce", "axis": axis_name, "count": None,
        "bytes": _bound(extra_allreduce_bytes), "dtypes": ["f32"],
    })
    return entries


# ---------------------------------------------------------------------------
# verification hooks: collectives + wire bytes out of compiled HLO
#
# The HLO text parser itself lives with the static-analysis subsystem
# (apex_tpu/analysis/hlo.py) — ONE implementation shared by these
# hooks, the analysis passes' collective-consistency rule, and
# tools/comm_structure.py.  The names below remain this module's public
# API (tests/test_comm.py and downstream callers import them here).
# ---------------------------------------------------------------------------

from apex_tpu.analysis.hlo import (  # noqa: E402
    collective_summary,
    ring_wire_bytes,
)


def compiled_collectives(fn, *args, **kwargs) -> dict:
    """:func:`collective_summary` of a jitted callable compiled on
    ``args`` — the hook regression tests assert on.  ``fn`` must carry
    ``.lower`` (i.e. be ``jax.jit``-wrapped)."""
    hlo = fn.lower(*args, **kwargs).compile().as_text()
    return collective_summary(hlo)


def publish_collective_summary(
    summary: dict, world: Optional[int] = None, prefix: str = "comm/hlo"
) -> None:
    """Gauge a :func:`collective_summary` onto the observability board.

    Per-kind ``{prefix}/<kind>_count`` / ``{prefix}/<kind>_bytes``
    gauges plus — when ``world`` is given — the ring-model
    ``{prefix}/ring_wire_bytes``, so a compiled program's MEASURED
    collective structure rides the same telemetry stream as the
    trace-time plan (``docs/observability.md``).
    """
    stats = {}
    for kind, rec in summary.items():
        key = kind.replace("-", "_")
        stats[f"{key}_count"] = rec["count"]
        stats[f"{key}_bytes"] = rec["bytes"]
    if world is not None:
        stats["ring_wire_bytes"] = ring_wire_bytes(summary, world)
    _publish_stats(prefix, **stats)
