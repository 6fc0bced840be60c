"""Pallas TPU grouped expert matmul — SwiGLU experts over rows sorted by
expert.

The rows of ``x`` are the tokens routed to the experts this chip holds,
sorted by expert, each expert's group padded to whole tiles of ``tile``
rows (:func:`apex_tpu.transformer.moe.dropless_moe` lays them out).  The
grid walks the tiles; a scalar-prefetched table names each tile's expert,
and the index maps fetch that expert's three matrices whole — consecutive
tiles of one expert re-use the resident blocks, and an expert no token was
routed to is never read.  The row count is static (the worst case); the
tiles past the last live one are skipped, their index maps parked on the
last live tile's blocks so that they move nothing.

A tile is 16 rows against ``(H, I)`` matrices: the step is bound by
streaming the expert's weights (11.8 MB at H 2560, I 768), which is the
layer's roofline at decode — the bytes of the experts touched.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._dispatch import pallas_interpret

__all__ = ["moe_grouped_fwd"]

#: two buffers of one expert's three matrices, and room to work
_VMEM_LIMIT = 56 * 1024 * 1024


def _kernel(te_ref, live_ref, x_ref, g_ref, u_ref, d_ref, o_ref):
    del te_ref

    @pl.when(pl.program_id(0) < live_ref[0])
    def _tile():
        x = x_ref[...]
        a = jnp.dot(x, g_ref[0], preferred_element_type=jnp.float32)
        b = jnp.dot(x, u_ref[0], preferred_element_type=jnp.float32)
        h = (a * jax.nn.sigmoid(a) * b).astype(x.dtype)
        o_ref[...] = jnp.dot(
            h, d_ref[0], preferred_element_type=jnp.float32
        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile",))
def moe_grouped_fwd(x, tile_expert, live_tiles, gate, up, down, *, tile):
    """``x`` ``(M, H)`` rows in expert order, ``tile_expert`` ``(M /
    tile,)`` int32 each tile's expert (a skipped tile repeats the last live
    one's), ``live_tiles`` ``(1,)`` int32, ``gate, up`` ``(E, H, I)``,
    ``down`` ``(E, I, H)``.  Returns ``(M, H)`` in ``x.dtype``: row ``r`` is
    ``SwiGLU_{expert of r's tile}(x[r])``; rows of skipped tiles are not
    written."""
    m, h = x.shape
    _, _, inter = gate.shape

    def row_block(t, te, live):
        return (jnp.maximum(jnp.minimum(t, live[0] - 1), 0), 0)

    def expert_block(t, te, live):
        return (te[t], 0, 0)

    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(m // tile,),
            in_specs=[
                pl.BlockSpec((tile, h), row_block),
                pl.BlockSpec((1, h, inter), expert_block),
                pl.BlockSpec((1, h, inter), expert_block),
                pl.BlockSpec((1, inter, h), expert_block),
            ],
            out_specs=pl.BlockSpec((tile, h), row_block),
        ),
        out_shape=jax.ShapeDtypeStruct((m, h), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=pallas_interpret(),
        name="moe_grouped_fwd",
    )(tile_expert, live_tiles, x, gate, up, down)
