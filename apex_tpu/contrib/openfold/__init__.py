"""OpenFold kernels + DAP — ≙ ``apex/contrib/openfold_triton``
(``mha.py``, ``layer_norm.py``, ``dap.py``: Triton kernels + dynamic
axial parallelism for AlphaFold2-style training).

The reference's Triton kernels map onto pieces this framework already has
(re-exported below so OpenFold-shaped code finds them in one place).
DAP — sharding the pair representation's two axial dims across devices
and swapping which axis is sharded between row- and column-attention —
maps to ``all_to_all`` over a mesh axis (the same collective Ulysses
uses), exposed with the reference surface's names (``scatter`` /
``gather`` / ``row_to_col`` / ``col_to_row``) plus
:class:`DAPAxialBlock`, a pair-stack block (row attention on the
row-sharded layout, transition, column attention on the col-sharded
layout, transition back, MLP) built on those transitions.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.ops.attention import flash_attention as mha  # noqa: F401
from apex_tpu.ops.layer_norm import (  # noqa: F401
    fused_layer_norm_affine as layer_norm,
)

__all__ = [
    "mha",
    "layer_norm",
    "scatter",
    "gather",
    "row_to_col",
    "col_to_row",
    "scatter_rows_gather_cols",
    "scatter_cols_gather_rows",
    "DAPAxialBlock",
    # evoformer pair-stack modules (openfold_triton's model-side surface)
    "GatedAttention",
    "TriangleAttention",
    "TriangleMultiplicativeUpdate",
    "PairTransition",
    "EvoformerPairBlock",
    "MSARowAttentionWithPairBias",
    "MSAColumnAttention",
    "OuterProductMean",
    "EvoformerBlock",
]


def scatter_rows_gather_cols(x, axis_name: str, row_axis: int = -3, col_axis: int = -2):
    """DAP transition: (rows sharded) → (cols sharded).

    ≙ dap.py's row↔col resharding between triangular/axial attention
    blocks: one all-to-all instead of gather+slice.
    """
    return jax.lax.all_to_all(
        x, axis_name, split_axis=col_axis % x.ndim,
        concat_axis=row_axis % x.ndim, tiled=True,
    )


def scatter_cols_gather_rows(x, axis_name: str, row_axis: int = -3, col_axis: int = -2):
    """Inverse DAP transition: (cols sharded) → (rows sharded)."""
    return jax.lax.all_to_all(
        x, axis_name, split_axis=row_axis % x.ndim,
        concat_axis=col_axis % x.ndim, tiled=True,
    )


# Reference-surface names (dap.py :: row_to_col / col_to_row / scatter /
# gather).  Directions: "row-sharded" = the R axial dim is split over the
# dap axis (each rank holds full columns of its rows).
row_to_col = scatter_rows_gather_cols
col_to_row = scatter_cols_gather_rows


def scatter(x, axis_name: str, dim: int):
    """≙ dap.py :: scatter — enter the DAP region: keep this rank's slice
    of ``dim`` (use on a replicated tensor inside shard_map)."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    dim = dim % x.ndim
    if x.shape[dim] % n:
        raise ValueError(
            f"DAP scatter: dim {dim} (size {x.shape[dim]}) is not "
            f"divisible by the {axis_name!r} axis size {n} — trailing "
            "rows would silently belong to no rank; pad the axial dim"
        )
    per = x.shape[dim] // n
    return jax.lax.dynamic_slice_in_dim(x, idx * per, per, axis=dim)


def gather(x, axis_name: str, dim: int):
    """≙ dap.py :: gather — leave the DAP region: all-gather ``dim``."""
    return jax.lax.all_gather(x, axis_name, axis=dim % x.ndim, tiled=True)


class DAPAxialBlock(nn.Module):
    """One pair-stack block under dynamic axial parallelism.

    ≙ the openfold evoformer pair-block pattern the reference's dap.py
    serves: row-wise self-attention while ROWS are sharded (each rank
    attends over its rows' full columns), ``row_to_col``, column-wise
    self-attention while COLS are sharded, ``col_to_row``, then a
    per-position transition MLP.  Pre-LN residual form throughout, all
    on the framework's fused LN + flash attention.

    Input/output: ``x`` of shape (R/dap, C, D) — row-sharded — when
    ``axis_name`` is set; (R, C, D) unsharded when ``axis_name=None``
    (the golden path; the test holds sharded == unsharded).
    """

    dim: int
    heads: int
    axis_name: Optional[str] = None
    mlp_ratio: int = 4

    def _attend(self, x, prefix):
        """Self-attention over the SECOND-to-last axis... x (B, S, D):
        batch B = the sharded axial dim, sequence S = the attended dim."""
        b, s, d = x.shape
        dh = d // self.heads
        qkv = nn.Dense(3 * d, use_bias=False, name=f"{prefix}_qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads_first(t):
            return t.reshape(b, s, self.heads, dh).transpose(0, 2, 1, 3)

        o = mha(heads_first(q), heads_first(k), heads_first(v))
        o = o.transpose(0, 2, 1, 3).reshape(b, s, d)
        return nn.Dense(d, use_bias=True, name=f"{prefix}_out")(o)

    def _ln(self, x, name):
        g = self.param(name + "_scale", nn.initializers.ones, (self.dim,))
        b = self.param(name + "_bias", nn.initializers.zeros, (self.dim,))
        return layer_norm(x, g, b, (self.dim,))

    @nn.compact
    def __call__(self, x):
        # --- row attention: rows sharded, attend along columns ---------
        h = self._ln(x, "ln_row")
        x = x + self._attend(h, "row")
        # --- transition to col-sharded ----------------------------------
        if self.axis_name is not None:
            x = row_to_col(x, self.axis_name)
        # --- col attention: cols sharded, attend along rows ------------
        h = self._ln(x, "ln_col")
        h = h.transpose(1, 0, 2)          # (C_loc, R, D): attend over R
        h = self._attend(h, "col")
        x = x + h.transpose(1, 0, 2)
        if self.axis_name is not None:
            x = col_to_row(x, self.axis_name)
        # --- per-position transition MLP --------------------------------
        h = self._ln(x, "ln_mlp")
        h = nn.Dense(self.mlp_ratio * self.dim, name="mlp_up")(h)
        h = jax.nn.gelu(h)
        return x + nn.Dense(self.dim, name="mlp_down")(h)


from apex_tpu.contrib.openfold.evoformer import (  # noqa: E402,F401
    EvoformerBlock,
    EvoformerPairBlock,
    GatedAttention,
    MSAColumnAttention,
    MSARowAttentionWithPairBias,
    OuterProductMean,
    PairTransition,
    TriangleAttention,
    TriangleMultiplicativeUpdate,
)
