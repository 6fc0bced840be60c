"""Evoformer pair-stack modules under dynamic axial parallelism.

Deepens the ``openfold`` contrib surface past re-exports (VERDICT r2
item 10 follow-up): the reference's ``apex/contrib/openfold_triton``
ships OpenFold-specific fused kernels (``mha.py``: gated attention with
pair bias; fused LayerNorm; the DAP helpers in ``dap.py`` that shard the
pair representation's axial dims).  The TPU realization keeps the same
model math on this framework's fused primitives:

- gated, pair-biased attention runs on the flash kernel with the
  *trainable-bias* backward (``flash_attention(..., bias_grad=True)``,
  the dedicated dbias kernel) instead of a bespoke Triton kernel;
- the triangle multiplicative updates become two einsum contractions
  whose DAP forms are the two canonical mesh collectives: *outgoing*
  all-gathers one operand, *incoming* reduce-scatters the contraction —
  both ride the same axis the ``dap.py`` transitions use;
- LayerNorm is the tuned Pallas kernel via
  :func:`apex_tpu.ops.layer_norm.fused_layer_norm_affine`.

Layout convention matches :mod:`apex_tpu.contrib.openfold`: under DAP the
leading axial dim is sharded over ``axis_name`` (rank r holds rows
``[r*per, (r+1)*per)``, the ``scatter``/``all_gather(tiled=True)``
order); ``axis_name=None`` runs the identical unsharded math — the
golden path the equivalence tests hold sharded runs against.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.ops.attention import flash_attention
from apex_tpu.ops.layer_norm import fused_layer_norm_affine

__all__ = [
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


def _layer_norm(mod: nn.Module, x, name: str):
    d = x.shape[-1]
    g = mod.param(name + "_scale", nn.initializers.ones, (d,))
    b = mod.param(name + "_bias", nn.initializers.zeros, (d,))
    return fused_layer_norm_affine(x, g, b, (d,))


def _pair_bias(mod: nn.Module, z_ln, heads: int, axis_name: Optional[str],
               n_res: int, name: str = "tri_bias"):
    """Per-head attention bias projected from the (LN'd) pair rep.

    Projects on the LOCAL rows first and all-gathers the small
    (N/dap, N, heads) result (heads < D: the gather moves and the ranks
    redundantly compute D/heads-fold less than gathering the pair itself
    for an identical pointwise result).  Returns (1, H, N, N) — one bias
    group shared by every batch row, trainable through the flash path's
    dbias kernel (the grouped-G reduction sums the batch dim).
    """
    tri = nn.Dense(heads, use_bias=False, name=name)(z_ln)
    if axis_name is not None:
        tri = jax.lax.all_gather(tri, axis_name, axis=0, tiled=True)
    if tri.shape[0] != n_res or tri.shape[1] != n_res:
        raise ValueError(
            f"pair bias needs a square pair representation matching the "
            f"attended dim {n_res}; got {tri.shape[:2]}"
        )
    return tri.transpose(2, 0, 1)[None]


class GatedAttention(nn.Module):
    """OpenFold-style attention: no-bias q/k/v projections, additive pair
    bias, sigmoid gating on the attended values, output projection
    (≙ openfold_triton ``mha.py``'s fused attention surface).

    Input ``x`` (B, S, D); optional ``bias`` broadcastable to
    (B, H, S, S).  When ``bias_grad`` the flash path backprops into the
    bias with the dedicated dbias kernel.  The gate projection starts at
    sigmoid(1) (zero kernel, unit bias) and the output projection at
    zero — the reference models' residual-stability init.
    """

    heads: int
    bias_grad: bool = True

    @nn.compact
    def __call__(self, x, bias=None):
        b, s, d = x.shape
        h = self.heads
        dh = d // h
        if d % h:
            raise ValueError(f"dim {d} not divisible by heads {h}")

        def split_heads(t):
            return t.reshape(b, s, h, dh).transpose(0, 2, 1, 3)

        q = split_heads(nn.Dense(d, use_bias=False, name="q")(x))
        k = split_heads(nn.Dense(d, use_bias=False, name="k")(x))
        v = split_heads(nn.Dense(d, use_bias=False, name="v")(x))
        o = flash_attention(q, k, v, bias, bias_grad=self.bias_grad)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, d)
        gate = nn.Dense(
            d, name="gate", kernel_init=nn.initializers.zeros,
            bias_init=nn.initializers.ones,
        )(x)
        o = jax.nn.sigmoid(gate) * o
        return nn.Dense(
            d, name="out", kernel_init=nn.initializers.zeros
        )(o)


class TriangleAttention(nn.Module):
    """Triangle self-attention around the starting node on the module's
    input layout: batch = leading axial dim, attention along the second,
    bias ``b[h, j, k]`` projected from the pair itself and shared across
    the batch dim (the triangle inequality edge, AF2 suppl. Algs 13/14).

    The *ending-node* variant is this module applied to the transposed
    pair — :class:`EvoformerPairBlock` wires that (and under DAP routes
    it through the ``row_to_col`` transition so the transposed frame is
    again leading-dim sharded).

    Under DAP (``axis_name`` set) the input is (N/dap, N, D): attention
    batches over local rows directly, but the bias needs the full pair —
    so the bias is projected on the LOCAL rows first and the (N/dap, N,
    heads) result all-gathered (heads < D, and the projection FLOPs split
    across ranks; gathering the pair itself then projecting would move
    and compute D/heads-fold more for an identical pointwise result).
    """

    heads: int
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, z):
        _, n_cols, _ = z.shape
        z_ln = _layer_norm(self, z, "ln")
        tri_bias = _pair_bias(
            self, z_ln, self.heads, self.axis_name, n_cols
        )
        return GatedAttention(heads=self.heads, name="attn")(
            z_ln, bias=tri_bias
        )


class TriangleMultiplicativeUpdate(nn.Module):
    """Triangle multiplicative update (AF2 suppl. Algs 11/12).

    ``outgoing``: out[i,j] = Σ_k a[i,k]·b[j,k]; ``incoming``:
    out[i,j] = Σ_k a[k,i]·b[k,j] — with a, b gated projections of the
    LN'd pair and a final gated, LN'd output projection.

    DAP forms (leading dim sharded) are pure mesh collectives:

    - outgoing contracts each local row block against *all* rows of b →
      ``all_gather(b)`` then einsum; output rows stay local.
    - incoming contracts over the *sharded* dim k → local einsum gives a
      partial (N, N) sum, ``psum_scatter`` both reduces it and re-shards
      the rows in one collective (the reduce-scatter dual of outgoing).
    """

    mode: str  # "outgoing" | "incoming"
    hidden: Optional[int] = None
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, z):
        if self.mode not in ("outgoing", "incoming"):
            raise ValueError(f"unknown mode {self.mode!r}")
        d = z.shape[-1]
        c = self.hidden or d
        z_ln = _layer_norm(self, z, "ln_in")

        def gated_proj(name):
            p = nn.Dense(c, name=name)(z_ln)
            g = nn.Dense(
                c, name=name + "_gate", kernel_init=nn.initializers.zeros,
                bias_init=nn.initializers.ones,
            )(z_ln)
            return jax.nn.sigmoid(g) * p

        a = gated_proj("a")
        b = gated_proj("b")
        if self.mode == "outgoing":
            if self.axis_name is not None:
                b = jax.lax.all_gather(b, self.axis_name, axis=0, tiled=True)
            x = jnp.einsum("ikc,jkc->ijc", a, b)
        else:
            x = jnp.einsum("kic,kjc->ijc", a, b)
            if self.axis_name is not None:
                x = jax.lax.psum_scatter(
                    x, self.axis_name, scatter_dimension=0, tiled=True
                )
        x = _layer_norm(self, x, "ln_out")
        x = nn.Dense(d, name="out", kernel_init=nn.initializers.zeros)(x)
        gate = nn.Dense(
            d, name="gate", kernel_init=nn.initializers.zeros,
            bias_init=nn.initializers.ones,
        )(z_ln)
        return jax.nn.sigmoid(gate) * x


class PairTransition(nn.Module):
    """Per-position transition MLP (LN → expand → relu → project)."""

    ratio: int = 4

    @nn.compact
    def __call__(self, z):
        d = z.shape[-1]
        h = _layer_norm(self, z, "ln")
        h = nn.Dense(self.ratio * d, name="up")(h)
        h = jax.nn.relu(h)
        return nn.Dense(d, name="down", kernel_init=nn.initializers.zeros)(h)


class EvoformerPairBlock(nn.Module):
    """One evoformer pair-stack block under DAP.

    Residual sequence on the square pair z (N, N, D) — triangle
    multiplicative outgoing, incoming, triangle attention around the
    starting then ending node, pair transition — the openfold pair stack
    the reference's dap.py shards.  Under DAP the block stays row-sharded
    for the multiplicative updates and starting-node attention, crosses
    to the column-sharded layout (one ``row_to_col`` all-to-all) for the
    ending-node attention in its transposed frame, and crosses back.
    """

    dim: int
    heads: int
    axis_name: Optional[str] = None
    mlp_ratio: int = 4

    @nn.compact
    def __call__(self, z):
        from apex_tpu.contrib.openfold import col_to_row, row_to_col

        if z.shape[-1] != self.dim:
            raise ValueError(
                f"pair channel dim {z.shape[-1]} != configured dim {self.dim}"
            )
        ax = self.axis_name
        z = z + TriangleMultiplicativeUpdate(
            mode="outgoing", axis_name=ax, name="tri_mul_out"
        )(z)
        z = z + TriangleMultiplicativeUpdate(
            mode="incoming", axis_name=ax, name="tri_mul_in"
        )(z)
        z = z + TriangleAttention(
            heads=self.heads, axis_name=ax, name="tri_att_start"
        )(z)
        if ax is not None:
            zc = row_to_col(z, ax)
        else:
            zc = z
        zt = zc.transpose(1, 0, 2)
        zt = zt + TriangleAttention(
            heads=self.heads, axis_name=ax, name="tri_att_end"
        )(zt)
        zc = zt.transpose(1, 0, 2)
        z = col_to_row(zc, ax) if ax is not None else zc
        return z + PairTransition(ratio=self.mlp_ratio, name="transition")(z)


class MSARowAttentionWithPairBias(nn.Module):
    """MSA row-wise gated self-attention, biased by the pair rep (AF2
    suppl. Alg 7): each MSA row attends across residues with a per-head
    additive bias projected from LN(z), shared by every row.

    DAP layout: MSA (S/dap, R, c_m) sharded over its row (sequence) dim,
    pair (R/dap, R, c_z) sharded over its leading residue dim.  The bias
    is projected from the LOCAL pair rows and all-gathered as the small
    (R, R, heads) tensor — the same local-project-then-gather shape
    trick :class:`TriangleAttention` uses.
    """

    heads: int
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, m, z):
        r = m.shape[1]
        m_ln = _layer_norm(self, m, "ln_m")
        z_ln = _layer_norm(self, z, "ln_z")
        bias = _pair_bias(
            self, z_ln, self.heads, self.axis_name, r, name="pair_bias"
        )
        return GatedAttention(heads=self.heads, name="attn")(m_ln, bias=bias)


class MSAColumnAttention(nn.Module):
    """MSA column-wise gated self-attention (AF2 suppl. Alg 8): per
    residue, attend over the MSA's sequence dim.  Operates on the
    COLUMN-major layout (R_loc, S, c_m) — :class:`EvoformerBlock` crosses
    into it with the same ``row_to_col`` all-to-all the pair stack uses.
    """

    heads: int

    @nn.compact
    def __call__(self, m_col):
        m_ln = _layer_norm(self, m_col, "ln")
        return GatedAttention(heads=self.heads, name="attn")(m_ln)


class OuterProductMean(nn.Module):
    """Pair update from the MSA (AF2 suppl. Alg 10):
    o[i,j] = Linear(flatten(mean_s a[s,i] ⊗ b[s,j])).

    DAP form: the mean contracts over the SHARDED MSA row dim, so each
    rank contracts its local rows and one ``psum_scatter`` both finishes
    the sum and lands the output pair rows on their owning ranks — the
    same reduce-scatter dual the incoming triangle update uses.  The
    mean's divisor is the GLOBAL row count, recovered as
    local · ``axis_size`` (shards are equal-sized by the DAP layout
    contract) — not the local shard size.
    """

    hidden: int = 8
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, m, out_dim: int):
        s_total = m.shape[0] * (
            jax.lax.axis_size(self.axis_name)
            if self.axis_name is not None
            else 1
        )
        m_ln = _layer_norm(self, m, "ln")
        a = nn.Dense(self.hidden, name="a")(m_ln)
        b = nn.Dense(self.hidden, name="b")(m_ln)
        o = jnp.einsum("sic,sjd->ijcd", a, b) / s_total
        if self.axis_name is not None:
            o = jax.lax.psum_scatter(
                o, self.axis_name, scatter_dimension=0, tiled=True
            )
        o = o.reshape(o.shape[0], o.shape[1], self.hidden * self.hidden)
        return nn.Dense(
            out_dim, name="out", kernel_init=nn.initializers.zeros
        )(o)


class EvoformerBlock(nn.Module):
    """One full evoformer block (AF2 suppl. Alg 6): the MSA stack (row
    attention with pair bias, column attention, transition), the
    outer-product-mean MSA→pair communication, then the pair stack
    (:class:`EvoformerPairBlock`'s sequence).  This is the model-level
    structure ALL of the reference's openfold_triton kernels serve; under
    DAP both representations stay sharded on their leading dim and every
    cross-layout move is one collective.

    ``msa_dim``/``pair_dim`` are the channel widths.
    """

    msa_dim: int
    pair_dim: int
    heads: int
    axis_name: Optional[str] = None
    mlp_ratio: int = 4
    opm_hidden: int = 8

    @nn.compact
    def __call__(self, m, z):
        from apex_tpu.contrib.openfold import col_to_row, row_to_col

        if m.shape[-1] != self.msa_dim:
            raise ValueError(
                f"MSA channel dim {m.shape[-1]} != configured {self.msa_dim}"
            )
        if z.shape[-1] != self.pair_dim:
            raise ValueError(
                f"pair channel dim {z.shape[-1]} != configured {self.pair_dim}"
            )
        ax = self.axis_name
        # --- MSA stack -------------------------------------------------
        m = m + MSARowAttentionWithPairBias(
            heads=self.heads, axis_name=ax, name="msa_row_att"
        )(m, z)
        mc = row_to_col(m, ax) if ax is not None else m
        mt = mc.transpose(1, 0, 2)  # (R_loc, S, c_m)
        mt = mt + MSAColumnAttention(heads=self.heads, name="msa_col_att")(mt)
        mc = mt.transpose(1, 0, 2)
        m = col_to_row(mc, ax) if ax is not None else mc
        m = m + PairTransition(ratio=self.mlp_ratio, name="msa_transition")(m)
        # --- MSA -> pair communication --------------------------------
        z = z + OuterProductMean(
            hidden=self.opm_hidden, axis_name=ax,
            name="outer_product_mean",
        )(m, self.pair_dim)
        # --- pair stack ------------------------------------------------
        z = EvoformerPairBlock(
            dim=self.pair_dim, heads=self.heads, axis_name=ax,
            mlp_ratio=self.mlp_ratio, name="pair_block",
        )(z)
        return m, z
