"""Tensor-parallel sharded layers.

≙ ``apex/transformer/tensor_parallel/layers.py`` ::
``VocabParallelEmbedding``, ``ColumnParallelLinear``, ``RowParallelLinear``
(+ ``LinearWithGradAccumulationAndAsyncCommunication``,
``set_tensor_model_parallel_attributes``, ``_initialize_affine_weight_*``).

Flax modules meant to run inside ``shard_map`` over the global mesh with
the ``tp`` axis bound.  Conventions and deltas from the reference:

- weights use the JAX layout ``(in_features, out_features)`` (the reference
  stores torch's ``(out, in)``);
- **reproducible-across-tp init**: like the reference's
  ``_initialize_affine_weight_cpu``, each shard is cut out of a
  *full-shape* initialization with the same key, so a checkpoint trained
  at tp=2 matches tp=4 initialization statistics exactly;
- ``gradient_accumulation_fusion`` (wgrad GEMM accumulating into an fp32
  main_grad — ``fused_weight_gradient_mlp_cuda``) is structural here:
  keep ``param_dtype=float32`` with bf16 ``dtype`` and the weight
  cotangent is produced directly in f32 by the backward matmul — no
  separate fused kernel exists or is needed.  The flag is accepted for
  API parity and validated, but changes nothing;
- ``no_async_tensor_model_parallel_allreduce`` — XLA overlaps the input-grad
  collective with the wgrad GEMM on its own (the hand-rolled async overlap
  in ``LinearWithGradAccumulationAndAsyncCommunication``); accepted, no-op.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu import parallel_state as ps
from apex_tpu.transformer.tensor_parallel.mappings import (
    copy_to_tensor_model_parallel_region,
    gather_from_sequence_parallel_region,
    gather_from_tensor_model_parallel_region,
    reduce_from_tensor_model_parallel_region,
    reduce_scatter_to_sequence_parallel_region,
    scatter_to_tensor_model_parallel_region,
)
from apex_tpu.transformer.tensor_parallel.utils import VocabUtility, divide

__all__ = [
    "VocabParallelEmbedding",
    "ColumnParallelLinear",
    "RowParallelLinear",
    "sharded_init",
]

_TP = ps.TENSOR_PARALLEL_AXIS


def _tp_world(axis_name: str) -> int:
    try:
        return jax.lax.axis_size(axis_name)
    except (NameError, KeyError):
        # Axis not bound.  Legitimate when running unsharded (no mesh, or
        # tp==1 outside shard_map); an error when the registry says the
        # model *is* tensor-parallel — then a typo'd/unbound axis would
        # silently compute dense math with full-shape params.
        if (
            ps.model_parallel_is_initialized()
            and axis_name == _TP
            and ps.get_tensor_model_parallel_world_size() > 1
        ):
            raise RuntimeError(
                f"tensor-parallel axis {axis_name!r} is not bound but the "
                f"mesh registry has tensor_model_parallel_size="
                f"{ps.get_tensor_model_parallel_world_size()}; run this "
                "layer inside jax.shard_map over the global mesh"
            )
        return 1


def sharded_init(
    base_init: Callable, full_shape: Tuple[int, ...], shard_axis: int,
    axis_name: str = _TP,
):
    """Initializer that cuts this rank's shard from a full-shape init.

    ≙ _initialize_affine_weight_cpu: "initialize the master weight, then
    split" — guarantees init statistics independent of the tp degree.
    """

    def init(key, shape, dtype=jnp.float32):
        world = _tp_world(axis_name)
        if world == 1:
            return base_init(key, full_shape, dtype)
        full = base_init(key, full_shape, dtype)
        rank = jax.lax.axis_index(axis_name)
        size = full_shape[shard_axis] // world
        if shape[shard_axis] != size:
            raise ValueError(
                f"local shard shape {shape} inconsistent with full shape "
                f"{full_shape} split {world}-way along axis {shard_axis}"
            )
        return jax.lax.dynamic_slice_in_dim(
            full, rank * size, size, axis=shard_axis
        )

    return init


class VocabParallelEmbedding(nn.Module):
    """Row-sharded (vocab-dim) embedding — ≙ VocabParallelEmbedding.

    Lookup masks out-of-range token ids, zeroes their rows, and all-reduces
    over tp (or reduce-scatters along the sequence dim when
    ``sequence_parallel_enabled`` — seq-first layout ``(s, ...)`` required
    then, as in Megatron).
    """

    num_embeddings: int
    embedding_dim: int
    init_method: Callable = nn.initializers.normal(stddev=0.02)
    sequence_parallel_enabled: bool = False
    param_dtype: Any = jnp.float32
    dtype: Optional[Any] = None
    axis_name: str = _TP

    @nn.compact
    def __call__(self, ids):
        world = _tp_world(self.axis_name)
        per = divide(self.num_embeddings, world)
        weight = self.param(
            "weight",
            sharded_init(
                self.init_method,
                (self.num_embeddings, self.embedding_dim),
                0,
                self.axis_name,
            ),
            (per, self.embedding_dim),
            self.param_dtype,
        )
        if world == 1:
            out = jnp.take(weight, ids, axis=0)
        else:
            rank = jax.lax.axis_index(self.axis_name)
            start, end = VocabUtility.vocab_range_from_per_partition_vocab_size(
                per, rank, world
            )
            in_range = (ids >= start) & (ids < end)
            local_ids = jnp.clip(ids - start, 0, per - 1)
            out = jnp.take(weight, local_ids, axis=0)
            out = jnp.where(in_range[..., None], out, 0.0)
            if self.sequence_parallel_enabled:
                out = reduce_scatter_to_sequence_parallel_region(
                    out, self.axis_name
                )
            else:
                out = reduce_from_tensor_model_parallel_region(
                    out, self.axis_name
                )
        if self.dtype is not None:
            out = out.astype(self.dtype)
        return out


class ColumnParallelLinear(nn.Module):
    """Y = XW + b with W column-sharded (output dim) — ≙ ColumnParallelLinear.

    fwd: SP ⇒ all-gather input along seq; else identity-with-psum-backward.
    ``gather_output`` reassembles the full output (all-gather over tp).
    ``skip_bias_add`` returns (output, bias) for downstream fusion.
    """

    input_size: int
    output_size: int
    use_bias: bool = True
    gather_output: bool = False
    sequence_parallel_enabled: bool = False
    skip_bias_add: bool = False
    init_method: Callable = nn.initializers.lecun_normal()
    bias_init: Callable = nn.initializers.zeros
    gradient_accumulation_fusion: bool = False  # structural no-op (see module doc)
    no_async_tensor_model_parallel_allreduce: bool = False  # no-op
    param_dtype: Any = jnp.float32
    dtype: Optional[Any] = None
    axis_name: str = _TP

    @nn.compact
    def __call__(self, x):
        if self.gather_output and self.sequence_parallel_enabled:
            raise ValueError(
                "gather_output and sequence_parallel_enabled are mutually "
                "exclusive (reference asserts the same)"
            )
        world = _tp_world(self.axis_name)
        out_per = divide(self.output_size, world)
        weight = self.param(
            "weight",
            sharded_init(
                self.init_method,
                (self.input_size, self.output_size),
                1,
                self.axis_name,
            ),
            (self.input_size, out_per),
            self.param_dtype,
        )
        bias = (
            self.param("bias", self.bias_init, (out_per,), self.param_dtype)
            if self.use_bias
            else None
        )
        if world > 1:
            if self.sequence_parallel_enabled:
                x = gather_from_sequence_parallel_region(x, self.axis_name)
            else:
                x = copy_to_tensor_model_parallel_region(x, self.axis_name)
        cdt = self.dtype or x.dtype
        y = jnp.matmul(
            x.astype(cdt), weight.astype(cdt),
            preferred_element_type=jnp.float32,
        ).astype(cdt)
        if bias is not None and not self.skip_bias_add:
            y = y + bias.astype(cdt)
        if self.gather_output and world > 1:
            y = gather_from_tensor_model_parallel_region(y, self.axis_name)
        if self.skip_bias_add:
            return y, (bias.astype(cdt) if bias is not None else None)
        return y


class RowParallelLinear(nn.Module):
    """Y = XW + b with W row-sharded (input dim) — ≙ RowParallelLinear.

    fwd: local GEMM then all-reduce (or reduce-scatter along seq under SP).
    ``input_is_parallel``: input already carries this rank's shard of the
    last dim (the usual case after a ColumnParallelLinear).

    SP + ``skip_bias_add`` contract: the bias is registered for the
    sequence-parallel gradient psum, so the caller MUST apply the returned
    bias inside the sequence-sharded region (the Megatron
    bias-dropout-add pattern).  Applying it after a gather back to full
    sequence would double-count its gradient tp-fold.
    """

    input_size: int
    output_size: int
    use_bias: bool = True
    input_is_parallel: bool = False
    sequence_parallel_enabled: bool = False
    skip_bias_add: bool = False
    init_method: Callable = nn.initializers.lecun_normal()
    bias_init: Callable = nn.initializers.zeros
    gradient_accumulation_fusion: bool = False  # structural no-op
    param_dtype: Any = jnp.float32
    dtype: Optional[Any] = None
    axis_name: str = _TP

    @nn.compact
    def __call__(self, x):
        if self.sequence_parallel_enabled and not self.input_is_parallel:
            raise ValueError(
                "sequence_parallel_enabled requires input_is_parallel "
                "(reference asserts the same)"
            )
        world = _tp_world(self.axis_name)
        in_per = divide(self.input_size, world)
        weight = self.param(
            "weight",
            sharded_init(
                self.init_method,
                (self.input_size, self.output_size),
                0,
                self.axis_name,
            ),
            (in_per, self.output_size),
            self.param_dtype,
        )
        bias = (
            self.param(
                "bias", self.bias_init, (self.output_size,), self.param_dtype
            )
            if self.use_bias
            else None
        )
        if bias is not None and self.sequence_parallel_enabled:
            # bias is added AFTER the reduce-scatter, i.e. inside the SP
            # region: tp-replicated param, per-rank S/tp-partial gradient.
            # This registration covers skip_bias_add=True as well, which
            # CONTRACTS the caller to apply the returned bias inside the
            # SP region (the Megatron bias-dropout-add convention; the
            # mirrored reference marks param.sequence_parallel there too).
            # Adding it outside the SP region (e.g. after a gather) would
            # make the psum overcount that grad tp-fold — see the class
            # docstring.
            ps.register_sequence_parallel_param(self.path + ("bias",))
        if world > 1 and not self.input_is_parallel:
            x = scatter_to_tensor_model_parallel_region(x, self.axis_name)
        cdt = self.dtype or x.dtype
        y = jnp.matmul(
            x.astype(cdt), weight.astype(cdt),
            preferred_element_type=jnp.float32,
        ).astype(cdt)
        if world > 1:
            if self.sequence_parallel_enabled:
                y = reduce_scatter_to_sequence_parallel_region(y, self.axis_name)
            else:
                y = reduce_from_tensor_model_parallel_region(y, self.axis_name)
        if bias is not None and not self.skip_bias_add:
            y = y + bias.astype(cdt)
        if self.skip_bias_add:
            return y, (bias.astype(cdt) if bias is not None else None)
        return y
