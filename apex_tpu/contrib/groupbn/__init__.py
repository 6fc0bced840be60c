"""NHWC BatchNorm with ReLU/Add fusions ("BNP") — ≙ ``apex/contrib/groupbn``
(``batch_norm.py`` :: ``BatchNorm2d_NHWC``, native ``batch_norm.cu``/``ipc.cu``).

The reference's MLPerf-ResNet BN: NHWC kernels with fused ReLU and fused
residual-add, plus ``bn_group`` — statistics all-reduced across a small
group of GPUs over CUDA IPC.  TPU-native: NHWC is the native layout, the
fusions are XLA's, and ``bn_group > 1`` maps to a ``psum`` over the ``dp``
mesh axis (the IPC/peer-memory machinery has no analog and needs none).
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu import parallel_state as ps

__all__ = ["BatchNorm2d_NHWC"]


def _axis_bound(axis_name: str) -> bool:
    from apex_tpu.parallel_state import axis_is_bound

    # truly-bound check (size-1 axes included): the caller distinguishes
    # "not in shard_map" from "bn_group != axis size", and a bound size-1
    # axis must produce the latter, actionable, error
    return axis_is_bound(axis_name)


class BatchNorm2d_NHWC(nn.Module):
    """≙ BatchNorm2d_NHWC(num_features, fuse_relu=False, bn_group=1).

    ``__call__(x, z=None)``: optional ``z`` is the fused residual add
    (≙ the reference's bn_add_relu path).  ``bn_group > 1`` all-reduces
    the batch statistics over ``axis_name`` (requires the axis bound and
    its size equal to ``bn_group``, mirroring the reference's assert that
    the process group matches).
    """

    num_features: int
    eps: float = 1e-5
    momentum: float = 0.1  # torch convention: running = (1-m)*running + m*new
    fuse_relu: bool = False
    bn_group: int = 1
    axis_name: str = ps.DATA_PARALLEL_AXIS
    use_running_average: Optional[bool] = None
    dtype: Any = None
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, z=None, use_running_average: Optional[bool] = None):
        use_running_average = nn.merge_param(
            "use_running_average",
            self.use_running_average,
            use_running_average,
        )
        feat = self.num_features
        if x.shape[-1] != feat:
            raise ValueError(
                f"BatchNorm2d_NHWC expects channels-last with {feat} "
                f"channels, got {x.shape}"
            )
        xf = x.astype(jnp.float32)
        reduce_axes = tuple(range(x.ndim - 1))

        ra_mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros((feat,), jnp.float32)
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda: jnp.ones((feat,), jnp.float32)
        )

        if use_running_average:
            mean, var = ra_mean.value, ra_var.value
        else:
            n_local = jnp.asarray(xf.size // feat, jnp.float32)
            s1 = jnp.sum(xf, axis=reduce_axes)
            s2 = jnp.sum(xf * xf, axis=reduce_axes)
            if self.bn_group > 1:
                if not _axis_bound(self.axis_name):
                    raise RuntimeError(
                        f"bn_group={self.bn_group} needs axis "
                        f"{self.axis_name!r} bound (run inside shard_map)"
                    )
                world = jax.lax.axis_size(self.axis_name)
                if world != self.bn_group:
                    raise ValueError(
                        f"bn_group ({self.bn_group}) must equal the "
                        f"{self.axis_name!r} axis size ({world})"
                    )
                n = jax.lax.psum(n_local, self.axis_name)
                s1 = jax.lax.psum(s1, self.axis_name)
                s2 = jax.lax.psum(s2, self.axis_name)
            else:
                n = n_local
            mean = s1 / n
            var = s2 / n - mean * mean
            if not self.is_initializing():
                m = self.momentum
                unbiased = var * n / jnp.maximum(n - 1.0, 1.0)
                ra_mean.value = (1.0 - m) * ra_mean.value + m * mean
                ra_var.value = (1.0 - m) * ra_var.value + m * unbiased

        scale = self.param(
            "weight", nn.initializers.ones, (feat,), self.param_dtype
        )
        bias = self.param(
            "bias", nn.initializers.zeros, (feat,), self.param_dtype
        )
        y = (xf - mean) * jax.lax.rsqrt(var + self.eps)
        y = y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
        if z is not None:  # fused residual add (bn_add_relu)
            y = y + z.astype(jnp.float32)
        if self.fuse_relu:
            y = jax.nn.relu(y)
        return y.astype(self.dtype or x.dtype)
