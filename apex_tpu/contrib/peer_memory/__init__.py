"""Halo exchange over a mesh axis — ≙ ``apex/contrib/peer_memory``
(``peer_memory.py`` :: ``PeerMemoryPool``, ``peer_halo_exchanger_1d.py`` ::
``PeerHaloExchanger1d``) and ≙ ``apex/contrib/nccl_p2p`` (raw
ncclSend/Recv halos).

The reference maintains a CUDA-IPC peer buffer pool so neighboring GPUs
can write each other's halo rows directly.  On TPU neighbor exchange IS
the hardware primitive — ``jax.lax.ppermute`` over an ICI ring — and XLA
owns buffers, so the pool disappears and only the exchange semantics
remain: each rank sends its edge rows to its neighbors and receives
theirs.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu import parallel_state as ps

__all__ = ["halo_exchange_1d", "PeerHaloExchanger1d", "PeerMemoryPool"]


def halo_exchange_1d(x, halo: int, *, axis: int = 1, axis_name: str = "dp"):
    """Pad ``x`` with ``halo`` rows from ring neighbors along ``axis``.

    x is this rank's shard, split along spatial ``axis`` (default 1 = H in
    NHWC).  Returns the shard concatenated with the received halos:
    shape grows by ``2*halo`` along ``axis``.  Edge ranks receive zeros
    (zero padding, matching conv zero-pad semantics at the true borders).
    """
    world = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)

    top = jax.lax.slice_in_dim(x, 0, halo, axis=axis)
    bottom = jax.lax.slice_in_dim(
        x, x.shape[axis] - halo, x.shape[axis], axis=axis
    )
    # bottom rows travel down (r -> r+1), top rows travel up (r -> r-1)
    down = [(i, (i + 1) % world) for i in range(world)]
    up = [(i, (i - 1) % world) for i in range(world)]
    from_above = jax.lax.ppermute(bottom, axis_name, down)
    from_below = jax.lax.ppermute(top, axis_name, up)
    # zero the wrapped-around halos at the global edges
    from_above = jnp.where(rank == 0, jnp.zeros_like(from_above), from_above)
    from_below = jnp.where(
        rank == world - 1, jnp.zeros_like(from_below), from_below
    )
    return jnp.concatenate([from_above, x, from_below], axis=axis)


class PeerHaloExchanger1d:
    """API-parity wrapper ≙ PeerHaloExchanger1d(ranks, rank_id, pool, half_halo)."""

    def __init__(
        self,
        axis_name: str = "dp",
        half_halo: int = 1,
        spatial_axis: int = 1,
    ):
        self.axis_name = axis_name
        self.half_halo = half_halo
        self.spatial_axis = spatial_axis

    def __call__(self, x):
        return halo_exchange_1d(
            x, self.half_halo, axis=self.spatial_axis, axis_name=self.axis_name
        )


class PeerMemoryPool:
    """≙ PeerMemoryPool — N/A on TPU (XLA owns device buffers; ppermute is
    the peer-transfer primitive).  Kept so ported code constructing a pool
    gets a clear answer instead of an AttributeError."""

    def __init__(self, *args, **kwargs):
        pass

    def allocate_peer_tensors(self, *args, **kwargs):
        raise NotImplementedError(
            "PeerMemoryPool has no TPU analog: XLA manages device buffers "
            "and jax.lax.ppermute performs neighbor transfers — use "
            "halo_exchange_1d / PeerHaloExchanger1d"
        )
