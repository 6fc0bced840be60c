"""Raw neighbor send/recv — ≙ ``apex/contrib/nccl_p2p`` (``nccl_p2p.py``,
native ``nccl_p2p_cuda.cu`` :: ``left_right_halo_exchange``).

The reference bypasses ``torch.distributed`` with raw ``ncclSend/Recv``
for halo traffic.  The TPU primitive is ``jax.lax.ppermute``; the
convenience functions below mirror the reference's call shapes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["left_right_halo_exchange", "halo_exchange_1d"]

from apex_tpu.contrib.peer_memory import halo_exchange_1d


def left_right_halo_exchange(
    left_output_halo, right_output_halo, axis_name: str = "dp"
):
    """Send left/right edge halos to the respective neighbors.

    ≙ nccl_p2p_cuda.left_right_halo_exchange: returns
    (left_input_halo, right_input_halo) — what the left/right neighbors
    sent this rank (zeros at the global edges).
    """
    world = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    to_left = [(i, (i - 1) % world) for i in range(world)]
    to_right = [(i, (i + 1) % world) for i in range(world)]
    # my left halo goes to my left neighbor's right input, and vice versa
    right_input_halo = jax.lax.ppermute(left_output_halo, axis_name, to_left)
    left_input_halo = jax.lax.ppermute(right_output_halo, axis_name, to_right)
    left_input_halo = jnp.where(
        rank == 0, jnp.zeros_like(left_input_halo), left_input_halo
    )
    right_input_halo = jnp.where(
        rank == world - 1, jnp.zeros_like(right_input_halo), right_input_halo
    )
    return left_input_halo, right_input_halo
