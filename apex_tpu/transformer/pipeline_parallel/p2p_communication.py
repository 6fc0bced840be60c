"""Stage-to-stage exchange — ≙ apex/transformer/pipeline_parallel/
p2p_communication.py.

The reference builds ``torch.distributed.P2POp`` lists and
``batch_isend_irecv`` with a shape handshake (``_communicate`` /
``_communicate_shapes``).  On TPU there is no point-to-point primitive —
stage exchange is ``jax.lax.ppermute`` along the ``pp`` mesh axis inside
``shard_map``: every (sender → receiver) pair moves simultaneously over ICI,
and a rank with no inbound edge receives **zeros** (ppermute's semantics),
which replaces the reference's "first stage receives None".

Semantic shift to be aware of: these are *collectives* — every pp rank
calls the same function and gets its neighbor's value — so the reference's
send/recv pairs collapse: ``recv_forward(x)`` ≡ ``send_forward(x)`` ≡ "the
value this rank receives from the previous stage given that every rank
sends ``x``".  The shape handshake is unnecessary: shapes are static under
jit.

All functions take/return activation pytrees.
"""

from __future__ import annotations

from typing import Any

import jax

from apex_tpu import parallel_state as ps

__all__ = [
    "send_forward",
    "recv_forward",
    "send_backward",
    "recv_backward",
    "send_forward_recv_backward",
    "send_backward_recv_forward",
    "send_forward_recv_forward",
    "send_backward_recv_backward",
]

_PP = ps.PIPELINE_PARALLEL_AXIS


def _shift(tree: Any, delta: int, axis_name: str, cyclic: bool = False):
    n = jax.lax.axis_size(axis_name)
    if cyclic:
        perm = [(i, (i + delta) % n) for i in range(n)]
    else:
        perm = [
            (i, i + delta) for i in range(n) if 0 <= i + delta < n
        ]
    with jax.named_scope("pp_p2p_shift"):
        return jax.tree_util.tree_map(
            lambda x: jax.lax.ppermute(x, axis_name, perm), tree
        )


def send_forward_recv_forward(x, axis_name: str = _PP, cyclic: bool = False):
    """Every rank sends ``x`` to the next stage; returns what this rank
    receives from the previous (zeros at stage 0 unless ``cyclic``)."""
    return _shift(x, +1, axis_name, cyclic)


def send_backward_recv_backward(g, axis_name: str = _PP, cyclic: bool = False):
    """Every rank sends ``g`` to the previous stage; returns what this rank
    receives from the next (zeros at the last stage unless ``cyclic``)."""
    return _shift(g, -1, axis_name, cyclic)


# Reference-shaped aliases (see module docstring on the collective collapse).
send_forward = send_forward_recv_forward
recv_forward = send_forward_recv_forward
send_backward = send_backward_recv_backward
recv_backward = send_backward_recv_backward


def send_forward_recv_backward(output, grad, axis_name: str = _PP):
    """1F1B steady-state edge: push activations down, pull grads up.

    Returns ``(recv_activation, recv_grad)`` — two independent ppermutes
    that XLA schedules concurrently (≙ the batched isend/irecv pair)."""
    return (
        send_forward_recv_forward(output, axis_name),
        send_backward_recv_backward(grad, axis_name),
    )


def send_backward_recv_forward(grad, output, axis_name: str = _PP):
    """Mirror of :func:`send_forward_recv_backward`."""
    return (
        send_backward_recv_backward(grad, axis_name),
        send_forward_recv_forward(output, axis_name),
    )
