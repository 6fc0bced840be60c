"""Data parallelism — ≙ apex/parallel/distributed.py.

The reference's ``DistributedDataParallel`` flattens gradients into
~``message_size`` buckets and overlaps NCCL all-reduce with backward via
grad-accumulator hooks (SURVEY.md §3.3).  Under XLA none of that machinery
exists or is needed: gradients of a jitted step are all-reduced with
``psum`` over the ``dp`` mesh axis, and the XLA scheduler overlaps the
collectives with remaining backward compute (the bucketing/ready-order
capture is the compiler's job).  What this module keeps is the *semantics
surface*: gradient averaging, predivide factors (for large world sizes where
pre-division avoids overflow in half precision), ``delay_allreduce`` /
``no_sync`` gradient accumulation, and the ``Reducer`` manual-reduction
helper.

Gradient sync itself is delegated to :mod:`apex_tpu.parallel.comm` (see
``docs/comm.md``): ``wire="bf16"|"int8"`` swaps the exact psum for a
bucketed quantized reduce-scatter + all-gather, and ``chunks=K`` splits
the bucket so XLA can overlap chunk collectives with dequant/optimizer
math — the same engine the ZeRO optimizers use.
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu import parallel_state as ps
from apex_tpu.parallel import comm

__all__ = ["all_reduce_gradients", "DistributedDataParallel", "Reducer"]


def all_reduce_gradients(
    grads: Any,
    axis_name: str = ps.DATA_PARALLEL_AXIS,
    gradient_average: bool = True,
    gradient_predivide_factor: Optional[float] = None,
):
    """psum gradients over the data-parallel axis (call inside shard_map).

    ≙ the flat_dist_call all-reduce + ``gradient_average`` /
    ``gradient_predivide_factor`` handling in
    apex/parallel/distributed.py :: DistributedDataParallel.  This is the
    EXACT (bit-reproducible) path; :func:`apex_tpu.parallel.comm
    .sync_gradients` layers wire formats and chunking on the same
    semantics.
    """
    world = jax.lax.axis_size(axis_name)

    def f(g):
        gf = g
        if gradient_predivide_factor is not None:
            gf = gf / gradient_predivide_factor
        gf = jax.lax.psum(gf, axis_name)
        if gradient_average:
            post = (
                world / gradient_predivide_factor
                if gradient_predivide_factor is not None
                else world
            )
            gf = gf / post
        return gf

    with jax.named_scope("ddp_allreduce"):
        return jax.tree_util.tree_map(f, grads)


class DistributedDataParallel:
    """Wraps a loss function for data-parallel training.

    ≙ ``apex.parallel.DistributedDataParallel(model, message_size=...,
    gradient_average=..., gradient_predivide_factor=...)``.  The
    ``message_size``/``allreduce_trigger_params`` bucketing knobs have no
    analog (XLA fuses and schedules collectives); ``delay_allreduce`` maps
    to ``delay_allreduce=True`` → the wrapper skips the psum so the caller
    reduces manually, and :meth:`no_sync` gives the torch-DDP-style scoped
    version: grads stay local inside the context, the caller pays ONE
    (possibly quantized) sync on the accumulation-boundary step.

    ``wire``/``chunks``/``block``/``min_size`` are the
    :mod:`apex_tpu.parallel.comm` engine knobs (``docs/comm.md``):
    ``wire="int8"`` cuts sync bytes ~4x at ~1/127-of-block-max gradient
    error, ``chunks`` splits the bucket for collective/compute overlap.
    The default (``wire="f32"``, no chunking) is the exact psum.

    Usage::

        ddp = DistributedDataParallel(loss_fn)
        step = ddp.make_step(tx, mesh)           # jitted SPMD train step
        params, opt_state, loss = step(params, opt_state, batch)

    or, inside your own ``shard_map``::

        loss, grads = ddp.value_and_grad(params, batch)

    Gradient accumulation, either scoped (all microbatches LOCAL, one
    engine sync on the summed tree)::

        with ddp.no_sync():
            _, g1 = ddp.value_and_grad(params, microbatch1)  # local
            _, g2 = ddp.value_and_grad(params, microbatch2)  # local
        acc = jax.tree_util.tree_map(lambda a, b: a + b, g1, g2)
        grads = ddp.all_reduce_gradients(acc)                # ONE sync

    or prebuilt: :meth:`accum_value_and_grad` scans ``(K, ...)``-stacked
    microbatches for you, and ``ddp.make_step(tx, mesh, accum_steps=K)``
    wraps that in a full jitted train step.
    """

    def __init__(
        self,
        loss_fn: Callable,
        axis_name: str = ps.DATA_PARALLEL_AXIS,
        gradient_average: bool = True,
        gradient_predivide_factor: Optional[float] = None,
        delay_allreduce: bool = False,
        wire: str = "f32",
        chunks: Optional[int] = None,
        block: int = comm.DEFAULT_BLOCK,
        min_size: int = 1024,
    ):
        self.loss_fn = loss_fn
        self.axis_name = axis_name
        self.gradient_average = gradient_average
        self.gradient_predivide_factor = gradient_predivide_factor
        self.delay_allreduce = delay_allreduce
        self.wire = comm.check_wire(wire)
        self.chunks = chunks
        self.block = block
        self.min_size = min_size
        self._no_sync = False

    @contextlib.contextmanager
    def no_sync(self):
        """Inside this context :meth:`value_and_grad` returns LOCAL
        (unsynced) grads — Apex's ``delay_allreduce`` as a scope, torch
        DDP's ``no_sync()`` by name.  Accumulate across microbatches,
        then sync once (:meth:`all_reduce_gradients`) on the boundary
        step.  Trace-time state: enter it around the tracing of the
        microbatch, not inside traced control flow."""
        prev = self._no_sync
        self._no_sync = True
        try:
            yield
        finally:
            self._no_sync = prev

    def collective_plan(self, params, world: int) -> dict:
        """The per-mesh-axis collective plan this wrapper's step
        promises — ``{"mesh": {axis: world}, "collectives": [...]}``
        in the schema of :func:`apex_tpu.analysis.sharding
        .reshard_pass`, built by :func:`apex_tpu.parallel.comm
        .sync_plan` from the same wire/chunks/min_size knobs the
        traced sync uses.  Feed it to ``analysis.check(...,
        expect_plan=...)`` (or ``tools/graph_lint.py`` does, for the
        resilient target) to prove the compiled step contains ONLY
        the declared gradient sync — an extra weight all-gather is a
        ``reshard-unplanned`` ERROR."""
        return {
            "mesh": {self.axis_name: int(world)},
            "collectives": comm.sync_plan(
                params, world, self.axis_name,
                wire=self.wire, chunks=self.chunks, block=self.block,
                min_size=self.min_size,
            ),
        }

    def all_reduce_gradients(self, grads):
        """Sync a (local) gradient tree with this wrapper's engine
        config — the one comms layer shared with the ZeRO optimizers
        (:func:`apex_tpu.parallel.comm.sync_gradients`)."""
        return comm.sync_gradients(
            grads,
            self.axis_name,
            wire=self.wire,
            chunks=self.chunks,
            block=self.block,
            min_size=self.min_size,
            gradient_average=self.gradient_average,
            gradient_predivide_factor=self.gradient_predivide_factor,
        )

    def accum_value_and_grad(self, params, *batch):
        """K-microbatch gradient accumulation (call inside shard_map):
        every ``batch`` leaf carries a leading ``(K, ...)`` microbatch
        axis; microbatch grads accumulate LOCALLY inside a ``lax.scan``
        (``no_sync`` semantics) and ONE engine sync runs on the
        boundary.  Returns ``(loss, grads)`` — the dp-mean of the mean
        microbatch loss, and the synced tree; with ``gradient_average``
        the accumulated sum is divided by K first, so the result matches
        one big-batch step over the same rows (equal microbatches)."""
        k = jax.tree_util.tree_leaves(batch)[0].shape[0]

        def micro(acc, mb):
            with self.no_sync():
                l, g = self.value_and_grad(params, *mb)
            return jax.tree_util.tree_map(jnp.add, acc, g), l

        # local (no_sync) grads are dp-varying; the scan carry must start
        # with the same vma type
        zeros = jax.tree_util.tree_map(
            lambda p: jax.lax.pcast(
                jnp.zeros(p.shape, jnp.result_type(p)),
                self.axis_name,
                to="varying",
            ),
            params,
        )
        acc, losses = jax.lax.scan(micro, zeros, batch)
        if self.gradient_average:
            acc = jax.tree_util.tree_map(lambda g: g / k, acc)
        grads = self.all_reduce_gradients(acc)
        loss = jax.lax.pmean(jnp.mean(losses), self.axis_name)
        return loss, grads

    def _wants_manual_sync(self) -> bool:
        return (
            self.delay_allreduce
            or self._no_sync
            or self.gradient_predivide_factor is not None
            or self.wire != "f32"
            or comm.chunks_requested(self.chunks)
        )

    def value_and_grad(self, params, *batch):
        """Per-shard loss + dp-reduced grads; call inside shard_map.

        Under jax's shard_map vma semantics, differentiating w.r.t.
        *replicated* params already inserts the cross-shard psum in the
        transpose (the bucketed all-reduce the reference implements by
        hand).  The fast path therefore only divides for averaging.  The
        ``delay_allreduce`` / ``no_sync`` / predivide / non-f32-wire
        paths need genuinely *local* grads, so params are marked varying
        (``pcast to='varying'``) first, which suppresses the automatic
        psum; sync (when not delayed) then runs through the comm engine.
        """
        if self._wants_manual_sync():
            params_v = jax.tree_util.tree_map(
                lambda p: jax.lax.pcast(p, self.axis_name, to="varying"),
                params,
            )
            loss, grads = jax.value_and_grad(self.loss_fn)(params_v, *batch)
            if not (self.delay_allreduce or self._no_sync):
                grads = self.all_reduce_gradients(grads)
                loss = jax.lax.pmean(loss, self.axis_name)
            return loss, grads
        loss, grads = jax.value_and_grad(self.loss_fn)(params, *batch)
        if self.gradient_average:
            world = jax.lax.axis_size(self.axis_name)
            grads = jax.tree_util.tree_map(lambda g: g / world, grads)
            loss = jax.lax.pmean(loss, self.axis_name)
        return loss, grads

    def make_step(self, tx, mesh=None, accum_steps: int = 1):
        """Build a jitted SPMD train step: batch sharded over dp, params
        replicated, grads synced via the engine, optimizer applied
        identically on every device.

        ``accum_steps=K > 1`` adds gradient accumulation: batch leaves
        carry a leading ``(K, ...)`` microbatch axis, microbatch grads
        accumulate LOCALLY inside a ``lax.scan`` (``no_sync``
        semantics), and the one engine sync runs on the boundary —
        K microbatches, one wire payment.
        """
        mesh = mesh or ps.get_mesh()
        if accum_steps < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")

        def _step(params, opt_state, batch):
            if accum_steps == 1:
                loss, grads = self.value_and_grad(params, batch)
            else:
                loss, grads = self.accum_value_and_grad(params, batch)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = jax.tree_util.tree_map(
                lambda p, u: p + u.astype(p.dtype), params, updates
            )
            return params, opt_state, loss

        batch_spec = (
            P(self.axis_name)
            if accum_steps == 1
            else P(None, self.axis_name)  # (K, per-rank batch, ...)
        )
        # The engine's bucketed sync (a quantized wire, or chunks) ends in
        # an all_gather: replicated by construction but typed varying.
        # Only there is the static replication check off; the exact-psum
        # paths (fast, accumulation, predivide) stay checked.
        bucketed = self.wire != "f32" or comm.chunks_requested(self.chunks)
        smapped = jax.shard_map(
            _step,
            mesh=mesh,
            in_specs=(P(), P(), batch_spec),
            out_specs=(P(), P(), P()),
            check_vma=not bucketed,
        )
        return jax.jit(smapped)


class Reducer:
    """Manual-reduction helper — ≙ apex/parallel/distributed.py :: Reducer.

    ``broadcast_params`` is a no-op under SPMD (all replicas trace the same
    init); ``reduce`` psums a pytree on demand.
    """

    def __init__(self, axis_name: str = ps.DATA_PARALLEL_AXIS):
        self.axis_name = axis_name

    def broadcast_params(self, params):
        return params  # replicated by construction

    def reduce(self, tree, average: bool = True):
        world = jax.lax.axis_size(self.axis_name)

        def f(x):
            s = jax.lax.psum(x, self.axis_name)
            return s / world if average else s

        return jax.tree_util.tree_map(f, tree)
