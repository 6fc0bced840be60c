"""ZeRO-style weight-update-sharded optimizers.

≙ ``apex/contrib/optimizers/distributed_fused_adam.py`` ::
``DistributedFusedAdam`` and ``.../distributed_fused_lamb.py`` ::
``DistributedFusedLamb`` (grads reduce-scattered over the data-parallel
group, shard-local fused update, params all-gathered; the technique TPU
literature calls automatic cross-replica sharding of the weight update —
see PAPERS.md).

Mapping to XLA collectives (inside ``shard_map`` over the ``dp`` axis):

- the reference's two-level NCCL reduce-scatter pipeline
  (``_pipeline_block_reductions``) → one ``jax.lax.psum_scatter`` over a
  flat f32 buffer (XLA schedules/overlaps);
- shard-local ``multi_tensor_adam``/``multi_tensor_lamb`` → elementwise
  update on the shard, with LAMB's per-tensor norms via ``segment_sum``
  over leaf-id segments + ``psum`` (the shard boundary does not align with
  tensor boundaries, exactly like the reference's flat buffer);
- param all-gather (``full_ar=False`` path) → ``jax.lax.all_gather(...,
  tiled=True)``.

Optimizer state (m, v, and the f32 ``master`` params) lives permanently
sharded: global arrays of shape ``(padded_size,)`` with sharding
``P("dp")`` — each device owns ``padded_size // world`` elements, the
1/N memory footprint that is the point of ZeRO.

The ``master`` shard is the AUTHORITATIVE param value (classic ZeRO
master weights): the update applies to it in f32 every step, and the
all-gathered replicated tree is only the working copy the next
forward/backward reads.  That is what makes a lossy ``param_wire``
safe — a bf16 gather rounds the working copy, never the accumulator,
so updates smaller than a wire ulp still accumulate instead of being
re-rounded away step after step.  (Consequence: edits to the replicated
params tree between steps are ignored; reinitialize via :meth:`init`
to reset the masters.)

Both collectives run through :mod:`apex_tpu.parallel.comm` (the engine
shared with ``DistributedDataParallel`` — see ``docs/comm.md``):
``wire="bf16" | "int8"`` swaps the f32 wire for a quantized one (f32
shard-local accumulation either way; ~2x / ~4x fewer sync bytes — the
analog of the reference LAMB's ``fp16 compressed allgather`` knob, which
r0 recorded as having "no XLA analog": it does now), and ``chunks=K``
splits the flat buffer so XLA can overlap chunk N's collective with
chunk N-1's dequant/optimizer math.  ``param_wire`` overrides the wire
for the param all-gather alone — it sets the precision of the WORKING
copy the forward/backward reads (the f32 masters below are never
rounded), so ``wire="int8", param_wire="bf16"`` is the recommended
aggressive setting: grads tolerate coarse wires, activations want the
params at >= bf16.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu import parallel_state as ps
from apex_tpu._tree_util import to_f32
from apex_tpu.parallel import comm

__all__ = ["DistributedFusedAdam", "DistributedFusedLAMB"]


class _FlatSpec(NamedTuple):
    flat_size: int
    padded_size: int
    shard_size: int
    world: int
    n_leaves: int
    unravel: Any  # host closure flat f32 -> param tree
    segment_ids: np.ndarray  # (padded_size,) int32 leaf index, pad -> n_leaves


def _make_spec(params, world: int) -> _FlatSpec:
    flat, unravel = ravel_pytree(
        jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    )
    flat_size = flat.size
    shard = -(-flat_size // world)  # ceil
    padded = shard * world
    leaves = jax.tree_util.tree_leaves(params)
    seg = np.full((padded,), len(leaves), np.int32)
    off = 0
    for i, leaf in enumerate(leaves):
        seg[off : off + leaf.size] = i
        off += leaf.size
    return _FlatSpec(
        flat_size=flat_size,
        padded_size=padded,
        shard_size=shard,
        world=world,
        n_leaves=len(leaves),
        unravel=unravel,
        segment_ids=seg,
    )


def _flatten_pad(tree, spec: _FlatSpec):
    flat, _ = ravel_pytree(to_f32(tree))
    return jnp.pad(flat, (0, spec.padded_size - spec.flat_size))


class _DistributedFusedBase:
    def __init__(
        self,
        axis_name: str = ps.DATA_PARALLEL_AXIS,
        wire: str = "f32",
        chunks: int | None = None,
        block: int = comm.DEFAULT_BLOCK,
        param_wire: str | None = None,
    ):
        self.axis_name = axis_name
        self.wire = comm.check_wire(wire)
        self.chunks = chunks
        self.block = block
        self.param_wire = (
            comm.check_wire(param_wire) if param_wire is not None else None
        )
        self._spec: _FlatSpec | None = None

    # -- host-side ------------------------------------------------------
    def init(self, params, world: int | None = None):
        """Returns the sharded state pytree (place with sharding P(dp));
        ``state.master`` is seeded with the flattened f32 params — the
        authoritative copy every later update applies to."""
        world = world or ps.get_data_parallel_world_size()
        self._spec = _make_spec(params, world)
        state = self._init_state(self._spec)
        return state._replace(master=_flatten_pad(params, self._spec))

    def state_sharding(self, mesh=None):
        """NamedShardings for the state (flat arrays sharded over dp)."""
        mesh = mesh or ps.get_mesh()
        flat_sh = NamedSharding(mesh, P(self.axis_name))
        return jax.tree_util.tree_map(
            lambda x: flat_sh if getattr(x, "ndim", 0) == 1 else NamedSharding(mesh, P()),
            self._init_state(self._spec),
        )

    @property
    def spec(self) -> _FlatSpec:
        if self._spec is None:
            raise RuntimeError("call init(params) first")
        return self._spec

    def collective_plan(self) -> dict:
        """The per-mesh-axis collective plan one sharded step promises
        (``analysis.sharding.reshard_pass`` schema): the chunked
        grad reduce-scatter at ``wire``, the param all-gather at
        ``param_wire or wire``, and the small norm/loss all-reduces —
        via :func:`apex_tpu.parallel.comm.zero_plan` on this
        optimizer's own flat spec.  Call after :meth:`init`."""
        spec = self.spec
        return {
            "mesh": {self.axis_name: spec.world},
            "collectives": comm.zero_plan(
                spec.flat_size, spec.world, self.axis_name,
                wire=self.wire, param_wire=self.param_wire,
                chunks=self.chunks, block=self.block,
            ),
        }

    # -- device-side (inside shard_map over the dp axis) ----------------
    def reduce_scatter_grads(self, grads, gradient_average: bool = True):
        """Local grads tree -> my reduced flat shard (f32), via the comm
        engine's (possibly quantized, possibly chunked) reduce-scatter
        with f32 shard-local accumulation."""
        spec = self.spec
        flat = _flatten_pad(grads, spec)
        shard = comm.reduce_scatter_flat(
            flat, self.axis_name,
            wire=self.wire, chunks=self.chunks, block=self.block,
        )
        if gradient_average:
            shard = shard / spec.world
        return shard

    def my_param_shard(self, params):
        spec = self.spec
        flat = _flatten_pad(params, spec)
        rank = jax.lax.axis_index(self.axis_name)
        return jax.lax.dynamic_slice(flat, (rank * spec.shard_size,), (spec.shard_size,))

    def my_segment_ids(self):
        spec = self.spec
        rank = jax.lax.axis_index(self.axis_name)
        seg = jnp.asarray(spec.segment_ids)
        return jax.lax.dynamic_slice(seg, (rank * spec.shard_size,), (spec.shard_size,))

    def gather_params(self, new_param_shard, params_template):
        """All-gather updated shards and rebuild the (dtype-cast) tree.

        Runs at ``param_wire`` (default: follow ``wire``); every rank
        decodes the same payloads — its own included — so params stay
        bit-identical across replicas whatever the wire."""
        spec = self.spec
        flat = comm.all_gather_flat(
            new_param_shard, self.axis_name,
            wire=self.param_wire or self.wire,
            chunks=self.chunks, block=self.block,
        )
        tree = spec.unravel(flat[: spec.flat_size])
        return jax.tree_util.tree_map(
            lambda t, x: x.astype(t.dtype), params_template, tree
        )

    def update_inside_shard_map(self, grads, state, params,
                                gradient_average: bool = True):
        """Full sharded step: returns (new_params, new_state).

        ``grads`` must be *local* per-shard gradients (not yet reduced):
        under ``check_vma=True`` shard_map, mark params varying first
        (``jax.lax.pcast(p, axis, to='varying')``) or jax's autodiff will
        have already all-reduced them and the reduce-scatter here would
        double-count.

        The update applies to ``state.master`` (the f32 shard), never to
        the possibly-wire-rounded ``params`` — ``params`` only supplies
        the tree structure/dtypes for the gathered working copy.
        """
        g_shard = self.reduce_scatter_grads(grads, gradient_average)
        new_p_shard, new_state = self._shard_update(
            g_shard, state, state.master
        )
        new_state = new_state._replace(master=new_p_shard)
        return self.gather_params(new_p_shard, params), new_state

    def update_with_norm(self, grads, state, params,
                         gradient_average: bool = True):
        """:meth:`update_inside_shard_map` that also returns the global
        L2 norm of the reduced (averaged) gradient — measured on the
        reduce-scattered shards, so it costs one extra scalar psum and
        nothing else.  The shards partition the flat buffer exactly, so
        the psum of per-shard square-sums is the exact norm of the
        gradient the update consumed (per ``axis_name`` group: with an
        additional tp axis the flat buffer duplicates tp-replicated
        leaves, so callers wanting a global norm there must account for
        it — :class:`apex_tpu.train.Trainer` refuses that combination).
        """
        g_shard = self.reduce_scatter_grads(grads, gradient_average)
        norm = jnp.sqrt(
            jax.lax.psum(jnp.sum(g_shard * g_shard), self.axis_name)
        )
        new_p_shard, new_state = self._shard_update(
            g_shard, state, state.master
        )
        new_state = new_state._replace(master=new_p_shard)
        return self.gather_params(new_p_shard, params), new_state, norm

    # -- convenience ----------------------------------------------------
    def make_train_step(self, loss_fn, mesh=None):
        """jitted SPMD step: (params, state, batch) -> (params, state, loss).

        ``batch`` sharded over dp; params replicated; state sharded.

        Runs with ``check_vma=False`` (classic manual-collective semantics):
        gradients stay *local* per shard so the communication pattern is a
        true reduce-scatter + all-gather — the ZeRO structure the reference
        implements — rather than the full grad all-reduce jax's vma
        autodiff would otherwise insert for replicated params.
        """
        mesh = mesh or ps.get_mesh()

        def _step(params, state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            loss = jax.lax.pmean(loss, self.axis_name)
            params, state = self.update_inside_shard_map(grads, state, params)
            return params, state, loss

        state_spec = jax.tree_util.tree_map(
            lambda x: P(self.axis_name) if getattr(x, "ndim", 0) == 1 else P(),
            self._init_state(self.spec),
        )
        smapped = jax.shard_map(
            _step,
            mesh=mesh,
            in_specs=(P(), state_spec, P(self.axis_name)),
            out_specs=(P(), state_spec, P()),
            check_vma=False,
        )
        return jax.jit(smapped)


class _AdamState(NamedTuple):
    count: jax.Array
    m: jax.Array  # (padded,) sharded over dp
    v: jax.Array
    master: jax.Array  # (padded,) f32 authoritative params, sharded over dp


class DistributedFusedAdam(_DistributedFusedBase):
    """≙ apex.contrib.optimizers.DistributedFusedAdam (ZeRO Adam(W))."""

    def __init__(
        self,
        lr: float = 1e-3,
        betas=(0.9, 0.999),
        eps: float = 1e-8,
        weight_decay: float = 0.0,
        adam_w_mode: bool = True,
        bias_correction: bool = True,
        axis_name: str = ps.DATA_PARALLEL_AXIS,
        wire: str = "f32",
        chunks: int | None = None,
        block: int = comm.DEFAULT_BLOCK,
        param_wire: str | None = None,
    ):
        super().__init__(axis_name, wire=wire, chunks=chunks, block=block,
                         param_wire=param_wire)
        self.lr = lr
        self.beta1, self.beta2 = betas
        self.eps = eps
        self.weight_decay = weight_decay
        self.adam_w_mode = adam_w_mode
        self.bias_correction = bias_correction

    def _init_state(self, spec: _FlatSpec):
        return _AdamState(
            count=jnp.zeros((), jnp.int32),
            m=jnp.zeros((spec.padded_size,), jnp.float32),
            v=jnp.zeros((spec.padded_size,), jnp.float32),
            master=jnp.zeros((spec.padded_size,), jnp.float32),
        )

    def _shard_update(self, g, state: _AdamState, p):
        count = state.count + 1
        cf = count.astype(jnp.float32)
        bc1 = 1.0 - self.beta1**cf if self.bias_correction else 1.0
        bc2 = 1.0 - self.beta2**cf if self.bias_correction else 1.0
        if not self.adam_w_mode and self.weight_decay != 0.0:
            g = g + self.weight_decay * p
        m = self.beta1 * state.m + (1.0 - self.beta1) * g
        v = self.beta2 * state.v + (1.0 - self.beta2) * g * g
        u = (m / bc1) / (jnp.sqrt(v / bc2) + self.eps)
        if self.adam_w_mode and self.weight_decay != 0.0:
            u = u + self.weight_decay * p
        return p - self.lr * u, _AdamState(
            count=count, m=m, v=v, master=state.master
        )


class _LambState(NamedTuple):
    count: jax.Array
    m: jax.Array
    v: jax.Array
    master: jax.Array  # (padded,) f32 authoritative params, sharded over dp


class DistributedFusedLAMB(_DistributedFusedBase):
    """≙ apex.contrib.optimizers.DistributedFusedLAMB (ZeRO LAMB).

    The reference's ``clip_after_ar`` (clip by the global grad norm after
    the all-reduce), per-tensor trust ratios across shard boundaries, and
    nvlamb gating are reproduced; its fp16 compressed-allgather knob maps
    to ``param_wire="bf16"`` (and grads go further: ``wire="int8"`` —
    see ``docs/comm.md``).
    """

    def __init__(
        self,
        lr: float = 1e-3,
        betas=(0.9, 0.999),
        eps: float = 1e-6,
        weight_decay: float = 0.01,
        bias_correction: bool = True,
        grad_averaging: bool = True,
        adam_w_mode: bool = True,
        max_grad_norm: float = 1.0,
        use_nvlamb: bool = False,
        axis_name: str = ps.DATA_PARALLEL_AXIS,
        wire: str = "f32",
        chunks: int | None = None,
        block: int = comm.DEFAULT_BLOCK,
        param_wire: str | None = None,
    ):
        super().__init__(axis_name, wire=wire, chunks=chunks, block=block,
                         param_wire=param_wire)
        self.lr = lr
        self.beta1, self.beta2 = betas
        self.eps = eps
        self.weight_decay = weight_decay
        self.bias_correction = bias_correction
        self.grad_averaging = grad_averaging
        self.adam_w_mode = adam_w_mode
        self.max_grad_norm = max_grad_norm
        self.use_nvlamb = use_nvlamb

    def _init_state(self, spec: _FlatSpec):
        return _LambState(
            count=jnp.zeros((), jnp.int32),
            m=jnp.zeros((spec.padded_size,), jnp.float32),
            v=jnp.zeros((spec.padded_size,), jnp.float32),
            master=jnp.zeros((spec.padded_size,), jnp.float32),
        )

    def _shard_update(self, g, state: _LambState, p):
        spec = self.spec
        seg = self.my_segment_ids()
        nseg = spec.n_leaves + 1  # +1 = padding segment
        count = state.count + 1
        cf = count.astype(jnp.float32)
        bc1 = 1.0 - self.beta1**cf if self.bias_correction else 1.0
        bc2 = 1.0 - self.beta2**cf if self.bias_correction else 1.0
        beta3 = (1.0 - self.beta1) if self.grad_averaging else 1.0

        # global grad norm over all shards (clip_after_ar semantics)
        gnorm = jnp.sqrt(
            jax.lax.psum(jnp.sum(g * g), self.axis_name)
        )
        clip_ratio = jnp.where(
            (self.max_grad_norm > 0.0) & (gnorm > self.max_grad_norm),
            gnorm / self.max_grad_norm,
            1.0,
        )
        g = g / clip_ratio
        if not self.adam_w_mode and self.weight_decay != 0.0:
            g = g + self.weight_decay * p

        m = self.beta1 * state.m + beta3 * g
        v = self.beta2 * state.v + (1.0 - self.beta2) * g * g
        u = (m / bc1) / (jnp.sqrt(v / bc2) + self.eps)
        if self.adam_w_mode and self.weight_decay != 0.0:
            u = u + self.weight_decay * p

        # per-tensor norms across shard boundaries: segment partials + psum
        w_sq = jax.ops.segment_sum(p * p, seg, num_segments=nseg)
        u_sq = jax.ops.segment_sum(u * u, seg, num_segments=nseg)
        w_norm = jnp.sqrt(jax.lax.psum(w_sq, self.axis_name))
        u_norm = jnp.sqrt(jax.lax.psum(u_sq, self.axis_name))
        ratio = jnp.where((w_norm > 0.0) & (u_norm > 0.0), w_norm / u_norm, 1.0)
        if not self.use_nvlamb and self.weight_decay == 0.0:
            ratio = jnp.ones_like(ratio)
        r = ratio[seg]
        return p - self.lr * r * u, _LambState(
            count=count, m=m, v=v, master=state.master
        )
