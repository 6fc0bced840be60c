"""`apex_tpu.train.Trainer` — one composable 3D-parallel train step.

The TorchTitan shape (PAPERS.md): a production-default trainer that
composes the framework's parallelisms from ONE declarative config
instead of asking the user to hand-wire DDP, ZeRO, TP and the comm
engine.  ``Trainer(config).build(loss_fn, params, example_batch)``
returns a compiled, donation-aliased SPMD step over a ``(dp, tp)``
mesh with:

- params placed by the config's regex→PartitionSpec rule table (the
  ``fmengine`` idiom, resolved through
  :func:`apex_tpu.analysis.match_partition_rules` so a leaf no rule
  covers fails the build naming the path);
- the gradient sync routed through the shared comm engine
  (:mod:`apex_tpu.parallel.comm` — ``wire=``/``chunks=`` exactly as
  ``docs/comm.md`` defines them);
- the weight update **sharded across dp replicas when the framework's
  heuristic says it pays** (:func:`apex_tpu.train.sharding
  .decide_update_sharding` — "Automatic Cross-Replica Sharding of
  Weight Update in Data-Parallel Training", PAPERS.md; the ZeRO
  machinery of :mod:`apex_tpu.parallel.distributed_fused_optimizers`),
  overridable via ``update_sharding=``;
- a :class:`~apex_tpu.observability.MetricRegistry` fold INSIDE the
  jitted step (no per-step host sync) and a
  :meth:`TrainStep.fit` loop riding
  :func:`apex_tpu.resilience.run_resilient` with goodput accounting
  and the flight recorder armable from the environment.

**Self-verifying builds.**  At build time the trainer runs
:func:`apex_tpu.analysis.check` over the compiled step with
``expect_sharding``/``expect_plan``/``hbm_budget`` DERIVED FROM ITS OWN
CONFIG — the same rule table that built ``in_specs``, the same
:func:`comm.sync_plan`/:func:`comm.zero_plan` arithmetic the traced
sync uses, plus the model's declared collectives.  A trainer that
compiles an unplanned collective, a replicated-but-should-be-sharded
param, or a step over the HBM budget raises
:class:`TrainBuildError` before handing out the step
(``verify="warn"`` demotes to a printed report, ``"off"`` skips).

See ``docs/training.md`` for the config reference and worked examples.
"""

from __future__ import annotations

import sys
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu._tree_util import to_f32
from apex_tpu.parallel import comm
from apex_tpu.train.config import TrainConfig
from apex_tpu.train import sharding as tsh
from apex_tpu.train.sharding import ZERO_TWINS  # noqa: F401 (re-export)

__all__ = ["Trainer", "TrainStep", "TrainBuildError", "ZERO_TWINS"]

_DP = "dp"
_TP = "tp"


class TrainBuildError(RuntimeError):
    """A trainer build that failed its own static verification (or its
    config could not be realized on the visible devices)."""


def _tree_map(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


def _replicated_specs(tree):
    return _tree_map(lambda _: P(), tree)


class Trainer:
    """Build compiled 3D-parallel train steps from a
    :class:`~apex_tpu.train.TrainConfig`."""

    def __init__(self, config: TrainConfig):
        self.config = config

    # -- mesh -----------------------------------------------------------
    def mesh(self) -> Mesh:
        cfg = self.config
        need = cfg.dp * cfg.tp
        devices = list(cfg.devices) if cfg.devices else jax.devices()
        if len(devices) < need:
            raise TrainBuildError(
                f"mesh {cfg.mesh_dict()} needs {need} devices, only "
                f"{len(devices)} visible (CPU: XLA_FLAGS="
                "--xla_force_host_platform_device_count=8 mocks a mesh)"
            )
        grid = np.asarray(devices[:need]).reshape(cfg.dp, cfg.tp)
        return Mesh(grid, (_DP, _TP))

    # -- optimizer resolution -------------------------------------------
    # One optimizer_kwargs vocabulary serves BOTH realizations: the
    # replicated optax factories spell the moments beta1=/beta2=, the
    # distributed twins betas=(b1, b2) — translated here, because the
    # update-sharding heuristic may flip a config between the two modes
    # just by the model growing past the floor, and a config that was
    # valid in one mode must stay valid in the other.

    def _replicated_tx(self):
        cfg = self.config
        name = cfg.optimizer_name()
        if name is None:
            return cfg.optimizer
        from apex_tpu import optimizers

        kwargs = dict(cfg.optimizer_kwargs)
        if "betas" in kwargs:
            kwargs["beta1"], kwargs["beta2"] = kwargs.pop("betas")
        factory = optimizers.by_name(name)
        return factory(learning_rate=cfg.learning_rate, **kwargs)

    def _distributed_tx(self):
        cfg = self.config
        from apex_tpu.parallel import (
            DistributedFusedAdam,
            DistributedFusedLAMB,
        )

        cls = {"adam": DistributedFusedAdam, "lamb": DistributedFusedLAMB}[
            cfg.optimizer_name()
        ]
        kwargs = dict(cfg.optimizer_kwargs)
        if "beta1" in kwargs or "beta2" in kwargs:
            kwargs["betas"] = (
                kwargs.pop("beta1", 0.9), kwargs.pop("beta2", 0.999),
            )
        return cls(
            lr=cfg.learning_rate,
            axis_name=_DP,
            wire=cfg.wire,
            param_wire=cfg.param_wire,
            chunks=cfg.chunks,
            block=cfg.block,
            **kwargs,
        )

    # -- the build ------------------------------------------------------
    def build(
        self,
        loss_fn: Callable[[Any, Any], Any],
        params,
        example_batch,
        *,
        name: str = "train",
    ) -> "TrainStep":
        """Compose, compile, and verify the step.  ``loss_fn(params,
        batch) -> scalar`` is traced INSIDE ``shard_map`` over the
        ``(dp, tp)`` mesh: params arrive as their local shards per the
        rule table, the batch as its dp slice; tensor-parallel
        collectives inside the model (``apex_tpu.transformer
        .tensor_parallel``) bind the ``tp`` axis.  ``params`` and
        ``example_batch`` are GLOBAL host trees."""
        cfg = self.config
        mesh = self.mesh()
        mesh_dict = cfg.mesh_dict()

        try:
            param_specs = tsh.resolve_param_specs(cfg.rules, params)
        except ValueError as e:
            raise TrainBuildError(str(e)) from e
        batch_specs = tsh.resolve_batch_specs(cfg.batch_rules,
                                              example_batch)
        decision = tsh.decide_update_sharding(params, cfg, param_specs)
        if decision.shard and cfg.track_grad_norm and cfg.tp > 1:
            raise TrainBuildError(
                "track_grad_norm with a tp axis needs the replicated "
                "update path (the ZeRO flat buffer duplicates "
                "tp-replicated leaves across groups, so a flat-shard "
                "norm would overcount them): set "
                "update_sharding='replicate' or drop track_grad_norm"
            )

        # local (per-device) param template — the dp sync moves these
        local_template = _tree_map(
            lambda l, s: jax.ShapeDtypeStruct(
                tsh.local_shape(l.shape, s, mesh_dict), l.dtype
            ),
            params, param_specs,
        )
        spec_leaves = tsh._spec_leaves(param_specs, params)
        tp_varying = [
            any(
                _TP in [n for n in (
                    (e if isinstance(e, (tuple, list)) else (e,))
                ) if n is not None]
                for e in (tuple(s) if s is not None else ())
            )
            for s in spec_leaves
        ]

        registry = None
        if cfg.metrics:
            from apex_tpu import observability as obs

            registry = obs.MetricRegistry(fetch_every=cfg.fetch_every)
            registry.gauge("train/loss", unit="loss")
            if cfg.track_grad_norm:
                registry.gauge("train/grad_norm")

        if decision.shard:
            dist = self._distributed_tx()
            state, state_specs, body = self._build_zero(
                loss_fn, params, param_specs, local_template, dist,
                registry, tp_varying, mesh_dict,
            )
            plan_entries = comm.zero_plan(
                dist.spec.flat_size, cfg.dp, _DP,
                wire=cfg.wire, param_wire=cfg.param_wire,
                chunks=cfg.chunks, block=cfg.block,
            )
            tx = dist
        else:
            tx = self._replicated_tx()
            state, state_specs, body = self._build_ddp(
                loss_fn, params, param_specs, tx, registry, tp_varying,
            )
            local_sizes = [
                int(np.prod(t.shape) or 1)
                for t in jax.tree_util.tree_leaves(local_template)
            ]
            plan_entries = comm.sync_plan(
                local_sizes, cfg.dp, _DP,
                wire=cfg.wire, chunks=cfg.chunks, block=cfg.block,
                min_size=cfg.min_sync_size,
            )

        expect_plan = {
            "mesh": mesh_dict,
            "collectives": list(plan_entries) + list(
                cfg.model_collectives
            ),
            "allow_unplanned_bytes": cfg.unplanned_tolerance,
        }
        expect_sharding = {
            "mesh": mesh_dict,
            "rules": tsh.exact_entry_rules([
                ("state", state, state_specs),
                ("batch", example_batch, batch_specs),
            ]),
            "min_bytes": cfg.min_shard_bytes,
        }

        aux_specs = {"loss": P()}
        if cfg.track_grad_norm:
            aux_specs["grad_norm"] = P()
        if registry is not None:
            # the metric fold rides the AUX output, not the carried
            # state: every gauge is recomputed per step, so folding it
            # into a donated state would leave a dead (never-aliased)
            # input behind — the build's own donation lint catches
            # exactly that
            aux_specs["metrics"] = _replicated_specs(registry.init())

        smapped = jax.shard_map(
            body, mesh=mesh,
            in_specs=(state_specs, batch_specs),
            out_specs=(state_specs, aux_specs),
            check_vma=False,
        )
        jitted = jax.jit(smapped, donate_argnums=(0,))

        step = TrainStep(
            trainer=self, name=name, mesh=mesh, step=jitted,
            state=state, state_specs=state_specs,
            batch_specs=batch_specs, registry=registry,
            decision=decision, expect_sharding=expect_sharding,
            expect_plan=expect_plan, example_batch=example_batch,
            loss_fn=loss_fn, tx=tx,
        )
        if cfg.verify != "off":
            step.report = step.verify(example_batch)
            errors = step.report.errors()
            if errors and cfg.verify == "error":
                raise TrainBuildError(
                    "trainer build failed its own verification "
                    f"({len(errors)} ERROR finding(s)):\n"
                    + step.report.render()
                )
            if step.report.findings and cfg.verify == "warn":
                print(step.report.render(), file=sys.stderr)
        return step

    def build_guarded(self, loss_fn, params, **kwargs):
        """The two-phase guarded-amp shape (grads program + update
        program with a host boundary between them) — see
        :func:`apex_tpu.train.guarded.build_guarded`."""
        from apex_tpu.train.guarded import build_guarded

        return build_guarded(self, loss_fn, params, **kwargs)

    # -- ddp / replicated-update composition ---------------------------
    def _build_ddp(self, loss_fn, params, param_specs, tx, registry,
                   tp_varying):
        cfg = self.config
        dp = cfg.dp
        opt_state = tx.init(params)
        opt_specs = tsh.mirror_optimizer_specs(
            opt_state, params, param_specs
        )
        state = {"params": params, "opt": opt_state}
        state_specs = {"params": param_specs, "opt": opt_specs}

        def body(state, batch):
            params = state["params"]
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            if dp > 1:
                grads = comm.sync_gradients(
                    grads, _DP, wire=cfg.wire, chunks=cfg.chunks,
                    block=cfg.block, min_size=cfg.min_sync_size,
                )
                loss = jax.lax.pmean(loss, _DP)
            aux = {"loss": loss}
            if cfg.track_grad_norm:
                aux["grad_norm"] = _global_grad_norm(
                    grads, tp_varying, cfg.tp
                )
            updates, new_opt = tx.update(grads, state["opt"], params)
            new_params = _tree_map(
                lambda p, u: p + u.astype(p.dtype), params, updates
            )
            new_state = {"params": new_params, "opt": new_opt}
            if registry is not None:
                folded = {"train/loss": loss}
                if cfg.track_grad_norm:
                    folded["train/grad_norm"] = aux["grad_norm"]
                aux["metrics"] = registry.update(registry.init(), folded)
            return new_state, aux

        return state, state_specs, body

    # -- zero / sharded-update composition ------------------------------
    def _build_zero(self, loss_fn, params, param_specs, local_template,
                    dist, registry, tp_varying, mesh_dict):
        cfg = self.config
        tp = cfg.tp
        # the distributed optimizer's flat spec is built on the LOCAL
        # (tp-sharded) tree: reduce-scatter/all-gather then run per tp
        # group automatically inside the (dp, tp) shard_map
        zeros_local = _tree_map(
            lambda t: jnp.zeros(t.shape, t.dtype), local_template
        )
        st0 = dist.init(zeros_local, world=cfg.dp)
        fspec = dist.spec

        # master shards: tp rank t owns segment t of the concatenated
        # flat state — spec P(("tp", "dp")) tiles tp-major, dp-minor,
        # exactly the (dp, tp) device grid's owner layout
        flats = []
        for t in range(tp):
            local = _tree_map(
                lambda l, s: tsh.slice_local(l, s, _TP, t, tp),
                params, param_specs,
            )
            flat, _ = ravel_pytree(to_f32(local))
            flats.append(jnp.pad(
                flat, (0, fspec.padded_size - fspec.flat_size)
            ))
        master = jnp.concatenate(flats) if tp > 1 else flats[0]
        if tp > 1:
            zeros = jnp.zeros((tp * fspec.padded_size,), jnp.float32)
            opt_state = st0._replace(m=zeros, v=zeros, master=master)
        else:
            opt_state = st0._replace(master=master)
        flat_spec = P((_TP, _DP)) if tp > 1 else P(_DP)
        opt_specs = _tree_map(
            lambda x: flat_spec if getattr(x, "ndim", 0) == 1 else P(),
            opt_state,
        )

        state = {"params": params, "opt": opt_state}
        state_specs = {"params": param_specs, "opt": opt_specs}

        def body(state, batch):
            params = state["params"]
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            loss = jax.lax.pmean(loss, _DP)
            aux = {"loss": loss}
            if cfg.track_grad_norm:
                # exact: the reduce-scattered shards partition the flat
                # buffer (build() rejects track_grad_norm + tp>1, where
                # duplicated replicated leaves would overcount)
                new_params, new_opt, gnorm = dist.update_with_norm(
                    grads, state["opt"], params
                )
                aux["grad_norm"] = gnorm
            else:
                new_params, new_opt = dist.update_inside_shard_map(
                    grads, state["opt"], params
                )
            new_state = {"params": new_params, "opt": new_opt}
            if registry is not None:
                folded = {"train/loss": loss}
                if cfg.track_grad_norm:
                    folded["train/grad_norm"] = aux["grad_norm"]
                aux["metrics"] = registry.update(registry.init(), folded)
            return new_state, aux

        return state, state_specs, body


def _global_grad_norm(grads, tp_varying, tp: int):
    """Global L2 norm of a dp-synced gradient tree whose leaves may be
    tp-sharded: tp-sharded partial square-sums psum over ``tp``,
    replicated leaves count once."""
    leaves = jax.tree_util.tree_leaves(grads)
    sq_rep = sum(
        (jnp.sum(jnp.square(l.astype(jnp.float32)))
         for l, v in zip(leaves, tp_varying) if not v),
        jnp.float32(0),
    )
    sq_tp = sum(
        (jnp.sum(jnp.square(l.astype(jnp.float32)))
         for l, v in zip(leaves, tp_varying) if v),
        jnp.float32(0),
    )
    if tp > 1 and any(tp_varying):
        sq_tp = jax.lax.psum(sq_tp, _TP)
    return jnp.sqrt(sq_rep + sq_tp)


class TrainStep:
    """A built trainer step: the compiled program plus everything the
    verification and run layers need (state template, declared plans,
    registry, the build's lint report)."""

    def __init__(self, *, trainer, name, mesh, step, state, state_specs,
                 batch_specs, registry, decision, expect_sharding,
                 expect_plan, example_batch, loss_fn, tx):
        self.trainer = trainer
        self.config = trainer.config
        self.name = name
        self.mesh = mesh
        self.step = step
        self.state = state
        self.state_specs = state_specs
        self.batch_specs = batch_specs
        self.registry = registry
        self.decision = decision
        self.expect_sharding = expect_sharding
        self.expect_plan = expect_plan
        self.example_batch = example_batch
        self.loss_fn = loss_fn
        self.tx = tx
        self.report = None
        self.goodput = None

    def __call__(self, state, batch):
        return self.step(state, batch)

    @property
    def mode(self) -> str:
        """``"zero"`` (update sharded across dp) or ``"ddp"``."""
        return self.decision.mode

    def collective_plan(self) -> dict:
        """The per-mesh-axis plan this step promises — the
        ``analysis.sharding.reshard_pass`` schema; also what the build
        verified the compiled HLO against."""
        return self.expect_plan

    def place(self, state):
        """Re-place a state tree onto the trainer's mesh per its specs
        — needed after a checkpoint restore, which commits arrays to a
        single device; already-conformant arrays pass through without
        a copy."""
        from jax.sharding import NamedSharding

        shardings = _tree_map(
            lambda spec: NamedSharding(self.mesh, spec), self.state_specs
        )
        return _tree_map(
            lambda x, s: jax.device_put(x, s), state, shardings
        )

    def n_params(self) -> int:
        return sum(
            int(p.size)
            for p in jax.tree_util.tree_leaves(self.state["params"])
        )

    def tokens_per_step(self) -> int:
        leaves = jax.tree_util.tree_leaves(self.example_batch)
        return int(leaves[0].shape[0]) if leaves else 0

    # -- verification ----------------------------------------------------
    def verify(self, batch=None, *, hbm_budget=None):
        """Run the full analysis suite over THIS compiled step against
        the trainer's own derived expectations; returns the
        :class:`apex_tpu.analysis.Report` with the shard-plan/memory
        sections attached (what ``tools/shard_report.py --target
        train`` renders)."""
        from apex_tpu import analysis

        batch = batch if batch is not None else self.example_batch
        budget = (
            hbm_budget if hbm_budget is not None
            else self.config.hbm_budget
        )
        report = analysis.check(
            self.step, self.state, batch,
            donate_argnums=(0,),
            expect_sharding=self.expect_sharding,
            expect_plan=self.expect_plan,
            hbm_budget=budget,
            name=f"{self.name}/{self.mode}",
        )
        analysis.attach_shard_sections(
            report, [(f"{self.name}/{self.mode}", report.hlo_text)],
            expect_sharding=self.expect_sharding,
        )
        return report

    # -- the composed run loop ------------------------------------------
    def fit(
        self,
        batch_fn: Callable[[int], Any],
        num_steps: int,
        *,
        directory,
        save_interval_steps: int = 10,
        max_to_keep: int = 3,
        observer: Any = None,
        flight: Any = None,
        reporter: Any = None,
        report_every: int = 10,
        checkpoint: str = "async",
    ):
        """Drive the step with the production defaults wired in:
        :func:`apex_tpu.resilience.run_resilient` (auto-resume,
        SIGTERM-safe, checkpoint retries), a
        :class:`~apex_tpu.observability.GoodputAccountant` on the
        observer stream, a :class:`~apex_tpu.observability.StepMeter`,
        and a flight recorder armable via ``APEX_TPU_FLIGHT``
        (``flight=`` to pass one explicitly).  ``checkpoint="async"``
        (default) saves through the zero-stall
        :class:`~apex_tpu.goodput.AsyncCheckpointEngine` — host
        snapshot on the step path, background write, drain at
        shutdown (docs/goodput.md); ``"sync"`` keeps the orbax
        manager inline.  Returns the
        :class:`~apex_tpu.resilience.runner.RunResult`; the goodput
        ledger lands on ``self.goodput``."""
        from apex_tpu import observability as obs
        from apex_tpu.resilience import ObserverFanout, run_resilient

        tokens = self.tokens_per_step()
        meter = obs.StepMeter(
            tokens_per_step=tokens,
            flops_per_step=obs.transformer_train_flops(
                self.n_params(), tokens
            ),
            devices=self.mesh.devices.flat,
        )
        goodput = obs.GoodputAccountant()
        self.goodput = goodput
        registry = self.registry
        counter = {"step": 0}

        def step_fn(state, batch):
            # a restore (auto-resume / rollback) hands back arrays
            # committed to one device; re-place them on the mesh
            new_state, aux = self.step(self.place(state), batch)
            counter["step"] += 1
            if registry is not None:
                registry.observe(counter["step"], aux["metrics"])
            meter.tick()
            if reporter is not None and (
                counter["step"] % report_every == 0
            ):
                reporter.report(counter["step"])
            return new_state, {"skipped": False, "loss": aux["loss"]}

        return run_resilient(
            step_fn,
            self.state,
            batch_fn,
            directory=directory,
            num_steps=num_steps,
            save_interval_steps=save_interval_steps,
            max_to_keep=max_to_keep,
            observer=ObserverFanout([goodput, observer]),
            flight=flight,
            checkpoint=checkpoint,
        )
