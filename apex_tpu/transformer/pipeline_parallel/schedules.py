"""Pipeline fwd/bwd schedules — ≙ apex/transformer/pipeline_parallel/
schedules/ (``forward_backward_no_pipelining``,
``forward_backward_pipelining_without_interleaving`` [1F1B],
``_forward_backward_pipelining_with_interleaving`` [virtual stages],
dispatcher ``get_forward_backward_func``).

Design (TPU-native, not a translation).  The reference hand-schedules
warmup/steady/cooldown phases of explicit forward and backward calls with
NCCL p2p edges per microbatch.  Under XLA the whole pipeline is **one
traced program**: activations advance one stage per tick through
``jax.lax.ppermute`` along the ``pp`` axis (lockstep), the tick loop is a
``lax.scan``, and the backward schedule *falls out of ``jax.grad``* —
XLA reverses the scan and the ppermutes, yielding the cooldown-mirrored
grad flow without hand-scheduling.  Memory behavior equivalent to 1F1B's
bounded live-activation window comes from rematerialization: each tick's
stage compute is wrapped in ``jax.checkpoint`` (``remat=True``), so the
backward recomputes per-tick activations instead of keeping all
``nm + pp - 1`` of them live.

Uniform-stage contract (SPMD): every pp rank runs the same
``stage_fn(stage_params, x) -> y`` with activation-shaped ``x`` and ``y``
(first-stage embedding / last-stage head live inside ``stage_fn`` gated on
:func:`parallel_state.get_pipeline_model_parallel_rank`, or outside the
pipeline).  ``loss_fn(y, target) -> scalar`` is evaluated on the last
stage; it must return finite values for arbitrary finite activations (it
is traced on every stage and masked).  With ``loss_takes_params=True``
the signature becomes ``loss_fn(stage_params, y, target)`` — ≙ Megatron's
post-process rank computing the loss THROUGH the output layer: the head
(e.g. a tied unembedding) lives in the uniform per-rank param tree and
receives gradients via the loss; see ``examples/gpt/train_gpt_pp.py``.

All schedules share one signature and return ``(losses, grads)`` where
``losses`` is the per-microbatch loss vector (psum-shared across pp) and
``grads`` matches ``params`` (``None`` when ``forward_only``).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu import parallel_state as ps
from apex_tpu.transformer.pipeline_parallel import p2p_communication as p2p

__all__ = [
    "forward_backward_no_pipelining",
    "forward_backward_pipelining_without_interleaving",
    "forward_backward_pipelining_1f1b",
    "forward_backward_pipelining_with_interleaving",
    "forward_backward_pipelining_interleaved_1f1b",
    "get_forward_backward_func",
]

_PP = ps.PIPELINE_PARALLEL_AXIS

# checkpoint_name tags the "sums" named-saves policy selects.  Defined in
# infra (models import it — apex_tpu.models.{bert,gpt} tag these in their
# layers) so the model layer depends on the schedule layer, never the
# reverse.  A stage whose model carries none of these tags saves nothing
# under "sums" (= "full" behavior, same values).
SUMS_SAVE_NAMES = (
    "bert_qkv", "bert_fc1", "bert_sum_attn", "bert_sum_mlp",
    "gpt_qkv", "gpt_fc1", "gpt_sum_attn", "gpt_sum_mlp",
)


def resolve_remat_policy(name):
    """The ONE full/dots/sums -> jax.checkpoint policy resolution, shared
    by the models (BertConfig/GptConfig remat_policy) and the pipeline
    schedules' per-tick wrap.  ``None``/"full" -> recompute everything
    (policy None); "dots" -> save no-batch-dim matmul outputs; "sums" ->
    save only the :data:`SUMS_SAVE_NAMES` tags."""
    if name in (None, "full"):
        return None
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if name == "sums":
        return jax.checkpoint_policies.save_only_these_names(
            *SUMS_SAVE_NAMES
        )
    raise ValueError(f"unknown remat_policy {name!r}")


def _wrap_remat(fn, remat, remat_policy=None):
    """Per-tick stage checkpoint.  ``remat_policy``: None = recompute
    everything (min memory); "dots" = save no-batch-dim matmul outputs
    and recompute only elementwise/attention internals (the models'
    selective-recompute default — ~4/3 → ~1.0 of the fwd+bwd premium
    for a modest memory bump); "sums" = save only the checkpoint_name
    tags the BERT layers mark (qkv/fc1/residual sums — epilogue-fusion
    friendly, see BertConfig.remat_policy).  A stage whose model carries
    no tags saves nothing under "sums" (= "full" behavior, same values)."""
    if not remat:
        return fn
    if remat_policy == "dots":
        # the schedules' historical "dots" is checkpoint_dots (saves all
        # matmul outputs), intentionally broader than the models'
        # no-batch-dim variant — per-tick stages see one microbatch
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots
        )
    policy = resolve_remat_policy(remat_policy)
    if policy is None:
        return jax.checkpoint(fn)
    return jax.checkpoint(fn, policy=policy)


# ---------------------------------------------------------------------------
# no pipelining: sequential microbatches with grad accumulation
# ---------------------------------------------------------------------------


def forward_backward_no_pipelining(
    stage_fn: Callable,
    loss_fn: Callable,
    params,
    batch: Tuple[Any, Any],
    *,
    num_microbatches: int,
    axis_name: str = _PP,
    forward_only: bool = False,
    remat: bool = False,
    remat_policy=None,
    loss_takes_params: bool = False,
):
    """≙ fwd_bwd_no_pipelining.py — scan microbatches, accumulate grads."""
    inputs, targets = batch
    run = _wrap_remat(stage_fn, remat, remat_policy)
    lfn = loss_fn if loss_takes_params else (lambda p, y, t: loss_fn(y, t))

    def mean_loss(params):
        def body(carry, mb):
            x, t = mb
            loss = lfn(params, run(params, x), t)
            return carry + loss, loss

        total, losses = jax.lax.scan(
            body, jnp.zeros((), jnp.float32), (inputs, targets)
        )
        return total / num_microbatches, losses

    if forward_only:
        _, losses = mean_loss(params)
        return losses, None
    (_, losses), grads = jax.value_and_grad(mean_loss, has_aux=True)(params)
    return losses, grads


# ---------------------------------------------------------------------------
# 1F1B (non-interleaved): lockstep tick loop over the pp axis
# ---------------------------------------------------------------------------


def forward_backward_pipelining_without_interleaving(
    stage_fn: Callable,
    loss_fn: Callable,
    params,
    batch: Tuple[Any, Any],
    *,
    num_microbatches: int,
    axis_name: str = _PP,
    forward_only: bool = False,
    remat: bool = True,
    remat_policy=None,
    carry_chunk: Optional[int] = None,
    loss_takes_params: bool = False,
):
    """≙ fwd_bwd_pipelining_without_interleaving.py (1F1B).

    ``params`` are *this rank's stage* params (call inside shard_map with
    e.g. a ``P('pp', ...)``-sharded stacked tree).  ``batch = (inputs,
    targets)`` with leaves stacked ``(num_microbatches, ...)``; ``inputs``
    must be activation-shaped (consumed by stage 0).

    ``carry_chunk=K`` bounds the backward's saved scan carries for large
    grad-accumulation ``nm`` (docs/pipeline-schedules.md's measured O(nm)
    slope): the tick loop becomes a two-level scan whose outer body is
    ``jax.checkpoint``-ed, so only the ~ticks/K chunk-boundary carries are
    saved and each chunk's K inner carries are recomputed during backward
    — O(ticks/K + K) live carries (minimal at K ≈ √ticks) for one extra
    forward recompute per tick.  Ticks are padded up to a K multiple;
    padded ticks compute masked garbage exactly like bubble ticks.
    """
    inputs, targets = batch
    nm = num_microbatches
    run = _wrap_remat(stage_fn, remat, remat_policy)
    lfn = loss_fn if loss_takes_params else (lambda p, y, t: loss_fn(y, t))

    def pipeline_loss(params):
        pp = jax.lax.axis_size(axis_name)
        stage = jax.lax.axis_index(axis_name)
        is_first = stage == 0
        is_last = stage == pp - 1
        ticks = nm + pp - 1
        h0 = jax.tree_util.tree_map(lambda x: jnp.zeros_like(x[0]), inputs)

        def tick(carry, t):
            h_recv, losses = carry
            mb_idx = jnp.clip(t, 0, nm - 1)
            inject = jax.tree_util.tree_map(lambda x: x[mb_idx], inputs)
            x_in = jax.tree_util.tree_map(
                lambda a, b: jnp.where(is_first, a, b), inject, h_recv
            )
            y = run(params, x_in)
            out_idx = t - (pp - 1)
            valid = (out_idx >= 0) & (out_idx < nm) & is_last
            tgt = jax.tree_util.tree_map(
                lambda x: x[jnp.clip(out_idx, 0, nm - 1)], targets
            )
            loss = lfn(params, y, tgt)
            losses = losses.at[jnp.clip(out_idx, 0, nm - 1)].add(
                jnp.where(valid, loss, 0.0)
            )
            h_next = p2p.send_forward_recv_forward(y, axis_name)
            return (h_next, losses), None

        carry0 = (h0, jnp.zeros((nm,), jnp.float32))
        if carry_chunk and carry_chunk > 0:
            k = min(carry_chunk, ticks)
            n_outer = -(-ticks // k)  # ceil; padded ticks are masked no-ops
            ts = jnp.arange(n_outer * k).reshape(n_outer, k)

            @jax.checkpoint
            def outer(carry, ts_chunk):
                carry, _ = jax.lax.scan(tick, carry, ts_chunk)
                return carry, None

            (_, losses), _ = jax.lax.scan(outer, carry0, ts)
        else:
            (_, losses), _ = jax.lax.scan(tick, carry0, jnp.arange(ticks))
        # Differentiate the LOCAL loss sum (nonzero only on the last stage):
        # grads reach earlier stages through the reversed ppermutes.  Do NOT
        # psum the differentiated scalar — under check_vma=False the psum
        # transpose cannot prove the cotangent replicated and would re-psum,
        # inflating grads by pp.  The shared per-microbatch losses are
        # returned via aux (not differentiated), psum'd for reporting.
        return jnp.sum(losses) / nm, jax.lax.psum(losses, axis_name)

    if forward_only:
        _, losses = pipeline_loss(params)
        return losses, None
    (_, losses), grads = jax.value_and_grad(pipeline_loss, has_aux=True)(
        params
    )
    return losses, grads


# ---------------------------------------------------------------------------
# hand-scheduled 1F1B: explicit O(pp) stash ring, manually reversed permutes
# ---------------------------------------------------------------------------


def forward_backward_pipelining_1f1b(
    stage_fn: Callable,
    loss_fn: Callable,
    params,
    batch: Tuple[Any, Any],
    *,
    num_microbatches: int,
    axis_name: str = _PP,
    forward_only: bool = False,
    stash: str = "residuals",
    remat: bool = False,
    remat_policy=None,
    loss_takes_params: bool = False,
):
    """True 1F1B with a bounded activation window and NO dependence on
    ``jax.grad`` over the tick loop — ≙ the reference's
    ``forward_backward_pipelining_without_interleaving`` memory/compute
    point (SURVEY §3.5: ≤pp in-flight activations, no recompute).

    Where :func:`forward_backward_pipelining_without_interleaving`
    differentiates a lockstep scan (backward falls out of autodiff, at
    the price of either per-tick rematerialization or O(nm) saved scan
    carries), this schedule computes gradients INSIDE a single forward
    scan: each tick runs one stage forward AND one stage backward on
    different microbatches, per-microbatch vjp residuals live in an
    explicit ring buffer, and cotangents ride a manually reversed
    ``ppermute`` (``send_backward_recv_backward``).  Nothing about the
    loop is differentiated, so nm-proportional autodiff memory never
    exists.

    Timetable (lockstep SPMD — every rank runs the same program; bubble
    slots compute masked garbage): stage ``s`` forwards microbatch ``m``
    at tick ``m + s`` and backwards it at tick ``2(pp-1) - s + m``;
    total ticks ``nm + 2(pp-1)`` (vs ``nm + pp - 1`` per direction for
    the lockstep scan — the steady state overlaps one fwd with one bwd
    per tick exactly like the reference's 1F1B).  The in-flight window
    on stage ``s`` is ``2(pp-1-s) + 1 <= 2pp - 1``: the lockstep
    round-trip bound (the reference's asynchronous ranks reach ``pp - s``
    by backpressure instead of clock; both are O(pp), independent of nm).

    ``stash`` selects what the ring holds:

    * ``"residuals"`` (default) — the stage vjp's residuals, so backward
      replays NOTHING: the no-recompute-premium point.  Residual leaves
      that are parameter passthroughs (detected by tracer identity) are
      NOT ring-stashed — they are loop-invariant and read from a single
      copy, so ring memory is ~W x the stage's activation-derived
      residuals only.  Combine with ``remat_policy`` to bound residual
      size (policy-saved tensors + stage input become the residuals).
    * ``"input"`` — the ring holds only each microbatch's stage input;
      backward re-runs the stage forward under ``jax.vjp`` (the ~4/3
      recompute premium, minimal O(pp x |activation|) ring — strictly
      less memory than ``carry_chunk``'s O(sqrt(nm)) carries at equal
      compute).

    Same contract as the other schedules: call inside ``shard_map``,
    ``batch`` leaves stacked ``(num_microbatches, ...)``, returns
    ``(losses, grads)`` with ``losses`` psum-shared across pp.
    """
    if stash not in ("residuals", "input"):
        raise ValueError(f"unknown stash mode {stash!r}")
    inputs, targets = batch
    nm = num_microbatches
    run = _wrap_remat(stage_fn, remat, remat_policy)
    lfn = loss_fn if loss_takes_params else (lambda p, y, t: loss_fn(y, t))

    if forward_only:
        losses, _ = forward_backward_pipelining_without_interleaving(
            stage_fn, loss_fn, params, batch, num_microbatches=nm,
            axis_name=axis_name, forward_only=True, remat=False,
            loss_takes_params=loss_takes_params,
        )
        return losses, None

    pp = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    is_first = stage == 0
    is_last = stage == pp - 1
    ticks = nm + 2 * (pp - 1)
    window = 2 * (pp - 1) + 1
    tree = jax.tree_util

    h0 = tree.tree_map(lambda x: jnp.zeros_like(x[0]), inputs)

    def stage_vjp(p, x):
        return jax.vjp(lambda p_, x_: run(p_, x_), p, x)

    # Template vjp (traced once, outside the loop): fixes the residual
    # pytree structure, and partitions its leaves into parameter
    # passthroughs (loop-invariant — kept as a single closed-over copy)
    # vs activation-derived residuals (ring-stashed per in-flight mb).
    y_t, vjp_t = stage_vjp(params, h0)
    t_leaves, t_def = tree.tree_flatten(vjp_t)
    param_ids = {id(l) for l in tree.tree_leaves(params)}
    varying = [
        i for i, l in enumerate(t_leaves) if id(l) not in param_ids
    ]

    if stash == "residuals":
        ring0 = [
            jnp.zeros((window,) + t_leaves[i].shape, t_leaves[i].dtype)
            for i in varying
        ]
    else:
        ring0 = [
            jnp.zeros((window,) + l.shape, l.dtype)
            for l in tree.tree_leaves(h0)
        ]
    x_def = tree.tree_structure(h0)
    g0 = tree.tree_map(jnp.zeros_like, y_t)
    dp0 = tree.tree_map(jnp.zeros_like, params)

    def tick(carry, t):
        h_recv, g_recv, ring, dp_acc, losses = carry

        # ---- forward lane: stage s forwards microbatch t - s ----------
        mf = t - stage
        mf_c = jnp.clip(mf, 0, nm - 1)
        inject = tree.tree_map(lambda x: x[mf_c], inputs)
        x_in = tree.tree_map(
            lambda a, b: jnp.where(is_first, a, b), inject, h_recv
        )
        y, vjp_f = stage_vjp(params, x_in)
        # NOTE: the per-tick vjp treedef is NOT == t_def (each trace
        # wraps a fresh closure in the Partial's static part), but the
        # residual LEAVES line up one-to-one with the template's — that
        # is what the ring relies on, so pin it structurally.
        f_leaves, f_def = tree.tree_flatten(vjp_f)
        _check_vjp_leaf_shapes(
            f_leaves, [(l.shape, l.dtype) for l in t_leaves], "hand-1F1B"
        )
        # Explicit raise, not assert (same rationale as the helper):
        # guards a tracer-identity invariant a future JAX change could
        # break silently.
        if [
            i for i, l in enumerate(f_leaves) if id(l) not in param_ids
        ] != varying:
            raise RuntimeError(
                "hand-1F1B ring invariant violated: param-passthrough "
                "residual positions changed across ticks"
            )
        slot_f = t % window
        if stash == "residuals":
            ring = [
                r.at[slot_f].set(f_leaves[i])
                for r, i in zip(ring, varying)
            ]
        else:
            ring = [
                r.at[slot_f].set(l)
                for r, l in zip(ring, tree.tree_leaves(x_in))
            ]

        # ---- loss lane (last stage; same tick as its forward) ---------
        tgt = tree.tree_map(lambda x: x[mf_c], targets)
        (loss, (dhead, dy)) = _loss_and_head_grads(
            lfn, params, y, tgt, loss_takes_params
        )
        f_valid = (mf >= 0) & (mf < nm) & is_last
        losses = losses.at[mf_c].add(jnp.where(f_valid, loss, 0.0))
        wt = jnp.where(f_valid, 1.0 / nm, 0.0)
        # dy may be non-finite on bubble ticks (loss vjp over the garbage
        # chain) — safe, because every consumer SELECTS with where()
        # (is_last/b_valid below).  dhead is ACCUMULATED, so it needs a
        # select, not the wt multiply: NaN * 0 = NaN would poison dp_acc.
        dy = tree.tree_map(lambda g: g * wt, dy)
        if dhead is not None:
            dp_acc = tree.tree_map(
                lambda a, d: a + jnp.where(
                    f_valid, d * (1.0 / nm), jnp.zeros_like(d)
                ),
                dp_acc, dhead,
            )

        # ---- backward lane: stage s backwards mb t - 2(pp-1) + s ------
        mb = t - 2 * (pp - 1) + stage
        b_valid = (mb >= 0) & (mb < nm)
        mb_c = jnp.clip(mb, 0, nm - 1)
        slot_b = (mb_c + stage) % window  # = that mb's fwd tick mod W
        if stash == "residuals":
            # invariant (param-passthrough) positions reuse this tick's
            # own leaves — identical values every tick, never stashed
            leaves_b = list(f_leaves)
            for r, i in zip(ring, varying):
                leaves_b[i] = r[slot_b]
            vjp_b = tree.tree_unflatten(f_def, leaves_b)
        else:
            x_b = tree.tree_unflatten(x_def, [r[slot_b] for r in ring])
            _, vjp_b = stage_vjp(params, x_b)
        g_in = tree.tree_map(
            lambda a, b: jnp.where(is_last, a, b), dy, g_recv
        )
        g_in = tree.tree_map(
            lambda g: jnp.where(b_valid, g, jnp.zeros_like(g)), g_in
        )
        dp, dx = vjp_b(g_in)
        # A zero cotangent is NOT enough to null a bubble tick: a
        # never-written (zero) ring slot can make the vjp divide by a
        # stored statistic (0 * inf = NaN), so mask the OUTPUTS too.
        dp = tree.tree_map(
            lambda d: jnp.where(b_valid, d, jnp.zeros_like(d)), dp
        )
        dx = tree.tree_map(
            lambda d: jnp.where(b_valid, d, jnp.zeros_like(d)), dx
        )
        dp_acc = tree.tree_map(jnp.add, dp_acc, dp)

        # ---- edges: activations down, cotangents up -------------------
        h_next = p2p.send_forward_recv_forward(y, axis_name)
        g_next = p2p.send_backward_recv_backward(dx, axis_name)
        return (h_next, g_next, ring, dp_acc, losses), None

    carry0 = (h0, g0, ring0, dp0, jnp.zeros((nm,), jnp.float32))
    (_, _, _, grads, losses), _ = jax.lax.scan(
        tick, carry0, jnp.arange(ticks)
    )
    return jax.lax.psum(losses, axis_name), grads


def _check_vjp_leaf_shapes(f_leaves, expected_shapes, schedule_name):
    """Trace-time guard shared by the hand schedules' stash rings: the
    per-tick vjp's residual leaves must line up one-to-one with the
    template's.  Explicit raise (not assert) so it survives ``python
    -O``; free at execution time."""
    if [(l.shape, l.dtype) for l in f_leaves] != expected_shapes:
        raise RuntimeError(
            f"{schedule_name} ring invariant violated: vjp residual "
            "structure changed across ticks"
        )


def _loss_and_head_grads(lfn, params, y, tgt, loss_takes_params):
    """Loss value + its cotangents wrt (params-if-taken, y), unscaled."""
    if loss_takes_params:
        loss, dvjp = jax.vjp(lambda p, y_: lfn(p, y_, tgt), params, y)
        dhead, dy = dvjp(jnp.ones((), loss.dtype))
        return loss, (dhead, dy)
    loss, dvjp = jax.vjp(lambda y_: lfn(params, y_, tgt), y)
    (dy,) = dvjp(jnp.ones((), loss.dtype))
    return loss, (None, dy)


# ---------------------------------------------------------------------------
# hand-scheduled interleaved 1F1B: chunk-granular stash ring, three phases
# ---------------------------------------------------------------------------


def forward_backward_pipelining_interleaved_1f1b(
    stage_fn: Callable,
    loss_fn: Callable,
    params,
    batch: Tuple[Any, Any],
    *,
    num_microbatches: int,
    num_model_chunks: Optional[int] = None,
    axis_name: str = _PP,
    forward_only: bool = False,
    stash: str = "residuals",
    remat: bool = False,
    remat_policy=None,
    loss_takes_params: bool = False,
):
    """True interleaved (virtual-stage) 1F1B with an explicit chunk-stash
    ring and NO autodiff over the tick loop — ≙ the reference's
    ``_forward_backward_pipelining_with_interleaving`` memory/compute
    point (SURVEY §2.3, §3.5): bubble **(pp−1)/vpp** per direction with
    no recompute premium, in-flight stashes bounded independent of
    ``num_microbatches``.

    This extends :func:`forward_backward_pipelining_1f1b`'s machinery to
    model chunks.  ``params`` hold this rank's ``num_model_chunks`` stage
    chunks stacked on a leading axis (rank ``r`` owns virtual stages
    ``r, r+pp, …``, exactly like the lockstep interleaved schedule).  A
    tick is **chunk-granular** (1/vpp of a stage) and the program runs
    three lockstep phases so warmup/cooldown ticks never pay for a
    masked opposite-direction lane:

    * warmup — ``V−1`` fwd-only ticks (``V = pp·vpp``): the virtual pipe
      fills at one virtual stage per tick;
    * steady — ``nm·vpp + pp − V`` fwd+bwd ticks: each tick runs one
      chunk forward AND one chunk backward (on a different microbatch),
      the 1F1B overlap;
    * cooldown — ``V−1`` bwd-only ticks: the cotangent drains.

    Wall = ``(V−1)·t_f/vpp + (nm·vpp+pp−V)·(t_f+t_b)/vpp + (V−1)·t_b/vpp
    = nm·(t_f+t_b) + (pp−1)·(t_f+t_b)/vpp`` — the Megatron interleaving
    bubble exactly, vs ``2(pp−1)·(t_f+t_b)`` for the single-phase plain
    hand schedule (docs/pipeline-schedules.md has the derivation and the
    measured memory frontier).

    Timetable.  Forward: rank ``r`` runs chunk ``c`` of microbatch
    ``m = g·pp + j`` at tick ``t = g·pp·vpp + c·pp + j + r`` (Megatron's
    round-robin order — groups of ``pp`` microbatches per chunk).
    Backward mirrors at one virtual stage per tick:
    ``T_b(m,v) = T_f(m,V−1) + (V−1−v)`` for global virtual stage
    ``v = c·pp + r``, i.e. rank ``r`` backwards ``(c_b, m_b)`` at tick
    ``t`` where ``w = t + r − (V+pp−2)``, ``c_b = vpp−1 − (w mod V)//pp``,
    ``m_b = (w//V)·pp + (w mod pp)``.  Cotangents ride a **cyclic**
    reversed ppermute (rank 0 → pp−1 wraps to the previous chunk), the
    dual of the forward wrap.

    The stash ring has ``W = 2V−1`` chunk-granular slots (max in-flight
    span ``T_b−T_f = 2(V−1−v) ≤ W−1``): forward at tick ``t`` writes slot
    ``t mod W``; backward reads slot ``(t + 2·v_b + 1) mod W``.  Ring
    memory ≈ ``2V × (stage residuals / vpp) = 2pp × stage residuals`` —
    the SAME total as the plain hand schedule, and flat in ``nm``
    (matching Megatron interleaved's O(pp·vpp) in-flight chunk window).

    Chunk-param handling: the per-tick vjp is taken wrt the *sliced*
    chunk params, so residual leaves that are chunk-param passthroughs
    cannot be detected against the stacked tree by tracer identity the
    way the plain schedule does.  Instead the template trace records, for
    each passthrough residual position, WHICH chunk-param leaf flows
    through it; at backward time that position is re-materialized by
    dynamically indexing the backward tick's chunk — so weights are never
    ring-stashed.  Param-derived (non-passthrough) residuals are stashed
    per chunk, which is exactly what correctness requires (they were
    computed from that chunk's weights).

    ``stash``/``remat``/``remat_policy``/``loss_takes_params`` as in
    :func:`forward_backward_pipelining_1f1b`.  Requires
    ``num_microbatches % pp == 0`` (the reference's interleaving
    constraint).
    """
    if stash not in ("residuals", "input"):
        raise ValueError(f"unknown stash mode {stash!r}")
    inputs, targets = batch
    nm = num_microbatches
    if num_model_chunks is None:
        num_model_chunks = ps.get_virtual_pipeline_model_parallel_world_size()
    vpp = num_model_chunks
    if vpp is None or vpp < 1:
        raise ValueError("num_model_chunks (virtual pipeline size) required")
    run = _wrap_remat(stage_fn, remat, remat_policy)
    lfn = loss_fn if loss_takes_params else (lambda p, y, t: loss_fn(y, t))

    if forward_only:
        losses, _ = forward_backward_pipelining_with_interleaving(
            stage_fn, loss_fn, params, batch, num_microbatches=nm,
            num_model_chunks=vpp, axis_name=axis_name, forward_only=True,
            remat=False, loss_takes_params=loss_takes_params,
        )
        return losses, None

    pp = jax.lax.axis_size(axis_name)
    if nm % pp != 0:
        raise ValueError(
            f"interleaved schedule requires num_microbatches ({nm}) to "
            f"be a multiple of pipeline_parallel_size ({pp})"
        )
    stage = jax.lax.axis_index(axis_name)
    is_first = stage == 0
    is_last = stage == pp - 1
    V = pp * vpp           # virtual pipeline depth == round-robin cycle
    W = 2 * V - 1          # ring slots: max in-flight span + 1
    tree = jax.tree_util

    h0 = tree.tree_map(lambda x: jnp.zeros_like(x[0]), inputs)

    def chunk_at(idx):
        return tree.tree_map(
            lambda x: jax.lax.dynamic_index_in_dim(x, idx, 0, keepdims=False),
            params,
        )

    def stage_vjp(p, x):
        return jax.vjp(lambda p_, x_: run(p_, x_), p, x)

    # Template trace (outside the loop): pins the residual pytree
    # structure and maps each chunk-param passthrough residual position
    # to the chunk-param leaf that flows through it.
    chunk_t = tree.tree_map(lambda x: x[0], params)
    y_t, vjp_t = stage_vjp(chunk_t, h0)
    t_leaves, _ = tree.tree_flatten(vjp_t)
    cp_pos_t = {id(l): i for i, l in enumerate(tree.tree_leaves(chunk_t))}
    passthrough = {
        pos: cp_pos_t[id(l)]
        for pos, l in enumerate(t_leaves)
        if id(l) in cp_pos_t
    }
    varying = [p for p in range(len(t_leaves)) if p not in passthrough]
    t_shapes = [(l.shape, l.dtype) for l in t_leaves]

    def check_residual_contract(f_leaves, cp_leaves):
        _check_vjp_leaf_shapes(f_leaves, t_shapes, "interleaved hand-1F1B")
        # Explicit raise, not assert (same rationale as the helper):
        # guards the tracer-identity mapping the ring substitution
        # relies on.
        cp_pos = {id(l): i for i, l in enumerate(cp_leaves)}
        got = {
            pos: cp_pos[id(l)]
            for pos, l in enumerate(f_leaves)
            if id(l) in cp_pos
        }
        if got != passthrough:
            raise RuntimeError(
                "interleaved hand-1F1B ring invariant violated: "
                "chunk-param passthrough residual positions changed"
            )

    if stash == "residuals":
        ring0 = [
            jnp.zeros((W,) + t_leaves[i].shape, t_leaves[i].dtype)
            for i in varying
        ]
    else:
        ring0 = [
            jnp.zeros((W,) + l.shape, l.dtype)
            for l in tree.tree_leaves(h0)
        ]
    x_def = tree.tree_structure(h0)
    g0 = tree.tree_map(jnp.zeros_like, y_t)
    dp0 = tree.tree_map(jnp.zeros_like, params)

    def scatter_add(acc, d, idx):
        cur = jax.lax.dynamic_index_in_dim(acc, idx, 0, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(acc, cur + d, idx, 0)

    def make_tick(do_fwd, do_bwd):
        def tick(carry, t):
            h_recv, g_recv, ring, dp_acc, losses = carry
            dy = None
            f_pack = None

            if do_fwd:
                # ---- forward lane: chunk c_f of microbatch m_f ---------
                u = t - stage
                c_f = jnp.clip(jnp.mod(u, V) // pp, 0, vpp - 1)
                m_f = jnp.floor_divide(u, V) * pp + jnp.mod(u, pp)
                active_f = (u >= 0) & (u < nm * vpp)
                m_f_c = jnp.clip(m_f, 0, nm - 1)
                injecting = is_first & (c_f == 0) & active_f
                inject = tree.tree_map(lambda x: x[m_f_c], inputs)
                x_in = tree.tree_map(
                    lambda a, b: jnp.where(injecting, a, b), inject, h_recv
                )
                cp_f = chunk_at(c_f)
                y, vjp_f = stage_vjp(cp_f, x_in)
                f_leaves, f_def = tree.tree_flatten(vjp_f)
                check_residual_contract(f_leaves, tree.tree_leaves(cp_f))
                slot_f = jnp.mod(t, W)
                if stash == "residuals":
                    ring = [
                        r.at[slot_f].set(f_leaves[i])
                        for r, i in zip(ring, varying)
                    ]
                else:
                    ring = [
                        r.at[slot_f].set(l)
                        for r, l in zip(ring, tree.tree_leaves(x_in))
                    ]
                f_pack = (f_leaves, f_def)

                # ---- loss lane: last rank finishing its last chunk -----
                finishing = active_f & is_last & (c_f == vpp - 1)
                tgt = tree.tree_map(lambda x: x[m_f_c], targets)
                loss, (dhead, dy) = _loss_and_head_grads(
                    lfn, cp_f, y, tgt, loss_takes_params
                )
                losses = losses.at[m_f_c].add(
                    jnp.where(finishing, loss, 0.0)
                )
                wt = jnp.where(finishing, 1.0 / nm, 0.0)
                # dy may be non-finite on bubble ticks; every consumer
                # SELECTS with where() (finishing/active_b below).  dhead
                # is accumulated, so it needs a select, not the multiply.
                dy = tree.tree_map(lambda g: g * wt, dy)
                if dhead is not None:
                    dp_acc = tree.tree_map(
                        lambda a, d: scatter_add(
                            a,
                            jnp.where(
                                finishing, d * (1.0 / nm), jnp.zeros_like(d)
                            ),
                            c_f,
                        ),
                        dp_acc, dhead,
                    )
                h_next = p2p.send_forward_recv_forward(
                    y, axis_name, cyclic=True
                )
            else:
                h_next = h_recv

            if do_bwd:
                # ---- backward lane: mirror timetable -------------------
                w = t + stage - (V + pp - 2)
                active_b = (w >= 0) & (w < nm * vpp)
                c_b = jnp.clip(
                    vpp - 1 - jnp.mod(w, V) // pp, 0, vpp - 1
                )
                cp_b = chunk_at(c_b)
                v_b = c_b * pp + stage
                slot_b = jnp.mod(t + 2 * v_b + 1, W)
                if stash == "residuals":
                    if f_pack is not None:
                        leaves_b, f_def = list(f_pack[0]), f_pack[1]
                    else:
                        # cooldown: no forward lane this tick, so trace a
                        # dummy vjp purely for a fresh treedef — every
                        # residual leaf is substituted below, so the dummy
                        # forward is dead code and XLA DCEs it.
                        _, vjp_d = stage_vjp(cp_b, h0)
                        leaves_d, f_def = tree.tree_flatten(vjp_d)
                        check_residual_contract(
                            leaves_d, tree.tree_leaves(cp_b)
                        )
                        leaves_b = list(leaves_d)
                    # chunk-param passthroughs: re-materialize from the
                    # BACKWARD tick's chunk (never ring-stashed)
                    cpb_leaves = tree.tree_leaves(cp_b)
                    for pos, pidx in passthrough.items():
                        leaves_b[pos] = cpb_leaves[pidx]
                    for r, pos in zip(ring, varying):
                        leaves_b[pos] = r[slot_b]
                    vjp_b = tree.tree_unflatten(f_def, leaves_b)
                else:
                    x_b = tree.tree_unflatten(
                        x_def, [r[slot_b] for r in ring]
                    )
                    _, vjp_b = stage_vjp(cp_b, x_b)
                if do_fwd:
                    # rank pp−1 backwarding chunk vpp−1 consumes the dy
                    # its OWN forward lane produced this very tick
                    g_in = tree.tree_map(
                        lambda a, b: jnp.where(
                            is_last & (c_b == vpp - 1), a, b
                        ),
                        dy, g_recv,
                    )
                else:
                    g_in = g_recv
                g_in = tree.tree_map(
                    lambda g: jnp.where(active_b, g, jnp.zeros_like(g)),
                    g_in,
                )
                dp, dx = vjp_b(g_in)
                # Zero cotangent is NOT enough to null a bubble tick (a
                # zero ring slot can make the vjp emit 0*inf=NaN) — mask
                # the OUTPUTS too.
                dp = tree.tree_map(
                    lambda d: jnp.where(active_b, d, jnp.zeros_like(d)),
                    dp,
                )
                dx = tree.tree_map(
                    lambda d: jnp.where(active_b, d, jnp.zeros_like(d)),
                    dx,
                )
                dp_acc = tree.tree_map(
                    lambda a, d: scatter_add(a, d, c_b), dp_acc, dp
                )
                g_next = p2p.send_backward_recv_backward(
                    dx, axis_name, cyclic=True
                )
            else:
                g_next = g_recv

            return (h_next, g_next, ring, dp_acc, losses), None

        return tick

    total = nm * vpp + V + pp - 2
    b1 = V - 1               # warmup end: fwd-only ticks [0, b1)
    b2 = nm * vpp + pp - 1   # steady end: fwd+bwd ticks [b1, b2)
    carry = (h0, g0, ring0, dp0, jnp.zeros((nm,), jnp.float32))
    carry, _ = jax.lax.scan(
        make_tick(True, False), carry, jnp.arange(0, b1)
    )
    carry, _ = jax.lax.scan(
        make_tick(True, True), carry, jnp.arange(b1, b2)
    )
    carry, _ = jax.lax.scan(
        make_tick(False, True), carry, jnp.arange(b2, total)
    )
    _, _, _, grads, losses = carry
    return jax.lax.psum(losses, axis_name), grads


# ---------------------------------------------------------------------------
# interleaved 1F1B (virtual pipeline stages)
# ---------------------------------------------------------------------------


def forward_backward_pipelining_with_interleaving(
    stage_fn: Callable,
    loss_fn: Callable,
    params,
    batch: Tuple[Any, Any],
    *,
    num_microbatches: int,
    num_model_chunks: Optional[int] = None,
    axis_name: str = _PP,
    forward_only: bool = False,
    remat: bool = True,
    remat_policy=None,
    carry_chunk: Optional[int] = None,
    loss_takes_params: bool = False,
):
    """≙ fwd_bwd_pipelining_with_interleaving.py (virtual/interleaved 1F1B).

    ``params`` hold this rank's ``num_model_chunks`` stage chunks stacked
    on a leading axis (every leaf ``(vpp, ...)``): rank r owns virtual
    stages ``r, r+pp, ..., r+(vpp-1)·pp``.

    Each tick computes exactly ONE chunk per rank (1/vpp of a full stage),
    so a tick costs 1/vpp of a non-interleaved tick.  Microbatches are
    processed in Megatron's round-robin order — groups of ``pp``
    microbatches traverse chunk 0 on every rank, then chunk 1, ... — which
    keeps every rank busy back-to-back in steady state.  At tick ``t``,
    rank ``r`` computes, with ``u = t - r``:

        group g     = u // (pp·vpp)
        chunk c     = (u mod pp·vpp) // pp
        microbatch  = g·pp + (u mod pp)

    valid while ``0 <= u < nm·vpp``.  Total ticks = ``nm·vpp + pp - 1`` of
    duration 1/vpp stage ⇒ wall ≈ ``nm + (pp-1)/vpp`` stage-times: the
    fill/drain bubble is **(pp-1)/vpp** — the Megatron interleaving win —
    vs the non-interleaved schedule's ``pp-1``.  Routing is a uniform
    rank→rank+1 ``ppermute``: the wrap pp-1→0 lands exactly where chunk
    ``c+1`` is scheduled next tick, and rank 0 overwrites the wrapped value
    with a fresh microbatch whenever its scheduled chunk is 0.

    Like the reference schedule, requires ``num_microbatches`` to be a
    multiple of the pipeline size (SURVEY §2.3 interleaving row: Megatron
    asserts ``num_microbatches % pipeline_parallel_size == 0``).

    ``carry_chunk``: same two-level checkpointed tick scan as the
    non-interleaved schedule — more valuable here, since this schedule
    runs ``nm·vpp + pp − 1`` ticks (vpp× the carries).
    """
    inputs, targets = batch
    nm = num_microbatches
    if num_model_chunks is None:
        num_model_chunks = ps.get_virtual_pipeline_model_parallel_world_size()
    vpp = num_model_chunks
    if vpp is None or vpp < 1:
        raise ValueError("num_model_chunks (virtual pipeline size) required")
    run = _wrap_remat(stage_fn, remat, remat_policy)
    lfn = loss_fn if loss_takes_params else (lambda p, y, t: loss_fn(y, t))

    def pipeline_loss(params):
        pp = jax.lax.axis_size(axis_name)
        if nm % pp != 0:
            raise ValueError(
                f"interleaved schedule requires num_microbatches ({nm}) to "
                f"be a multiple of pipeline_parallel_size ({pp})"
            )
        stage = jax.lax.axis_index(axis_name)
        is_first = stage == 0
        is_last = stage == pp - 1
        cycle = pp * vpp
        ticks = nm * vpp + pp - 1
        h0 = jax.tree_util.tree_map(lambda x: jnp.zeros_like(x[0]), inputs)

        def tick(carry, t):
            h_recv, losses = carry
            u = t - stage
            w = jnp.mod(u, cycle)
            chunk = w // pp
            mb = jnp.floor_divide(u, cycle) * pp + jnp.mod(u, pp)
            active = (u >= 0) & (u < nm * vpp)
            mb_idx = jnp.clip(mb, 0, nm - 1)

            injecting = is_first & (chunk == 0) & active
            inject = jax.tree_util.tree_map(lambda x: x[mb_idx], inputs)
            x_in = jax.tree_util.tree_map(
                lambda a, b: jnp.where(injecting, a, b), inject, h_recv
            )
            chunk_params = jax.tree_util.tree_map(
                lambda x: jax.lax.dynamic_index_in_dim(
                    x, chunk, 0, keepdims=False
                ),
                params,
            )
            y = run(chunk_params, x_in)

            # loss: last virtual stage = rank pp-1 running chunk vpp-1
            finishing = is_last & (chunk == vpp - 1) & active
            tgt = jax.tree_util.tree_map(lambda x: x[mb_idx], targets)
            loss = lfn(chunk_params, y, tgt)
            losses = losses.at[mb_idx].add(jnp.where(finishing, loss, 0.0))

            h_next = p2p.send_forward_recv_forward(y, axis_name, cyclic=True)
            return (h_next, losses), None

        carry0 = (h0, jnp.zeros((nm,), jnp.float32))
        if carry_chunk and carry_chunk > 0:
            kk = min(carry_chunk, ticks)
            n_outer = -(-ticks // kk)  # padded ticks are masked no-ops
            ts = jnp.arange(n_outer * kk).reshape(n_outer, kk)

            @jax.checkpoint
            def outer(carry, ts_chunk):
                carry, _ = jax.lax.scan(tick, carry, ts_chunk)
                return carry, None

            (_, losses), _ = jax.lax.scan(outer, carry0, ts)
        else:
            (_, losses), _ = jax.lax.scan(
                tick, carry0, jnp.arange(ticks)
            )
        # local sum differentiated; psum only in aux (see 1F1B note above)
        return jnp.sum(losses) / nm, jax.lax.psum(losses, axis_name)

    if forward_only:
        _, losses = pipeline_loss(params)
        return losses, None
    (_, losses), grads = jax.value_and_grad(pipeline_loss, has_aux=True)(
        params
    )
    return losses, grads


# ---------------------------------------------------------------------------
# dispatcher
# ---------------------------------------------------------------------------


def get_forward_backward_func(
    virtual_pipeline_model_parallel_size: Optional[int] = None,
    pipeline_model_parallel_size: Optional[int] = None,
    hand_scheduled: bool = False,
):
    """≙ schedules/__init__.py :: get_forward_backward_func.

    ``hand_scheduled=True`` opts into the explicit-stash-ring schedules
    (no autodiff over the tick loop — the reference's 1F1B memory
    points): :func:`forward_backward_pipelining_1f1b` without virtual
    stages, :func:`forward_backward_pipelining_interleaved_1f1b` with
    them; see docs/pipeline-schedules.md for when each wins."""
    if pipeline_model_parallel_size is None and ps.model_parallel_is_initialized():
        pipeline_model_parallel_size = ps.get_pipeline_model_parallel_world_size()
    if virtual_pipeline_model_parallel_size is None and ps.model_parallel_is_initialized():
        virtual_pipeline_model_parallel_size = (
            ps.get_virtual_pipeline_model_parallel_world_size()
        )
    if (pipeline_model_parallel_size or 1) <= 1:
        return forward_backward_no_pipelining
    if virtual_pipeline_model_parallel_size is not None:
        return functools.partial(
            forward_backward_pipelining_interleaved_1f1b
            if hand_scheduled
            else forward_backward_pipelining_with_interleaving,
            num_model_chunks=virtual_pipeline_model_parallel_size,
        )
    if hand_scheduled:
        return forward_backward_pipelining_1f1b
    return forward_backward_pipelining_without_interleaving
