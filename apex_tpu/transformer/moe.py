"""Expert parallelism — Switch-style Mixture-of-Experts over a mesh axis.

**No reference analog** (SURVEY §2.3: EP/MoE is ABSENT in the reference —
``parallel_state`` has no expert groups).  This module is the TPU-native
extension that completes the parallelism envelope (dp/tp/sp/pp/cp/ep):

- :class:`SwitchMoe` — a drop-in MoE FFN block: top-1 or top-2 router,
  fixed expert capacity (static shapes — the XLA requirement), experts
  sharded across ``expert_axis`` (Megatron's convention: the expert group
  is carved out of the data-parallel world, so no new mesh axis is
  needed), token dispatch via ``jax.lax.all_to_all``, and the Switch
  auxiliary load-balancing loss.

Dataflow per shard_map rank (T = local tokens, E = global experts,
E_l = E / ep local experts, C = capacity per expert):

    router logits (T, E) → dispatch one-hots (T, E, C)        [einsum form:
    combine weights  (T, E, C)                 Mesh-TensorFlow/GShard MoE]
    x (T, H) ──einsum──▶ (E, C, H) ──all_to_all(ep)──▶ (E_l, ep·C, H)
        ──batched expert FFN (E_l,·,H)@(E_l,H,F)──▶ (E_l, ep·C, H)
        ──all_to_all back──▶ (E, C, H) ──combine──▶ (T, H)

The one-hot dispatch keeps every shape static and lowers to MXU-friendly
einsums; overflow tokens beyond an expert's capacity are dropped (their
combine weight is zero — the standard Switch behavior) and pass through
the residual connection of the surrounding block.

**The dropless path** (:func:`route_group_limited`, :func:`dropless_moe`)
is what serving runs: any ``top_k``, no capacity and no ``(tokens,
experts, capacity)`` tensor.  The layer is TOLD which experts it holds —
one chip's share of an expert-parallel group: it routes over all the
layer's experts, sorts the tokens routed to the experts it holds, runs one
grouped matmul over them (:mod:`apex_tpu.ops.moe_grouped`) and adds only
their terms.  What the other chips' experts would add, and the exchange
with them, is not stood in for.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu import parallel_state as ps
from apex_tpu.ops.moe_grouped import grouped_swiglu

__all__ = [
    "MoeConfig",
    "SwitchMoe",
    "moe_dispatch_combine",
    "sync_moe_gradients",
    "route_group_limited",
    "dropless_moe",
]


@dataclasses.dataclass(frozen=True)
class MoeConfig:
    hidden_size: int
    ffn_hidden_size: int
    num_experts: int
    top_k: int = 1  # 1 = Switch, 2 = GShard-style top-2
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01
    # router always computes in f32 (the Switch paper's stability rule);
    # expert FFN computes in `dtype`
    dtype: Any = jnp.bfloat16
    # mesh axis the experts shard over; None = unsharded (single program).
    # "dp" is the Megatron convention (expert group ⊂ data-parallel world).
    expert_axis: Optional[str] = ps.DATA_PARALLEL_AXIS
    # True when this block runs inside the sequence-parallel region at
    # tp > 1 (each tp rank routes only its S/tp tokens): router + expert
    # params then carry tp-PARTIAL gradients and are registered for
    # allreduce_sequence_parallel_gradients' tp psum.
    sequence_parallel: bool = False
    # True under context parallelism (tokens sharded over the cp axis):
    # aux stats are pmean'd over cp with grad scale 1.0 — cp gradients
    # are synced with pmean (a data axis), not psum, so no rescale is
    # needed and no param marking happens.  Mutually exclusive with
    # sequence_parallel.
    context_parallel: bool = False

    def __post_init__(self):
        if self.top_k not in (1, 2):
            raise ValueError(
                f"the capacity path routes top-1 or top-2, got top_k="
                f"{self.top_k} (dropless_moe takes any top_k)"
            )
        if self.sequence_parallel and self.context_parallel:
            raise ValueError(
                "sequence_parallel and context_parallel are mutually "
                "exclusive (both shard the token dimension)"
            )


def route_group_limited(x, router, bias, *, top_k: int, n_group: int,
                        topk_group: int, scale: float):
    """Sigmoid router with group-limited top-k (DeepSeek-V3's rule).
    ``x`` ``(T, H)``, ``router`` ``(H, E)`` f32, ``bias`` ``(E)``: scores
    ``sigmoid(x @ router)`` in f32; selection on ``score + bias``; the
    experts lie in ``n_group`` runs of ``E / n_group``, a group scores the
    sum of its two best, the best ``topk_group`` groups stay, the ``top_k``
    best experts within them are chosen; weights are the chosen experts'
    scores (without the bias) normalised to sum 1, times ``scale``.
    Returns ``(idx (T, top_k) int32, weights (T, top_k) f32)``."""
    with jax.named_scope("moe_route"):
        e = router.shape[-1]
        s = jax.nn.sigmoid(jnp.matmul(
            x.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        ))
        sel = s + bias
        grp = jnp.sum(
            jax.lax.top_k(sel.reshape(-1, n_group, e // n_group), 2)[0], -1
        )
        # the topk_group-th best group's score is the bar (ties keep both:
        # scores are f32 sums of sigmoids, a tie is a measure-zero event)
        bar = jax.lax.top_k(grp, topk_group)[0][:, -1:]
        allowed = jnp.repeat(grp >= bar, e // n_group, axis=1)
        _, idx = jax.lax.top_k(jnp.where(allowed, sel, -jnp.inf), top_k)
        w = jnp.take_along_axis(s, idx, axis=1)
        w = w / jnp.sum(w, axis=-1, keepdims=True) * scale
        return idx.astype(jnp.int32), w


def dropless_moe(x, idx, weights, experts, *, held, live=None,
                 tile: int = 16):
    """The terms of the experts this chip holds, with nothing dropped.

    ``x`` ``(T, H)``; ``idx, weights`` ``(T, K)`` from the router, over
    ALL the layer's experts; ``experts`` ``{"gate", "up": (E_held, H, I),
    "down": (E_held, I, H)}``; ``held = (first, count)``: this chip holds
    experts ``first .. first + count - 1``; ``live`` ``(T,)`` bool leaves
    rows out (bucket padding, idle slots).  The pairs (token, expert) that
    land here are sorted by expert, each expert's group padded to whole
    tiles of ``tile`` rows, and run through one grouped matmul; a token's
    output is the weighted sum of its own pairs' rows.

    Returns ``(out (T, H) f32, stats (2,) int32)``: pairs routed to held
    experts, and distinct held experts touched."""
    t, k = idx.shape
    first, count = held
    with jax.named_scope("moe_sort"):
        local = idx - first
        here = (local >= 0) & (local < count)
        if live is not None:
            here = here & live[:, None]
        local = jnp.where(here, local, count).reshape(-1)     # (T*K,)
        order = jnp.argsort(local, stable=True)
        sorted_local = local[order]
        sizes = jnp.sum(
            local[:, None] == jnp.arange(count)[None, :], axis=0
        )                                                      # (count,)
        padded = -(-sizes // tile) * tile
        starts = jnp.cumsum(sizes) - sizes                     # unpadded
        pad_starts = jnp.cumsum(padded) - padded
        m = (-(-t * k // tile) + count) * tile                 # worst case
        # sorted pair i -> its row in the padded layout (m: not held)
        safe = jnp.minimum(sorted_local, count - 1)
        dest = jnp.where(
            sorted_local < count,
            pad_starts[safe] + jnp.arange(t * k) - starts[safe], m,
        )
        row_token = jnp.zeros((m,), jnp.int32).at[dest].set(
            (order // k).astype(jnp.int32), mode="drop"
        )
        # pair (token, j) -> its row
        pair_row = jnp.zeros((t * k,), jnp.int32).at[order].set(
            jnp.minimum(dest, m - 1).astype(jnp.int32)
        ).reshape(t, k)
        ends = jnp.cumsum(padded)
        live_tiles = ends[-1] // tile
        tile_start = jnp.arange(m // tile) * tile
        tile_expert = jnp.sum(tile_start[:, None] >= ends[None, :], axis=1)
        last = tile_expert[jnp.maximum(live_tiles - 1, 0)]
        tile_expert = jnp.minimum(
            jnp.where(tile_start < ends[-1], tile_expert, last), count - 1
        )
        rows = jnp.take(x, row_token, axis=0)
    y = grouped_swiglu(
        rows, tile_expert, live_tiles, experts["gate"], experts["up"],
        experts["down"], tile=tile,
    )
    with jax.named_scope("moe_combine"):
        picked = jnp.take(y, pair_row, axis=0).astype(jnp.float32)
        out = jnp.sum(
            jnp.where(here[..., None], weights[..., None] * picked, 0.0),
            axis=1,
        )
        stats = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0)])
    return out, stats.astype(jnp.int32)


def _axis_size(axis: Optional[str]) -> int:
    return 1 if axis is None else ps.bound_axis_size(axis)


def moe_dispatch_combine(router_probs, top_k, capacity, stats_axis=None,
                         stats_grad_scale=None):
    """Dispatch/combine tensors from router probabilities.

    router_probs f32 (T, E) (already softmaxed).  Returns
    ``(dispatch (T, E, C) bool-as-float, combine (T, E, C) f32, aux)``:
    position-in-expert is assigned by cumulative count in token order
    (earlier tokens win capacity — the Switch rule), ``aux`` is the
    load-balancing loss term  E · Σ_e f_e · P_e  (fraction routed ×
    mean prob).

    ``stats_axis``: mesh axis to pmean the aux statistics (f_e, P_e) over
    before forming the product — used whenever tokens are SHARDED over an
    axis (Megatron SP over tp; context parallelism over cp): aux is
    quadratic in the stats, so the mean of per-shard aux ≠ the
    global-batch aux; pmean'ing the stats first recovers exactly the
    unsharded value.

    ``stats_grad_scale``: per-rank scale applied to the aux GRADIENT
    (value unchanged, via stop_gradient).  pmean's VJP psums the
    cotangent across ranks, so each rank's aux backward carries the FULL
    E·f̄ factor on its local-path derivative.  The right scale depends on
    how the caller then syncs gradients over ``stats_axis``:

    - psum sync (Megatron SP: allreduce_sequence_parallel_gradients):
      scale 1/n, else the summed partials are n× the true gradient —
      the default (``None`` → 1/axis_size);
    - pmean sync (context parallelism treats cp as a data axis): scale
      1.0 — the 1/n of the pmean already cancels the full factor.
    """
    t, e = router_probs.shape
    # top-k expert choices per token
    _, expert_idx = jax.lax.top_k(router_probs, top_k)  # (T, K)
    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)  # (T, K, E)

    # aux loss uses the top-1 assignment fraction (Switch definition)
    frac_routed = jnp.mean(onehot[:, 0, :], axis=0)  # (E,)
    mean_prob = jnp.mean(router_probs, axis=0)  # (E,)
    if stats_axis is not None:
        frac_routed = jax.lax.pmean(frac_routed, stats_axis)
        mean_prob = jax.lax.pmean(mean_prob, stats_axis)
        aux = e * jnp.sum(frac_routed * mean_prob)
        scale = (
            1.0 / jax.lax.axis_size(stats_axis)
            if stats_grad_scale is None
            else stats_grad_scale
        )
        if scale != 1.0:
            aux = aux * scale + jax.lax.stop_gradient(aux * (1.0 - scale))
    else:
        aux = e * jnp.sum(frac_routed * mean_prob)

    dispatch = jnp.zeros((t, e, capacity), jnp.float32)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    # running per-expert fill counts across the K choices: a token's k-th
    # choice sees capacity consumed by ALL tokens' earlier choices and by
    # earlier tokens' k-th choice (exact GShard ordering for K <= 2)
    fill = jnp.zeros((e,), jnp.float32)
    for k in range(top_k):
        oh = onehot[:, k, :]  # (T, E)
        pos = jnp.cumsum(oh, axis=0) - oh + fill[None, :]  # (T, E)
        keep = oh * (pos < capacity)
        pos_clamped = jnp.minimum(pos, capacity - 1).astype(jnp.int32)
        pos_oh = jax.nn.one_hot(pos_clamped, capacity, dtype=jnp.float32)
        sel = keep[..., None] * pos_oh  # (T, E, C)
        dispatch = dispatch + sel
        gate = jnp.sum(router_probs * oh, axis=-1)  # (T,)
        combine = combine + sel * gate[:, None, None]
        fill = fill + jnp.sum(oh, axis=0)
    if top_k == 2:
        # renormalize the KEPT gates so they sum to 1 per token (GShard's
        # top-2 rule); a token whose both choices overflowed keeps 0
        denom = jnp.sum(combine, axis=(1, 2), keepdims=True)
        combine = jnp.where(
            denom > 0.0, combine / jnp.maximum(denom, 1e-9), 0.0
        )
    return dispatch, combine, aux


def sync_moe_gradients(grads, axis: str = ps.EXPERT_PARALLEL_AXIS,
                       average: bool = True,
                       sequence_parallel_axis: Optional[str] = None):
    """Data-parallel gradient sync that understands expert sharding.

    A plain ``psum``/``pmean`` over dp (apex_tpu.parallel's DDP) is WRONG
    for an MoE model: expert weights are dp-SHARDED (rank r owns experts
    ``[r·E_l, (r+1)·E_l)``), so an element-wise allreduce would mix the
    gradients of DIFFERENT experts.  And it is also unnecessary — each
    rank's experts already saw every rank's tokens through the all_to_all
    dispatch, so their backward aggregates over the full global batch.
    This helper reduces every leaf EXCEPT those whose path contains a
    parameter named with SwitchMoe's ``expert_`` prefix.

    Scaling: the backward ``all_to_all`` already delivers to each expert
    owner the SUM over every rank's loss of that expert's gradient.  So
    for the mean global objective (``average=True``, pmean on the other
    leaves — DDP's gradient_average semantics) expert leaves are scaled
    by ``1/axis_size`` to match; for the sum objective (``average=False``,
    psum) they are left as the sum they already are.

    With tensor parallelism AND ``sequence_parallel`` (each tp rank routes
    only its S/tp tokens — set ``MoeConfig.sequence_parallel=True``), pass
    ``sequence_parallel_axis="tp"``: router/expert/LN grads are then also
    psum'd over tp via :func:`allreduce_sequence_parallel_gradients`
    (they are tp-replicated params with tp-partial gradients; without the
    reduction the replicated copies silently diverge).
    """
    from jax.tree_util import DictKey, tree_map_with_path

    reduce_ = jax.lax.pmean if average else jax.lax.psum
    world = jax.lax.axis_size(axis)

    def maybe_reduce(path, g):
        for k in path:
            if isinstance(k, DictKey) and str(k.key).startswith("expert_"):
                return g / world if average else g
        return reduce_(g, axis)

    grads = tree_map_with_path(maybe_reduce, grads)
    if sequence_parallel_axis is not None:
        from apex_tpu.transformer.tensor_parallel.mappings import (
            allreduce_sequence_parallel_gradients,
        )

        grads = allreduce_sequence_parallel_gradients(
            grads, sequence_parallel_axis
        )
    return grads


class SwitchMoe(nn.Module):
    """MoE FFN block (router + sharded experts + dispatch/combine).

    Input/output ``(S, B, H)`` (seq-first, matching the transformer
    stack).  Returns ``(y, aux_loss)`` — add ``cfg.aux_loss_coef * aux``
    to the training loss.  Expert weights are stored as the LOCAL shard
    ``(E_l, ...)`` when ``cfg.expert_axis`` is bound (ep-degree-invariant
    init: each rank folds its expert ids into the param key, so global
    expert e has identical weights at any ep degree).
    """

    cfg: MoeConfig

    @nn.compact
    def __call__(self, x) -> Tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        s, b, h = x.shape
        if h != cfg.hidden_size:
            raise ValueError(f"hidden {h} != cfg.hidden_size {cfg.hidden_size}")
        ep = _axis_size(cfg.expert_axis)
        if cfg.num_experts % ep:
            raise ValueError(
                f"num_experts ({cfg.num_experts}) must be divisible by the "
                f"expert axis size ({ep})"
            )
        e_local = cfg.num_experts // ep
        tokens = s * b
        capacity = int(cfg.capacity_factor * tokens / cfg.num_experts + 0.5)
        capacity = max(capacity, 1)

        xt = x.reshape(tokens, h)
        # --- router (f32, replicated) ---------------------------------
        router_w = self.param(
            "router",
            nn.initializers.normal(stddev=0.02),
            (h, cfg.num_experts),
            jnp.float32,
        )
        logits = xt.astype(jnp.float32) @ router_w
        probs = jax.nn.softmax(logits, axis=-1)
        stats_axis, stats_grad_scale = None, None
        if cfg.sequence_parallel and ps.axis_is_bound(
            ps.TENSOR_PARALLEL_AXIS
        ):
            stats_axis = ps.TENSOR_PARALLEL_AXIS  # psum sync → 1/n scale
        elif cfg.context_parallel and ps.axis_is_bound(
            ps.CONTEXT_PARALLEL_AXIS
        ):
            stats_axis = ps.CONTEXT_PARALLEL_AXIS
            stats_grad_scale = 1.0  # pmean sync cancels the factor
        dispatch, combine, aux = moe_dispatch_combine(
            probs, cfg.top_k, capacity, stats_axis=stats_axis,
            stats_grad_scale=stats_grad_scale,
        )

        # --- expert weights: LOCAL shard, ep-degree-invariant init ----
        def expert_init(fan_in, fan_out):
            def init(key):
                rank = 0
                if ep > 1:
                    rank = jax.lax.axis_index(cfg.expert_axis)
                keys = jax.vmap(
                    lambda i: jax.random.fold_in(key, rank * e_local + i)
                )(jnp.arange(e_local))
                w_init = nn.initializers.normal(stddev=fan_in**-0.5)
                return jax.vmap(lambda k: w_init(k, (fan_in, fan_out)))(keys)

            return init

        # the "expert_" prefix marks dp-SHARDED parameters — the contract
        # sync_moe_gradients uses to exclude them from the dp grad psum
        w1 = self.param(
            "expert_w1", expert_init(h, cfg.ffn_hidden_size)
        ).astype(cfg.dtype)
        w2 = self.param(
            "expert_w2", expert_init(cfg.ffn_hidden_size, h)
        ).astype(cfg.dtype)
        if cfg.sequence_parallel:
            # under SP each tp rank routes a different S/tp token shard, so
            # router/expert grads are tp-partial (sum over tp = true grad)
            for name in ("router", "expert_w1", "expert_w2"):
                ps.register_sequence_parallel_param(self.path + (name,))

        # --- dispatch -> experts -> combine ---------------------------
        ex = jnp.einsum(
            "tec,th->ech", dispatch.astype(cfg.dtype), xt.astype(cfg.dtype)
        )  # (E, C, H): this rank's C capacity slots for EVERY expert
        if ep > 1:
            # tiled all_to_all, expert axis split source-rank-major:
            # (E, C, H) -> (E_l, ep*C, H) — each rank receives the slots
            # routed to ITS experts from every expert-group peer (the
            # received axis is source-rank major: peer r's block sits at
            # [r*C, (r+1)*C))
            ex = jax.lax.all_to_all(
                ex, cfg.expert_axis, split_axis=0, concat_axis=1, tiled=True
            )
        hmid = jnp.einsum("ekh,ehf->ekf", ex, w1)
        hmid = jax.nn.gelu(hmid, approximate=True)
        ey = jnp.einsum("ekf,efh->ekh", hmid, w2)  # (E_l, ep*C, H)
        if ep > 1:
            # reverse: split the source-rank-major slot axis, concat on the
            # expert axis in owner-rank order -> (E, C, H) globally
            # expert-ordered, exactly what combine expects
            ey = jax.lax.all_to_all(
                ey, cfg.expert_axis, split_axis=1, concat_axis=0, tiled=True
            )
        y = jnp.einsum(
            "tec,ech->th", combine.astype(cfg.dtype), ey
        )
        return y.reshape(s, b, h).astype(x.dtype), aux
