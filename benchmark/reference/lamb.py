"""LAMB as NVIDIA's FusedLAMB states it (You et al. 2019 + apex's
multi_tensor_lamb): global gradient-norm clip, Adam moments with bias
correction, decoupled weight decay inside the update, per-tensor trust
ratio.  Plain jax.numpy on a flat dict of f32 leaves."""

from __future__ import annotations

import jax.numpy as jnp


def init(params):
    z = {k: jnp.zeros_like(v) for k, v in params.items()}
    return {"count": 0, "m": z, "v": dict(z)}


def step(params, grads, state, *, lr, weight_decay, beta1=0.9, beta2=0.999,
         eps=1e-6, max_grad_norm=1.0, second_moment=True):
    """``second_moment=False`` is a planted fault (the update's direction
    taken from the first moment alone), never the configuration's LAMB."""
    count = state["count"] + 1
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
    clip = jnp.where(gnorm > max_grad_norm, gnorm / max_grad_norm, 1.0)
    bc1, bc2 = 1.0 - beta1 ** count, 1.0 - beta2 ** count
    new_p, new_m, new_v = {}, {}, {}
    for k, p in params.items():
        g = grads[k] / clip
        m = beta1 * state["m"][k] + (1.0 - beta1) * g
        v = beta2 * state["v"][k] + (1.0 - beta2) * g * g
        adam = (m / bc1) / (jnp.sqrt(v / bc2) + eps) if second_moment else m / bc1
        u = adam + weight_decay * p
        pn, un = jnp.sqrt(jnp.sum(p * p)), jnp.sqrt(jnp.sum(u * u))
        ratio = jnp.where((pn > 0) & (un > 0), pn / un, 1.0)
        if weight_decay == 0.0:
            ratio = 1.0
        new_p[k], new_m[k], new_v[k] = p - lr * ratio * u, m, v
    return new_p, {"count": count, "m": new_m, "v": new_v}
