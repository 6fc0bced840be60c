"""Weights from ``--seed``, made by the harness on the device."""

from __future__ import annotations


def seeded_weights(shapes, seed: int, std: float):
    """Every leaf of ``shapes`` from ``seed`` in one jitted call: LayerNorm
    scales are 1, everything else N(0, std) in the leaf's own dtype."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def gen(key):
        out = []
        for i, (path, s) in enumerate(leaves):
            if getattr(path[-1], "key", None) == "scale":
                out.append(jnp.ones(s.shape, s.dtype))
            else:
                k = jax.random.fold_in(key, i)
                out.append(std * jax.random.normal(k, s.shape, s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    key = jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31
    )
    return jax.jit(gen)(key)
