"""Compile-only rehearsal: XLA:TPU's memory analysis of the serving step
programs at a configuration's real sizes, for a described (not attached)
v5e chip.  Nothing runs; no time, rate or result comes from here.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse_compile.py gpt2-large 1201 1401 ...

prints, for each pool size, the bytes `serve_decode` and
`serve_prefill_1024` hold (arguments + outputs + temporaries - aliased).
This is how ``serve.num_pages`` in benchmark/configs/gpt2-large.json was
chosen: the largest pool whose decode step, with weights and pool counted
once, leaves room for the reference's forward (PERF.md has the table).
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["TPU_SKIP_MDS_QUERY"] = "1"
os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def total_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes - m.alias_size_in_bytes)


def main(argv):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from apex_tpu.models.gpt import GptModel
    from apex_tpu.ops import _dispatch
    from apex_tpu.ops.pallas import decode_attention, flash_attention, layer_norm
    from apex_tpu.serve import cache as cache_lib
    from apex_tpu.serve import model as model_lib
    from benchmark.drivers.gpt_serve import program_config

    with open(os.path.join(HERE, "configs", argv[0] + ".json")) as f:
        cfg = json.load(f)
    sv = cfg["serve"]
    pcfg = program_config(cfg)
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2",
        chips_per_host_bounds=(2, 2, 1), num_slices=1,
    )
    dev = SingleDeviceSharding(topo.devices[0])
    # lower as the chip would: kernels on, Mosaic (not interpret) mode
    _dispatch.use_pallas = lambda: True
    for mod in (_dispatch, decode_attention, flash_attention, layer_norm):
        mod.pallas_interpret = lambda: False

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=dev), tree
        )

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    params = on_chip(jax.eval_shape(
        GptModel(pcfg).init, jax.random.PRNGKey(0), jnp.zeros((8, 1), jnp.int32)
    ))
    b, mp, ps = sv["max_batch"], sv["max_pages_per_seq"], sv["page_size"]
    bucket = max(sv["prefill_buckets"])
    for pages in [int(a) for a in argv[1:]] or [sv["num_pages"]]:
        cache = on_chip(jax.eval_shape(lambda: cache_lib.init_kv_pages(
            pcfg.num_layers, pages, pcfg.num_heads, ps,
            pcfg.hidden_size // pcfg.num_heads, dtype=pcfg.dtype)))

        def decode(params, kv, tokens, lengths, tables, temps, rng):
            return model_lib.decode_body(
                pcfg, params, kv, tokens, lengths, tables, temps, rng,
                page_size=ps, kv_wire="f32", top_k=0)

        def prefill(params, kv, tokens, length, page_ids, temp, rng):
            return model_lib.prefill_body(
                pcfg, params, kv, tokens, length, page_ids, temp, rng,
                page_size=ps, kv_wire="f32", top_k=0)

        d = jax.jit(decode, donate_argnums=(1,)).lower(
            params, cache, S((b,), jnp.int32), S((b,), jnp.int32),
            S((b, mp), jnp.int32), S((b,), jnp.float32), S((b, 2), jnp.uint32),
        ).compile()
        p = jax.jit(prefill, donate_argnums=(1,)).lower(
            params, cache, S((bucket, 1), jnp.int32), S((), jnp.int32),
            S((bucket // ps,), jnp.int32), S((), jnp.float32), S((2,), jnp.uint32),
        ).compile()
        pool = sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(cache))
        print(json.dumps({
            "num_pages": pages, "pool_bytes": pool,
            "serve_decode_bytes": total_bytes(d),
            f"serve_prefill_{bucket}_bytes": total_bytes(p),
        }), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
