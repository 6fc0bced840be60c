"""Compile-only checks against XLA:TPU for a described (not attached)
v5e chip — what interpret mode and the CPU compiler cannot show: the
layouts XLA:TPU assigns and the temporaries it holds.  Nothing runs; no
time, rate or result comes from here.

All such tests live in THIS file and describe the topology inside a
fixture: only one process may load libtpu, and a module that touched it
while being imported would give the xdist workers different tests to
collect.  Skipped, not failed, where no TPU compiler can be described.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from apex_tpu import analysis
from apex_tpu.models.gpt import GptConfig, GptModel
from apex_tpu.ops import _dispatch
from apex_tpu.ops.pallas import (
    decode_attention, flash_attention, kda, layer_norm, mla_decode,
    moe_grouped, ssm,
)
from apex_tpu.serve import cache as cache_lib
from apex_tpu.serve import model as model_lib


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def lower_as_chip(monkeypatch):
    """Kernels on and in Mosaic (not interpret) mode while lowering —
    ``jax.default_backend()`` still says cpu here."""
    monkeypatch.setattr(_dispatch, "use_pallas", lambda: True)
    for mod in (_dispatch, decode_attention, flash_attention, layer_norm,
                kda, mla_decode, moe_grouped, ssm):
        monkeypatch.setattr(mod, "pallas_interpret", lambda: False)


#: GPT-2 Large as benchmark/configs/gpt2-large.json serves it
GPT2_LARGE = dict(
    vocab_size=50257, hidden_size=1280, num_layers=36, num_heads=20,
    intermediate_size=5120, max_seq_len=1024, rotary=False,
    dtype=jnp.bfloat16,
)
PAGE, PAGES, SLOTS, PAGES_PER_SEQ = 16, 1201, 32, 64


@pytest.mark.parametrize("kv_wire", ["f32", "int8"])
@pytest.mark.parametrize("program", ["serve_decode", "serve_prefill_1024"])
def test_serving_step_never_moves_the_kv_pool(
    one_chip, lower_as_chip, program, kv_wire
):
    """At the benchmark's shapes the pool is one buffer in one layout
    from entry to exit: no instruction of the compiled program
    materializes the pool or a layer of it (`memory-pool-copy`), and
    XLA's temporaries stay under one layer's K slice (49 MB; the xs/ys
    programs held 5.67 GB and 4.30 GB).  The parameters are the engine's
    step tree (`model_lib.step_params`: the block's matmul weights and
    biases already bf16), so no program casts a stacked weight: until PR 34
    every program held 1.416 GB of such casts and began with them."""
    cfg = GptConfig(**GPT2_LARGE)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=one_chip
            ),
            tree,
        )

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = on_chip(jax.eval_shape(
        lambda *a: model_lib.step_params(cfg, GptModel(cfg).init(*a)),
        jax.random.PRNGKey(0), jnp.zeros((8, 1), jnp.int32),
    ))
    stack = params["params"]["layers"]["block"]
    assert stack["qkv"]["weight"].dtype == jnp.bfloat16
    assert stack["ln_attn"]["scale"].dtype == jnp.float32
    cache = on_chip(jax.eval_shape(lambda: cache_lib.init_kv_pages(
        cfg.num_layers, PAGES, cfg.num_heads, PAGE,
        cfg.hidden_size // cfg.num_heads, dtype=cfg.dtype, kv_wire=kv_wire,
    )))
    assert cache["k"].shape == (36, PAGES, 10, PAGE, 128)
    if kv_wire == "int8":
        assert cache["k_scale"].shape == (36, PAGES, 1, PAGE, 128)
    if program == "serve_decode":
        def fn(params, kv, tokens, lengths, tables, temps, rng):
            return model_lib.decode_body(
                cfg, params, kv, tokens, lengths, tables, temps, rng,
                page_size=PAGE)
        args = (
            arg((SLOTS,), jnp.int32), arg((SLOTS,), jnp.int32),
            arg((SLOTS, PAGES_PER_SEQ), jnp.int32),
            arg((SLOTS,), jnp.float32), arg((SLOTS, 2), jnp.uint32),
        )
    else:
        def fn(params, kv, tokens, length, page_ids, temp, rng):
            return model_lib.prefill_body(
                cfg, params, kv, tokens, length, page_ids, temp, rng,
                page_size=PAGE)
        args = (
            arg((1024, 1), jnp.int32), arg((), jnp.int32),
            arg((1024 // PAGE,), jnp.int32), arg((), jnp.float32),
            arg((2,), jnp.uint32),
        )
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args
    ).compile()

    text = compiled.as_text()
    report = analysis.lint_hlo(
        text, donated=2, rules=("memory", "donation"),
        expect_pool={"shapes": [x.shape for x in cache.values()]},
    )
    assert report.findings == [], report.render()
    if program == "serve_decode":
        assert "paged_decode_fwd" in text  # the trace's name for the kernel

    layer_bytes = cache["k"].size * cache["k"].dtype.itemsize // cfg.num_layers
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < layer_bytes, (temp, layer_bytes)
    stacked = {
        "[" + ",".join(map(str, x.shape)) + "]"
        for x in jax.tree_util.tree_leaves(stack) if x.ndim > 2
    }
    casts = [
        line.strip() for line in text.splitlines()
        if " convert(" in line and any(
            shape in line.split(" convert(")[0] for shape in stacked
        )
    ]
    assert len(stacked) == 4 and not casts, casts


@pytest.mark.parametrize("h,d,page,np_,dtype,kv_wire,rope", [
    (20, 64, PAGE, PAGES_PER_SEQ, jnp.bfloat16, "f32", False),
    (20, 64, PAGE, PAGES_PER_SEQ, jnp.bfloat16, "int8", True),
    (8, 128, 128, 4, jnp.float32, "int8", True),
    # rows that do not fill their tiles: heads that do not pair up, heads
    # of 80, 96 and 192 lanes — one head a row, padded to whole tiles
    (25, 64, PAGE, PAGES_PER_SEQ, jnp.bfloat16, "f32", True),
    (20, 80, PAGE, PAGES_PER_SEQ, jnp.bfloat16, "int8", True),
    (12, 96, PAGE, PAGES_PER_SEQ, jnp.float32, "f32", True),
    (8, 192, PAGE, PAGES_PER_SEQ, jnp.bfloat16, "int8", False),
    # a 32k-token table on the int8 wire: a step's scale slab in VMEM
    (32, 128, PAGE, 2048, jnp.bfloat16, "int8", True),
], ids=["gpt2-large", "gpt2-large-int8-rope", "f32-d128-int8-rope",
        "25x64-rope", "d80-int8-rope", "d96-f32-rope", "d192-int8",
        "np2048-int8-rope"])
def test_paged_decode_walk_compiles(
    one_chip, lower_as_chip, h, d, page, np_, dtype, kv_wire, rope
):
    """The decode kernel's in-kernel walk in Mosaic, over the pool as
    ``init_kv_pages`` lays it out: page copies out of a pool left in HBM
    (a copy may slice only whole 128-lane rows, which is why every row —
    the int8 wire's scale planes' too — is whole tiles), a loop to the
    sequence's live steps, ``G`` query rows a contraction."""
    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    kv = jax.eval_shape(lambda: cache_lib.init_kv_pages(
        2, 65, h, page, d, dtype=dtype, kv_wire=kv_wire))
    assert kv["k"].shape[-1] % 128 == 0
    kw = {n: arg(x.shape, x.dtype) for n, x in kv.items() if "scale" in n}
    if rope:
        kw.update(rope_cos=arg((SLOTS, d), dtype),
                  rope_sin=arg((SLOTS, d), dtype))
    text = jax.jit(
        lambda *a, **k: decode_attention.paged_decode_fwd(
            *a, scale=d ** -0.5, **k)
    ).lower(
        arg((SLOTS, h, d), dtype), arg(kv["k"].shape, kv["k"].dtype),
        arg(kv["v"].shape, kv["v"].dtype), arg((SLOTS, np_), jnp.int32),
        arg((SLOTS,), jnp.int32), arg((), jnp.int32), **kw
    ).compile().as_text()
    assert text.count("paged_decode_fwd") >= 1
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kv_int8", [False, True])
def test_plain_narrow_pages_reach_the_compiled_walk(
    one_chip, lower_as_chip, kv_int8
):
    """Plain ``(P, H, page, D)`` pages of 64 lanes through the public op
    — what ``tests_tpu`` feeds the chip: the op pads the rows to whole
    tiles, so the kernel compiles in Mosaic and no shape falls back."""
    from apex_tpu.ops.paged_attention import paged_decode_attention

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    b, h, d, page, pool, np_ = 2, 8, 64, 128, 8, 2
    kw = dict(rope_cos=arg((b, d), jnp.float32),
              rope_sin=arg((b, d), jnp.float32))
    if kv_int8:
        kw.update(k_scale=arg((pool, h, page), jnp.float32),
                  v_scale=arg((pool, h, page), jnp.float32))
    pages = arg((pool, h, page, d), jnp.int8 if kv_int8 else jnp.float32)
    text = jax.jit(paged_decode_attention).lower(
        arg((b, h, d), jnp.float32), pages, pages,
        arg((b, np_), jnp.int32), arg((b,), jnp.int32), **kw
    ).compile().as_text()
    assert _dispatch.last_paths()["paged_decode_attention"] == "pallas"
    assert text.count("paged_decode_fwd") >= 1


#: Ling-3.0-flash's language stack as benchmark/configs/
#: ling-3.0-flash-vl-ep8.json serves it: one chip of 8, the first 8 layers
LING_EP8 = dict(
    vocab_size=19648, hidden_size=2560, num_layers=8, num_heads=32,
    head_dim=128, intermediate_size=6144, max_seq_len=131072,
    layer_group_size=6, first_dense_layers=2, num_experts=512,
    held_experts=(0, 64), moe_intermediate_size=768,
    shared_intermediate_size=768, top_k=8, n_group=8, topk_group=4,
    routed_scaling_factor=2.5, rope_theta=6e6,
)
LING_PAGES, LING_SLOTS, LING_PAGES_PER_SEQ = 16385, 128, 128


@pytest.mark.parametrize(
    "program", ["serve_decode", "serve_decode_block16", "serve_prefill_1024"])
def test_hybrid_step_never_moves_its_cache_set(
    one_chip, lower_as_chip, program
):
    """The hybrid stack's programs at the benchmark's shapes, on the step
    tree (bf16 weights, as installed): the latent pool and the per-slot
    recurrent slab stay one buffer each from entry to exit — no
    instruction materializes either or a layer of either
    (`memory-pool-copy`), all four kernels are in the program under the
    names the trace reads, and XLA's temporaries stay under one layer of
    the slab in decode (a prompt's chunked-KDA Gram factors and MLA scores
    take 0.43 GB: under a gigabyte)."""
    from apex_tpu.models.hybrid import HybridConfig, param_shapes

    cfg = HybridConfig(**LING_EP8)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=one_chip
            ),
            tree,
        )

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = on_chip(param_shapes(cfg))
    cache = on_chip(jax.eval_shape(lambda: cache_lib.init_hybrid_cache(
        cfg, LING_PAGES, PAGE, LING_SLOTS
    )))
    assert cache["latent"].shape == (1, LING_PAGES, 1, PAGE, 640)
    assert cache["state"].shape == (7, LING_SLOTS, 32, 128, 128)
    if program.startswith("serve_decode"):
        # the cell's decode program runs 16 iterations a call
        block = 16 if program.endswith("block16") else 1

        def fn(params, kv, tokens, lengths, tables, temps, rng):
            return model_lib.decode_body(
                cfg, params, kv, tokens, lengths, tables, temps, rng,
                page_size=PAGE, block=block)
        args = (
            arg((LING_SLOTS,), jnp.int32), arg((LING_SLOTS,), jnp.int32),
            arg((LING_SLOTS, LING_PAGES_PER_SEQ), jnp.int32),
            arg((LING_SLOTS,), jnp.float32),
            arg((LING_SLOTS, 2) if block == 1 else (block, LING_SLOTS, 2),
                jnp.uint32),
        )
        names = ("kda_step_fwd", "moe_grouped_fwd", "mla_decode_fwd")
    else:
        def fn(params, kv, tokens, length, page_ids, slot, temp, rng):
            return model_lib.prefill_body(
                cfg, params, kv, tokens, length, page_ids, temp, rng,
                page_size=PAGE, slot=slot)
        args = (
            arg((1024, 1), jnp.int32), arg((), jnp.int32),
            arg((1024 // PAGE,), jnp.int32), arg((), jnp.int32),
            arg((), jnp.float32), arg((2,), jnp.uint32),
        )
        names = ("kda_chunk_fwd", "moe_grouped_fwd")
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args
    ).compile()

    text = compiled.as_text()
    report = analysis.lint_hlo(
        text, donated=3, rules=("memory", "donation"),
        expect_pool={"shapes": [cache["latent"].shape, cache["state"].shape]},
    )
    assert report.findings == [], report.render()
    for name in names:
        assert name in text, name
    slab_layer = cache["state"].size * 4 // 7
    mem = compiled.memory_analysis()
    limit = slab_layer if program.startswith("serve_decode") else 1e9
    assert mem.temp_size_in_bytes < limit, mem.temp_size_in_bytes
    # what a chip holds: weights + cache set + temporaries, under 16 GB
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert held < 12e9, held


#: Falcon-H1-34B-Instruct's first four layers and whole vocabulary as
#: benchmark/configs/falcon-h1-34b-instruct-4l.json serves them
FALCON_H1 = dict(
    vocab_size=261120, hidden_size=5120, num_layers=4, num_heads=20,
    num_kv_heads=4, head_dim=128, intermediate_size=21504,
    max_seq_len=262144, pattern=(("ssm_gqa", "dense"),) * 4,
    rope_theta=1e11, rms_eps=1e-5, ssm_heads=32, ssm_head_dim=128,
    ssm_groups=2, ssm_state=256, ssm_chunk=128,
    embedding_multiplier=5.656854249492381, lm_head_multiplier=0.0078125,
    attention_out_multiplier=0.0375, key_multiplier=0.011048543456039804,
    ssm_in_multiplier=0.25, ssm_out_multiplier=0.08838834764831845,
    ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738),
    mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
)
H1_PAGES, H1_SLOTS, H1_PAGES_PER_SEQ = 10241, 128, 80


@pytest.mark.parametrize(
    "program", ["serve_decode_block16", "serve_prefill_128",
                "serve_prefill_1024"])
def test_falcon_h1_step_never_moves_its_cache_set(
    one_chip, lower_as_chip, program
):
    """The parallel hybrid block's programs at the benchmark's shapes: the
    K/V pages, and the state-space slab (2.15 GB) stay one buffer each from
    entry to exit (`memory-pool-copy` over what the cache kinds declare
    in place), the kernels are in the program under the names the trace
    reads, XLA's temporaries stay far under a layer of the slab in decode,
    and weights + cache set + temporaries fit the chip."""
    from apex_tpu.models.hybrid import HybridConfig, param_shapes

    cfg = HybridConfig(**FALCON_H1)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=one_chip
            ),
            tree,
        )

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = on_chip(param_shapes(cfg))
    cache = on_chip(jax.eval_shape(lambda: cache_lib.init_hybrid_cache(
        cfg, H1_PAGES, PAGE, H1_SLOTS
    )))
    assert cache["k"].shape == (4, H1_PAGES, 4, PAGE, 128)
    assert cache["ssm"].shape == (4, H1_SLOTS, 32, 128, 256)
    assert cache["ssm_conv"].shape == (4, H1_SLOTS, 3, 5120)
    if program.startswith("serve_decode"):
        block = 16

        def fn(params, kv, tokens, lengths, tables, temps, rng):
            return model_lib.decode_body(
                cfg, params, kv, tokens, lengths, tables, temps, rng,
                page_size=PAGE, block=block)
        args = (
            arg((H1_SLOTS,), jnp.int32), arg((H1_SLOTS,), jnp.int32),
            arg((H1_SLOTS, H1_PAGES_PER_SEQ), jnp.int32),
            arg((H1_SLOTS,), jnp.float32),
            arg((block, H1_SLOTS, 2), jnp.uint32),
        )
        names = ("ssm_step_fwd", "paged_decode_fwd")
    else:
        bucket = int(program.rsplit("_", 1)[1])

        def fn(params, kv, tokens, length, page_ids, slot, temp, rng):
            return model_lib.prefill_body(
                cfg, params, kv, tokens, length, page_ids, temp, rng,
                page_size=PAGE, slot=slot)
        args = (
            arg((bucket, 1), jnp.int32), arg((), jnp.int32),
            arg((bucket // PAGE,), jnp.int32), arg((), jnp.int32),
            arg((), jnp.float32), arg((2,), jnp.uint32),
        )
        # the flash kernel takes a prompt from 1,024 rows up
        names = ("ssd_chunk_fwd",) + (("flash_fwd",) if bucket >= 1024 else ())
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args
    ).compile()

    text = compiled.as_text()
    kinds = cache_lib.hybrid_cache_kinds(cfg, PAGE)
    report = analysis.lint_hlo(
        text, donated=len(cache), rules=("memory", "donation"),
        expect_pool={"shapes": [
            cache[k.name].shape for k in kinds if k.in_place]},
    )
    assert report.findings == [], report.render()
    for name in names:
        assert name in text, name
    slab_layer = cache["ssm"].size * 4 // 4
    mem = compiled.memory_analysis()
    limit = slab_layer / 4 if program.startswith("serve_decode") else 1e9
    assert mem.temp_size_in_bytes < limit, mem.temp_size_in_bytes
    # what a chip holds: weights + cache set + temporaries, under 16 GB
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 12e9 < held < 13.5e9, held


def test_ssm_kernels_compile_at_falcon_h1s_widths(one_chip, lower_as_chip):
    """`ssm_step_fwd` over the slab in place (aliased) and `ssd_chunk_fwd`
    over a 1,024-row prompt, in Mosaic: 8 heads of 128 x 256 f32 a grid
    step, in and out, inside VMEM."""
    def arg(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    b, h, p, n = H1_SLOTS, 32, 128, 256
    text = jax.jit(
        lambda *a: ssm.ssm_step_fwd(*a, layer=2), donate_argnums=(0,)
    ).lower(
        arg((4, b, h, p, n)), arg((b, h, p)), arg((b, h, n)),
        arg((b, h, n)), arg((b, h, n)),
    ).compile().as_text()
    assert "ssm_step_fwd" in text and "tpu_custom_call" in text
    assert "input_output_alias" in text
    nc, c = 8, 128
    text = jax.jit(ssm.ssd_chunk_fwd).lower(
        arg((h, nc, c, n)), arg((h, nc, p, n)), arg((h, nc, 1, n)),
    ).compile().as_text()
    assert "ssd_chunk_fwd" in text and "tpu_custom_call" in text


@pytest.mark.parametrize("h,kv,d", [(20, 4, 128), (8, 4, 64), (10, 2, 96)],
                         ids=["falcon-h1", "pairs-in-a-row", "padded-row"])
def test_grouped_query_paged_decode_walk_compiles(
    one_chip, lower_as_chip, h, kv, d
):
    """The decode kernel with ``h / kv`` query rows a KV head, in Mosaic,
    over the pool as ``init_kv_pages`` lays it at the KV heads."""
    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = jax.eval_shape(lambda: cache_lib.init_kv_pages(
        2, 65, kv, PAGE, d, dtype=jnp.bfloat16))
    text = jax.jit(
        lambda *a: decode_attention.paged_decode_fwd(
            *a, scale=d ** -0.5, kv_heads=kv)
    ).lower(
        arg((H1_SLOTS, h, d), jnp.bfloat16),
        arg(pool["k"].shape, pool["k"].dtype),
        arg(pool["v"].shape, pool["v"].dtype),
        arg((H1_SLOTS, H1_PAGES_PER_SEQ), jnp.int32),
        arg((H1_SLOTS,), jnp.int32), arg((), jnp.int32),
    ).compile().as_text()
    assert text.count("paged_decode_fwd") >= 1
    assert "tpu_custom_call" in text


def test_bert_recipe_step_leaves_room_for_a_second_copy_of_the_weights(
    one_chip, lower_as_chip, bert_recipe
):
    """``examples/bert/pretrain_bert.py``'s step at the benchmark cell's
    size (BERT-Large, 128 rows of 128 tokens, K = 20, one step a call),
    under the checkpoint form the recipe builds: it compiles, the Pallas
    LayerNorm kernels are in it, and XLA's own count — arguments + outputs
    + temporaries, less what is aliased — leaves 1.5 GB of the chip's 16 GB:
    the harness holds a second copy of the weights (1.34 GB) for a moment.
    (XLA's count over-reads what the chip needs: a form it counts at 17.1 GB
    ran on the chip's 16.9 GB, PERF.md section 6, PR 38.)"""
    from apex_tpu import parallel_state as ps
    from jax.sharding import NamedSharding, PartitionSpec as P

    recipe = bert_recipe
    rows, seq, k = 128, 128, 20
    args = recipe.parse_args([
        "--batch", str(rows), "--seq-len", str(seq), "--chunk", "1",
        "--max-predictions-per-seq", str(k),
    ])
    model = recipe.BertForPreTraining(recipe.model_config(args))
    tx = recipe.fused_lamb(learning_rate=args.lr, weight_decay=0.01)
    (device,) = one_chip.device_set
    mesh = ps.initialize_model_parallel(devices=[device])

    def on_mesh(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec)
        )

    params = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((seq, rows), jnp.int32)
    )
    state = jax.tree_util.tree_map(
        lambda x: on_mesh(x.shape, x.dtype),
        (params, jax.eval_shape(tx.init, params)),
    )
    by_row = P(None, None, "dp")
    batches = {
        "input_ids": on_mesh((1, seq, rows), jnp.int32, by_row),
        "token_type_ids": on_mesh((1, seq, rows), jnp.int32, by_row),
        "attention_mask": on_mesh((1, rows, seq), jnp.int32, P(None, "dp")),
        "nsp_labels": on_mesh((1, rows), jnp.int32, P(None, "dp")),
        "mlm_positions": on_mesh((1, k, rows), jnp.int32, by_row),
        "mlm_label_ids": on_mesh((1, k, rows), jnp.int32, by_row),
        "mlm_weights": on_mesh((1, k, rows), jnp.float32, by_row),
    }
    compiled = recipe.build_step(model, tx, mesh, k).lower(
        *state, batches
    ).compile()
    text = compiled.as_text()
    assert "layer_norm_fwd" in text and "layer_norm_bwd" in text
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    weights = sum(
        x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params)
    )
    assert 1.3e9 < weights < 1.4e9, weights
    assert held < 16e9 - 1.5e9, held
