"""Falcon-H1's parallel hybrid block (a Mamba-2 state-space branch and a
grouped-query attention branch from one norm, both added to the residual)
through the serving engine and scheduler, against the plain reference
``benchmark/reference/falcon_h1.py`` — LOGITS, not tokens.

The tiny preset keeps the published ratios: 5 query heads a KV head, 2
state-space groups with the heads split over them, every muP multiplier as
published; weights by the benchmark's laws (unit-spread logits).

Tolerance: ``F32_TOL = 2e-5``.  Program and reference both compute in f32
here; what differs is the order of the sums (chunked SSD form against the
per-token recurrence, online softmax over pages against a dense softmax,
one fused projection against three).  Logits are O(1); the observed gap is
~2e-6.  A bf16 state would read ~1e-2 (``tests/test_ssm_kernels.py``).
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from apex_tpu.models.hybrid import (  # noqa: E402
    HybridConfig, init_params, ling_pattern, param_shapes,
)
from apex_tpu.serve import (  # noqa: E402
    ContinuousBatchingScheduler, InferenceEngine, Request, ServeConfig,
)
from apex_tpu.serve import cache as cache_lib  # noqa: E402
from apex_tpu.serve import model as serve_model  # noqa: E402
from apex_tpu.serve.spec import SpecConfig  # noqa: E402
from benchmark.drivers import falcon_h1_serve as drv  # noqa: E402
from benchmark.drivers import ling_serve  # noqa: E402
from benchmark.reference import falcon_h1 as ref_h1  # noqa: E402

F32_TOL = 2e-5
CONFIGS = os.path.join(ROOT, "benchmark", "configs")


def load(name):
    with open(os.path.join(CONFIGS, name)) as f:
        return json.load(f)


def tiny(**kw):
    """The configuration file's own rehearsal size (the published ratios),
    two layers."""
    cfg = load("falcon-h1-34b-instruct-4l.json")
    cfg = dict(cfg, **{k: v for k, v in cfg["rehearsal"].items()
                       if k != "serve"})
    return dict(cfg, num_hidden_layers=2, **kw)


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    pcfg = drv.program_config(cfg)
    params = drv.seeded_weights(param_shapes(pcfg), 3, cfg)
    return cfg, pcfg, params, drv.to_reference(params, cfg)


def make_engine(model, params=None, **kw):
    _, pcfg, own, _ = model
    serve = dict(page_size=8, num_pages=65, max_batch=3,
                 max_pages_per_seq=16, prefill_buckets=(32, 64),
                 verify=False)
    serve.update(kw)
    return InferenceEngine(pcfg, params or own, ServeConfig(**serve))


@pytest.fixture(scope="module")
def engine(model):
    return make_engine(model).build()


def reference(model, ids, branches=ref_h1.BRANCHES):
    cfg, _, _, weights = model
    padded = np.zeros((96,), np.int32)
    padded[: len(ids)] = ids
    with jax.default_matmul_precision("highest"):
        out = ref_h1.logits(weights, jnp.asarray(padded), cfg,
                            branches=branches)
    return np.asarray(out)[: len(ids)]


def stream(eng, prompt, slot, pages, n_new):
    """Prefill into ``slot`` then decode greedily: every step's logits."""
    b, ps = eng.serve.max_batch, eng.serve.page_size
    logits, tok = eng.prefill(prompt, pages[: -(-len(prompt) // ps)],
                              slot=slot)
    rows, seq = [np.asarray(logits)], list(prompt)
    table = np.zeros((b, eng.serve.max_pages_per_seq), np.int32)
    table[slot, : len(pages)] = pages
    for _ in range(n_new):
        seq.append(tok)
        tokens, lengths = np.zeros((b,), np.int32), np.zeros((b,), np.int32)
        tokens[slot], lengths[slot] = tok, len(seq)
        logits, toks = eng.decode(tokens, lengths, table)
        rows.append(np.asarray(logits)[slot])
        tok = int(toks[slot])
    return np.stack(rows), seq


def prompt_of(seed, n, vocab=128):
    return [int(t) for t in np.random.RandomState(seed).randint(0, vocab, n)]


# -- (a) prefill + decode through the cache set against the reference -------


def test_whole_stack_prefill_then_decode_matches_reference(model, engine):
    prompt = prompt_of(0, 21)
    got, seq = stream(engine, prompt, 1, [5, 9, 2, 7], 8)
    want = reference(model, seq)[len(prompt) - 1:]
    np.testing.assert_allclose(got, want, atol=F32_TOL)
    assert np.abs(want).max() > 1.0       # unit-spread logits: something in it


@pytest.mark.parametrize("branch,dead", [
    ("ssm", ("attn", "wo")), ("attention", ("ssm", "out_proj")),
], ids=["ssm-alone", "attention-alone"])
def test_each_branch_alone_matches_reference(model, branch, dead):
    """One branch's output projection zeroed in the program, that branch
    left out of the reference: the other branch, through its own cache kind,
    carries the block."""
    _, _, params, _ = model
    tree = jax.tree_util.tree_map(lambda x: x, params)
    for lp in tree["params"]["layers"]:
        leaf = lp[dead[0]][dead[1]]
        leaf["weight"] = leaf["weight"] * 0
    eng = make_engine(model, tree)
    prompt = prompt_of(1, 19)
    got, seq = stream(eng, prompt, 0, [3, 4, 6], 5)
    want = reference(model, seq, branches=(branch,))[len(prompt) - 1:]
    np.testing.assert_allclose(got, want, atol=F32_TOL)
    both = reference(model, seq)[len(prompt) - 1:]
    assert np.abs(both - want).max() > 100 * F32_TOL   # the other one matters


def test_the_kernels_serve_the_same_logits(model, engine):
    """Every Pallas kernel of the stack (interpret mode) against the jnp
    paths the shared engine took."""
    from apex_tpu.ops import _dispatch

    prompt = prompt_of(2, 40)
    want, _ = stream(engine, prompt, 2, [11, 12, 13, 14, 15, 16], 4)
    _dispatch.set_use_pallas(True)
    try:
        got, _ = stream(make_engine(model), prompt, 2,
                        [11, 12, 13, 14, 15, 16], 4)
        paths = _dispatch.last_paths()
    finally:
        _dispatch.set_use_pallas(None)
    assert {paths[k] for k in ("ssd_chunk", "ssm_step", "flash_attention",
                               "paged_decode_attention")} == {"pallas"}
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_padded_prompt_leaves_state_and_logits_equal(model):
    prompt = prompt_of(6, 32)
    tight = make_engine(model, prefill_buckets=(32,))
    loose = make_engine(model, prefill_buckets=(64,))
    got_t, _ = stream(tight, prompt, 2, [1, 2, 3, 4, 5], 3)
    got_l, _ = stream(loose, prompt, 2, [1, 2, 3, 4, 5], 3)
    np.testing.assert_allclose(got_l, got_t, atol=2e-6)
    for name in ("ssm", "ssm_conv"):
        np.testing.assert_allclose(
            np.asarray(loose.cache[name][:, 2]),
            np.asarray(tight.cache[name][:, 2]), atol=2e-6)


# -- (b) the slot contract: replaced at admission, kept by an idle row --------


def test_admission_replaces_a_slots_state_and_tail(model, engine):
    first, second = prompt_of(7, 30), prompt_of(8, 17)
    stream(engine, first, 0, [1, 2, 3, 4, 5], 6)        # dirties slot 0
    dirty = {n: np.asarray(engine.cache[n][:, 0]) for n in ("ssm", "ssm_conv")}
    got, seq = stream(engine, second, 0, [6, 7, 8], 5)
    want = reference(model, seq)[len(second) - 1:]
    np.testing.assert_allclose(got, want, atol=F32_TOL)
    # and what the prefill left is what a clean engine's prefill leaves
    fresh = make_engine(model)
    fresh.prefill(second, [6, 7, 8], slot=0)
    engine.prefill(second, [6, 7, 8], slot=0)
    for name in ("ssm", "ssm_conv"):
        now = np.asarray(engine.cache[name][:, 0])
        np.testing.assert_allclose(
            now, np.asarray(fresh.cache[name][:, 0]), atol=1e-6)
        assert np.abs(now - dirty[name]).max() > 1e-3


def test_idle_row_of_a_decode_block_keeps_state_and_tail(model):
    """A block of 4 iterations: slot 0 runs all four, slot 1 two, slot 2
    none.  Slot 2's state and tail are bit for bit what they were; slot 1's
    are what two single steps leave; each stream is the single-step one."""
    blk = make_engine(model, decode_block=4, prefill_buckets=(32,))
    one = make_engine(model, prefill_buckets=(32,))
    prompts = [prompt_of(20 + i, 9 + 4 * i) for i in range(3)]
    pages = [[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12]]
    toks = []
    for eng in (blk, one):
        toks.append([eng.prefill(p, pg[: -(-len(p) // 8)], slot=i)[1]
                     for i, (p, pg) in enumerate(zip(prompts, pages))])
    assert toks[0] == toks[1]
    table = np.zeros((3, 16), np.int32)
    for i, pg in enumerate(pages):
        table[i, : len(pg)] = pg
    before = {n: np.asarray(blk.cache[n]) for n in ("ssm", "ssm_conv")}
    tokens = np.asarray(toks[0], np.int32)
    lengths = np.asarray([len(p) + 1 for p in prompts], np.int32)
    steps = np.asarray([4, 2, 0], np.int32)
    live = np.where(steps > 0, lengths, 0).astype(np.int32)
    _, out = blk.decode(tokens, live, table, steps=steps)
    out = np.asarray(out).reshape(4, 3)
    # the same through single steps
    cur, lens, want = tokens.copy(), lengths.copy(), []
    for j in range(4):
        on = steps > j
        _, nxt = one.decode(cur, np.where(on, lens, 0).astype(np.int32),
                            table)
        nxt = np.where(on, np.asarray(nxt), cur)
        want.append(nxt)
        cur, lens = nxt.astype(np.int32), lens + on
        if j == 1:
            two = {n: np.asarray(one.cache[n][:, 1])
                   for n in ("ssm", "ssm_conv")}
    np.testing.assert_array_equal(out, np.stack(want))
    for name in ("ssm", "ssm_conv"):
        after = np.asarray(blk.cache[name])
        np.testing.assert_array_equal(after[:, 2], before[name][:, 2])
        np.testing.assert_allclose(after[:, 1], two[name], atol=1e-6)
        assert np.abs(after[:, 0] - before[name][:, 0]).max() > 1e-4


# -- (c) through the scheduler -------------------------------------------------


def run_requests(eng, n=7, **sched_kw):
    sched = ContinuousBatchingScheduler(eng, **sched_kw)
    rs = np.random.RandomState(8)
    reqs = [sched.submit(Request(
        prompt=[int(t) for t in rs.randint(0, 128, rs.randint(5, 60))],
        max_new_tokens=int(rs.randint(2, 9)),
    )) for _ in range(n)]
    sched.run()
    return sched, reqs


def test_scheduler_serves_reference_tokens_and_counts_the_state(model, engine):
    from apex_tpu.observability import MetricRegistry

    registry = MetricRegistry(fetch_every=1)
    sched, reqs = run_requests(engine, registry=registry)
    assert all(r.status == "done" for r in reqs)
    assert engine.pool.in_use == 0 and sched.slots_in_use() == 0
    for r in reqs[:3]:
        want = reference(model, r.prompt + r.tokens)
        assert list(want[len(r.prompt) - 1:-1].argmax(-1)) == r.tokens
    registry.fetch()
    vals = registry.values()
    # every admission replaced a slot's state; the gauge is riders x the
    # slab's bytes a slot (2 layers x 4 heads x 8 x 16 f32) x 2
    assert vals["serve/ssm/slots_written"] == len(reqs)
    slot_bytes = 2 * 4 * 8 * 16 * 4
    assert vals["serve/ssm/state_bytes_per_iter"] % (2 * slot_bytes) == 0
    assert 0 < vals["serve/ssm/state_bytes_per_iter"] <= 3 * 2 * slot_bytes
    assert vals["serve/state/slots_in_use"] == 0


def test_decode_block_serves_the_single_step_streams(model):
    def serve_all(block):
        eng = make_engine(model, decode_block=block, prefill_buckets=(32,))
        sched = ContinuousBatchingScheduler(eng)
        rs = np.random.RandomState(12)
        reqs = [sched.submit(Request(
            prompt=[int(t) for t in rs.randint(0, 128, rs.randint(5, 30))],
            max_new_tokens=int(rs.randint(2, 12)),
            temperature=0.0 if i % 2 else 0.8, stream_seed=100 + i,
        )) for i in range(6)]
        sched.run()
        assert all(r.status == "done" for r in reqs)
        assert eng.pool.in_use == 0 and sched.slots_in_use() == 0
        return [r.tokens for r in reqs], eng.decode_iters

    one, calls_one = serve_all(1)
    four, calls_four = serve_all(4)
    assert four == one and calls_four < calls_one / 2


def test_refusals_name_the_mechanism(model):
    _, pcfg, params, _ = model
    eng = make_engine(model)
    with pytest.raises(ValueError, match="prefix cache"):
        ContinuousBatchingScheduler(eng, prefix_cache=True)
    with pytest.raises(ValueError, match="chunked prefill"):
        ContinuousBatchingScheduler(eng, prefill_chunk_tokens=8)
    with pytest.raises(ValueError, match="speculative programs"):
        InferenceEngine(pcfg, params, ServeConfig(), spec=SpecConfig(None, k=2))
    with pytest.raises(ValueError, match="int8"):
        InferenceEngine(pcfg, params, ServeConfig(kv_wire="int8"))


# -- (d) the pattern is data; the cache set is declared -------------------------


def test_ling_files_pattern_is_pinned():
    """``layer_group_size`` / ``first_dense_layers`` still STATE the Ling
    pattern; the tuple they produce for the benchmark's file is this one."""
    pcfg = ling_serve.program_config(load("ling-3.0-flash-vl-ep8.json"))
    want = (
        ("kda", "dense"), ("kda", "dense"), ("kda", "moe"), ("kda", "moe"),
        ("kda", "moe"), ("mla", "moe"), ("kda", "moe"), ("kda", "moe"),
    )
    assert pcfg.pattern == pcfg.kinds == want
    assert ling_pattern(8, 6, 2, True) == want
    assert serve_model.layer_kinds(pcfg) == want


def test_pattern_is_the_configurations_data():
    base = dict(vocab_size=32, hidden_size=16, num_layers=3, num_heads=2,
                head_dim=8, intermediate_size=32, max_seq_len=64)
    mixed = HybridConfig(
        **base, pattern=(("mla", "dense"), ("kda", "dense"),
                         ("mla", "dense")))
    assert mixed.layers_of("mla") == (0, 2) and mixed.stateful
    assert not HybridConfig(
        **base, pattern=(("mla", "dense"),) * 3).stateful
    with pytest.raises(ValueError, match="names 2 layers"):
        HybridConfig(**base, pattern=(("mla", "dense"),) * 2)
    with pytest.raises(ValueError, match="unknown layer kind"):
        HybridConfig(**base, pattern=(("mamba", "dense"),) * 3)
    with pytest.raises(ValueError, match="ssm_heads"):
        HybridConfig(**base, pattern=(("ssm_gqa", "dense"),) * 3)
    with pytest.raises(ValueError, match="num_kv_heads"):
        HybridConfig(**dict(base, num_heads=5), num_kv_heads=2,
                     ssm_heads=2, ssm_head_dim=4, ssm_state=8,
                     pattern=(("ssm_gqa", "dense"),) * 3)


def test_cache_set_is_declared_once_a_kind(model):
    cfg, pcfg, _, _ = model
    kinds = {k.name: k for k in cache_lib.hybrid_cache_kinds(pcfg, 8)}
    assert sorted(kinds) == ["k", "ssm", "ssm_conv", "v"]
    assert (kinds["k"].per, kinds["ssm"].per) == ("token", "slot")
    assert kinds["ssm"].in_place and not kinds["ssm_conv"].in_place
    cache = cache_lib.init_hybrid_cache(pcfg, 17, 8, 3)
    # K/V pages at the KV heads, as the GPT pool lays them
    gpt = cache_lib.init_kv_pages(2, 17, 2, 8, 8, dtype=jnp.float32)
    assert cache["k"].shape == gpt["k"].shape == (2, 17, 2, 8, 128)
    assert cache["ssm"].shape == (2, 3, 4, 8, 16)
    assert cache["ssm"].dtype == jnp.float32
    assert cache["ssm_conv"].shape == (2, 3, 3, 32 + 2 * 2 * 16)
    # the published widths, from the same declaration
    full = drv.program_config(load("falcon-h1-34b-instruct-4l.json"))
    shapes = {k.name: k.full_shape(10241, 128)
              for k in cache_lib.hybrid_cache_kinds(full, 16)}
    assert shapes == {
        "k": (4, 10241, 4, 16, 128), "v": (4, 10241, 4, 16, 128),
        "ssm": (4, 128, 32, 128, 256), "ssm_conv": (4, 128, 3, 5120),
    }
    # Ling's set reads the same declaration and keeps its names
    ling = ling_serve.program_config(load("ling-3.0-flash-vl-ep8.json"))
    names = [k.name for k in cache_lib.hybrid_cache_kinds(ling, 16)]
    assert sorted(names) == ["conv", "latent", "state"]


def test_engine_reads_the_declaration(model, engine):
    assert [k.name for k in engine.cache_kinds] == ["k", "v", "ssm",
                                                    "ssm_conv"]
    intent = engine._pool_intent(engine.cache)
    assert sorted(intent["shapes"]) == sorted(
        engine.cache[n].shape for n in ("k", "v", "ssm"))
    assert list(engine.compile_counts) == ["prefill_32", "prefill_64",
                                           "decode"]


def test_gpt_engine_returns_and_folds_nothing_new():
    from apex_tpu.models.gpt import GptConfig, GptModel
    from apex_tpu.observability import MetricRegistry

    cfg = GptConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                    intermediate_size=64, max_seq_len=64, dtype=jnp.float32)
    params = GptModel(cfg).init(jax.random.PRNGKey(0),
                                jnp.zeros((8, 1), jnp.int32))
    eng = InferenceEngine(cfg, params, ServeConfig(
        page_size=8, num_pages=17, max_batch=2, max_pages_per_seq=8,
        verify=False))
    registry = MetricRegistry(fetch_every=1)
    sched = ContinuousBatchingScheduler(eng, registry=registry)
    sched.submit(Request(prompt=[1, 2, 3], max_new_tokens=3))
    sched.run()
    registry.fetch()
    assert eng.cache_kinds == () and sorted(eng.cache) == ["k", "v"]
    assert sched._ssm_slot_bytes == 0 and sched._slot_state_bytes == 0
    assert not any(k.startswith(("serve/ssm", "serve/state", "serve/moe"))
                   for k in registry.values())
    assert eng._host_args("prefill", 8).size == 8 + 1 + 3   # no slot field


def test_ling_engine_counts_its_state_as_before():
    """Ling's `serve/state/bytes` is still a slot's share of the KDA slab
    alone (not the convolution tails), now read from the declaration."""
    cfg = dict(load("ling-3.0-flash-vl-ep8.json"))
    cfg.update({k: v for k, v in cfg["rehearsal"].items() if k != "serve"})
    pcfg = ling_serve.program_config(dict(cfg, num_hidden_layers=2,
                                          layer_group_size=2,
                                          first_k_dense_replace=1))
    eng = InferenceEngine(pcfg, init_params(pcfg, seed=1), ServeConfig(
        page_size=8, num_pages=17, max_batch=2, max_pages_per_seq=8,
        prefill_buckets=(32,), verify=False))
    sched = ContinuousBatchingScheduler(eng)
    slab = eng.cache["state"]
    assert sched._slot_state_bytes == slab.nbytes // slab.shape[1]
    assert sched._ssm_slot_bytes == 0 and sched._latent


# -- (e) multipliers and laws ---------------------------------------------------


def test_multipliers_are_the_published_ones_and_logits_have_unit_spread(model):
    cfg, pcfg, _, _ = model
    pub = load("falcon-h1-34b-instruct-4l.json")
    for key in ("embedding_multiplier", "lm_head_multiplier",
                "attention_in_multiplier", "attention_out_multiplier",
                "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier"):
        assert getattr(pcfg, key) == pub[key]
    assert pcfg.ssm_multipliers == tuple(pub["ssm_multipliers"])
    assert pcfg.mlp_multipliers == tuple(pub["mlp_multipliers"])
    spread = reference(model, prompt_of(3, 64)).std()
    assert 0.5 < spread < 2.0


def test_a_stack_without_multipliers_traces_no_multiply():
    """Ling's programs are the parent's: the multipliers at 1 add nothing
    to the trace."""
    cfg = dict(load("ling-3.0-flash-vl-ep8.json"))
    cfg.update({k: v for k, v in cfg["rehearsal"].items() if k != "serve"})
    pcfg = ling_serve.program_config(dict(cfg, num_hidden_layers=1,
                                          layer_group_size=1,
                                          first_k_dense_replace=1))
    tree = {"norm_f": {"scale": jnp.ones((64,))},
            "lm_head": {"weight": jnp.ones((64, 96))}}
    text = str(jax.make_jaxpr(
        lambda h: serve_model._hybrid_logits(pcfg, tree, h))(
            jnp.ones((2, 64))))
    x = jnp.ones((2,))
    assert serve_model._scaled(x, 1.0) is x
    assert text.count(" mul ") == 3      # x*x, x*rsqrt, *scale: RMSNorm's own
