"""A stack whose layers differ in kind (KDA / MLA mixers, dense / routed
FFNs) through the serving engine and scheduler, against the plain reference
``benchmark/reference/ling.py`` — LOGITS, not tokens.

Tolerances, and why each:

- ``F32_TOL = 2e-5``: program and reference both compute in f32 here; what
  differs is the order of the sums (chunked WY form against the per-token
  recurrence, absorbed against un-absorbed MLA, sorted rows against dense
  masked experts, online softmax).  Logits are O(0.5); the observed gap is
  ~1e-6.  A bf16 recurrent state or a bf16 router would read ~1e-2 and
  flip experts: ``test_bf16_state_or_router_would_fail`` shows both.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from apex_tpu.models.hybrid import init_params  # noqa: E402
from apex_tpu.serve import (  # noqa: E402
    ContinuousBatchingScheduler, InferenceEngine, Request, ServeConfig,
)
from apex_tpu.serve import model as serve_model  # noqa: E402
from apex_tpu.serve.spec import SpecConfig  # noqa: E402
from benchmark.drivers import ling_serve  # noqa: E402
from benchmark.reference import ling as ref_ling  # noqa: E402

F32_TOL = 2e-5

#: the tiny preset: hidden 64, 4 heads of 16, 16 experts in 4 groups,
#: top-4 of 2 groups, 8 layers in the published pattern (2 dense-FFN KDA
#: layers, then KDA KDA KDA MLA KDA KDA, routed), this chip holding group 0
TINY = dict(
    num_hidden_layers=8, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, moe_shared_expert_intermediate_size=32,
    num_attention_heads=4, head_dim=16, num_experts=4,
    num_experts_published=16, held_experts_first=0, num_experts_per_tok=4,
    n_group=4, topk_group=2, routed_scaling_factor=2.5, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, vocab_size=96,
    rope_theta=6000000, rms_norm_eps=1e-6, layer_group_size=6,
    first_k_dense_replace=2, max_position_embeddings=512,
    short_conv_kernel_size=4, kda_lower_bound=-5,
    compute_dtype="float32", param_dtype="float32",
    serve=dict(page_size=8, max_pages_per_seq=16),
)


def tiny(**kw):
    return dict(TINY, **kw)


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    pcfg = ling_serve.program_config(cfg)
    params = init_params(pcfg, seed=3)
    return cfg, pcfg, params, ling_serve.to_reference(params, cfg)


def make_engine(model, **kw):
    _, pcfg, params, _ = model
    serve = dict(page_size=8, num_pages=65, max_batch=3,
                 max_pages_per_seq=16, prefill_buckets=(32, 64),
                 verify=False)
    serve.update(kw)
    return InferenceEngine(pcfg, params, ServeConfig(**serve))


@pytest.fixture(scope="module")
def engine(model):
    """One built engine for the tests that can share it (a prefill writes
    its slot whole, so what an earlier test left in the cache set is part
    of the test)."""
    return make_engine(model).build()


@pytest.fixture(scope="module")
def small():
    """Two layers, one of each kind: KDA + dense, MLA + routed."""
    cfg = tiny(num_hidden_layers=2, layer_group_size=2,
               first_k_dense_replace=1)
    pcfg = ling_serve.program_config(cfg)
    params = init_params(pcfg, seed=11)
    return cfg, pcfg, params, ling_serve.to_reference(params, cfg)


_REF = {}


def reference(model, ids):
    """The reference's logits over ``ids``, in one forward padded to 96
    positions (causal: the padding changes nothing before it), jitted once
    a model."""
    cfg, _, _, weights = model
    key = id(weights)
    if key not in _REF:
        rcfg = ling_serve.reference_config(cfg)
        held = ling_serve.held_experts(cfg)
        _REF[key] = jax.jit(
            lambda w, ids: ref_ling.logits(w, ids, rcfg, held))
    padded = np.zeros((96,), np.int32)
    padded[: len(ids)] = ids
    with jax.default_matmul_precision("highest"):
        return np.asarray(_REF[key](weights, jnp.asarray(padded)))[: len(ids)]


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


# -- (a) prefill + decode through the cache set against the reference -------


def test_whole_stack_prefill_then_decode_matches_reference(model, engine):
    eng = engine
    rs = np.random.RandomState(0)
    prompt = [int(t) for t in rs.randint(0, 96, size=21)]
    got, seq = stream(eng, prompt, 1, [5, 9, 2, 7], 8)
    want = reference(model, seq)[len(prompt) - 1:]
    np.testing.assert_allclose(got, want, atol=F32_TOL)
    assert np.abs(want).max() > 0.1       # the comparison has something in it


@pytest.mark.parametrize("kinds", [
    dict(layer_group_size=99, first_k_dense_replace=99),   # KDA + dense
    dict(layer_group_size=1, first_k_dense_replace=99),    # MLA + dense
    dict(layer_group_size=99, first_k_dense_replace=0),    # KDA + routed
    dict(layer_group_size=1, first_k_dense_replace=0),     # MLA + routed
], ids=["kda-dense", "mla-dense", "kda-moe", "mla-moe"])
def test_each_layer_kind_alone_matches_reference(kinds):
    cfg = tiny(num_hidden_layers=2, **kinds)
    pcfg = ling_serve.program_config(cfg)
    params = init_params(pcfg, seed=5)
    m = (cfg, pcfg, params, ling_serve.to_reference(params, cfg))
    eng = make_engine(m)
    prompt = [int(t) for t in np.random.RandomState(1).randint(0, 96, 19)]
    got, seq = stream(eng, prompt, 0, [3, 4, 6], 4)
    want = reference(m, seq)[len(prompt) - 1:]
    np.testing.assert_allclose(got, want, atol=F32_TOL)


def test_bf16_state_or_router_would_fail():
    """What the tolerance is there to catch.  The recurrent state rounded
    to bf16 after every token moves a KDA layer's output by far more than
    ``F32_TOL``; so does the router fed a bf16 input (and it flips
    experts)."""
    from apex_tpu.ops import kda
    from apex_tpu.transformer.moe import route_group_limited

    rs = np.random.RandomState(2)
    s, h, d = 48, 4, 16
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    q, k = (jnp.asarray(unit(rs.randn(s, h, d)), jnp.float32) for _ in "qk")
    v = jnp.asarray(rs.randn(s, h, d), jnp.float32)
    g = -5 * jax.nn.sigmoid(jnp.asarray(rs.randn(s, h, d) - 4, jnp.float32))
    beta = jax.nn.sigmoid(jnp.asarray(rs.randn(s, h), jnp.float32))
    want, _ = kda.kda_recurrent(q, k, v, g, beta)
    state, rows = jnp.zeros((h, d, d)), []
    for t in range(s):
        o, state = kda.kda_recurrent(
            q[t:t + 1], k[t:t + 1], v[t:t + 1], g[t:t + 1], beta[t:t + 1],
            state)
        state = state.astype(jnp.bfloat16).astype(jnp.float32)
        rows.append(o[0])
    assert np.abs(np.stack(rows) - want).max() > 20 * F32_TOL

    y = jnp.asarray(rs.randn(256, 64), jnp.float32)
    router = jnp.asarray(rs.randn(64, 16) / 8, jnp.float32)
    kw = dict(top_k=4, n_group=4, topk_group=2, scale=2.5)
    idx, w = route_group_limited(y, router, jnp.zeros((16,)), **kw)
    idx16, w16 = route_group_limited(
        y.astype(jnp.bfloat16), router, jnp.zeros((16,)), **kw)
    same = np.asarray(idx) == np.asarray(idx16)
    assert np.abs(np.where(same, w - w16, 0.0)).max() > 20 * F32_TOL


# -- (b) the share: eight chips' routed parts + the shared expert once ------


def test_shares_add_up_to_the_uncut_layer():
    """Each of the 4 chips of this preset's group (held experts 4j..4j+3)
    computes its routed part through the program's dropless layer; the
    parts plus the shared expert counted once are the reference's whole
    layer over all 16 experts."""
    from apex_tpu.transformer.moe import dropless_moe, route_group_limited

    cfg = tiny()
    rs = np.random.RandomState(4)
    h, e, im = 64, 16, 32
    lp = dict(
        router=jnp.asarray(rs.randn(h, e) / 8, jnp.float32),
        bias=jnp.zeros((e,)),
        e_gate=jnp.asarray(rs.randn(e, h, im) * 0.1, jnp.float32),
        e_up=jnp.asarray(rs.randn(e, h, im) * 0.1, jnp.float32),
        e_down=jnp.asarray(rs.randn(e, im, h) * 0.1, jnp.float32),
        s_gate=jnp.asarray(rs.randn(h, im) * 0.1, jnp.float32),
        s_up=jnp.asarray(rs.randn(h, im) * 0.1, jnp.float32),
        s_down=jnp.asarray(rs.randn(im, h) * 0.1, jnp.float32),
    )
    y = jnp.asarray(rs.randn(29, h), jnp.float32)
    rcfg = ling_serve.reference_config(cfg)
    with jax.default_matmul_precision("highest"):
        whole = ref_ling.moe(lp, y, rcfg, list(range(e)), "f32")
        shared = ref_ling.swiglu(y, lp["s_gate"], lp["s_up"], lp["s_down"],
                                 "f32")
        idx, w = route_group_limited(
            y, lp["router"], lp["bias"], top_k=4, n_group=4, topk_group=2,
            scale=2.5)
        total, pairs = shared, 0
        for chip in range(4):
            lo = 4 * chip
            part, stats = dropless_moe(
                y, idx, w, dict(gate=lp["e_gate"][lo:lo + 4],
                                up=lp["e_up"][lo:lo + 4],
                                down=lp["e_down"][lo:lo + 4]),
                held=(lo, 4), tile=8)
            total, pairs = total + part, pairs + int(stats[0])
    assert pairs == 29 * 4          # every (token, expert) pair lands once
    np.testing.assert_allclose(total, whole, atol=F32_TOL)


# -- (c) bucket padding leaves state and logits as the unpadded prompt's ----


def test_padded_prompt_leaves_state_and_logits_equal(small):
    prompt = [int(t) for t in np.random.RandomState(6).randint(0, 96, 32)]
    tight = make_engine(small, prefill_buckets=(32,))
    loose = make_engine(small, prefill_buckets=(64,))
    got_t, _ = stream(tight, prompt, 2, [1, 2, 3, 4, 5], 3)
    got_l, _ = stream(loose, prompt, 2, [1, 2, 3, 4, 5], 3)
    # same chunks, same order of sums: the padding rows are identities
    np.testing.assert_allclose(got_l, got_t, atol=1e-6)
    for name in ("state", "conv"):
        np.testing.assert_allclose(
            np.asarray(loose.cache[name][:, 2]),
            np.asarray(tight.cache[name][:, 2]), atol=1e-6)


# -- (d) slot reuse, leaks ---------------------------------------------------


def test_reused_slot_starts_from_its_own_sequence(model, engine):
    eng = engine
    rs = np.random.RandomState(7)
    first = [int(t) for t in rs.randint(0, 96, 30)]
    second = [int(t) for t in rs.randint(0, 96, 17)]
    stream(eng, first, 0, [1, 2, 3, 4, 5], 6)       # dirties slot 0
    got, seq = stream(eng, second, 0, [6, 7, 8], 5)
    want = reference(model, seq)[len(second) - 1:]
    np.testing.assert_allclose(got, want, atol=F32_TOL)


def run_requests(eng, n=7, **sched_kw):
    sched = ContinuousBatchingScheduler(eng, **sched_kw)
    rs = np.random.RandomState(8)
    reqs = [sched.submit(Request(
        prompt=[int(t) for t in rs.randint(0, 96, rs.randint(5, 60))],
        max_new_tokens=int(rs.randint(2, 9)),
    )) for _ in range(n)]
    sched.run()
    return eng, sched, reqs


def test_scheduler_serves_reference_tokens_and_leaks_nothing(model, engine):
    eng, sched, reqs = run_requests(engine)
    assert all(r.status == "done" for r in reqs)
    assert eng.pool.in_use == 0 and sched.slots_in_use() == 0
    for r in reqs[:3]:
        want = reference(model, r.prompt + r.tokens)
        greedy = want[len(r.prompt) - 1:-1].argmax(-1)
        assert list(greedy) == r.tokens


def test_shed_request_leaks_neither_pages_nor_slot(small):
    # 8 usable pages: three 24-token prompts fit, their growth does not
    eng = make_engine(small, num_pages=9, max_pages_per_seq=8)
    sched = ContinuousBatchingScheduler(eng)
    rs = np.random.RandomState(9)
    reqs = [sched.submit(Request(
        prompt=[int(t) for t in rs.randint(0, 96, 24)], max_new_tokens=30,
    )) for _ in range(3)]
    sched.run()
    assert {r.status for r in reqs} == {"done", "shed"}
    assert eng.pool.in_use == 0 and sched.slots_in_use() == 0


def test_retried_request_prefills_again_into_its_new_slot(
        engine, monkeypatch):
    """A decode fault sends every rider to `retrying`; a model with
    recurrent layers re-prefills prompt + generated prefix (its state is
    not retained), and the stream is the fault-free one."""
    _, _, clean = run_requests(engine, n=3)
    before = engine.prefill_calls
    real, calls = engine.decode, []

    def faulty(*a, **k):
        calls.append(1)
        if len(calls) == 3:
            raise RuntimeError("injected decode fault")
        return real(*a, **k)

    monkeypatch.setattr(engine, "decode", faulty)
    monkeypatch.setattr(engine, "rebuild", lambda **k: engine)
    eng, sched, reqs = run_requests(engine, n=3)
    assert [r.tokens for r in reqs] == [r.tokens for r in clean]
    assert all(r.status == "done" for r in reqs)
    assert sum(r.retries for r in reqs) > 0
    # the retried requests went through prefill again
    assert eng.prefill_calls - before > len(reqs)
    assert eng.pool.in_use == 0 and sched.slots_in_use() == 0


# -- (f) what a stateful model refuses, by name ------------------------------


def test_refusals_name_the_mechanism(small):
    _, pcfg, params, _ = small
    eng = make_engine(small)
    with pytest.raises(ValueError, match="prefix cache"):
        ContinuousBatchingScheduler(eng, prefix_cache=True)
    with pytest.raises(ValueError, match="chunked prefill"):
        ContinuousBatchingScheduler(eng, prefill_chunk_tokens=8)
    with pytest.raises(ValueError, match="speculative programs"):
        InferenceEngine(pcfg, params, ServeConfig(), spec=SpecConfig(None, k=2))
    with pytest.raises(ValueError, match="int8"):
        InferenceEngine(pcfg, params, ServeConfig(kv_wire="int8"))
    # a stack without recurrent layers is not refused these
    serve_model.validate_features(
        ling_serve.program_config(tiny(layer_group_size=1)),
        prefix_cache=True, chunked_prefill=True, spec=True)


# -- counters, gauges, the program table --------------------------------------


def test_moe_counts_ride_the_token_readback_and_fold_on_the_host(engine):
    from apex_tpu.observability import MetricRegistry

    eng = engine
    assert list(eng.compile_counts) == ["prefill_32", "prefill_64", "decode"]
    calls = eng.prefill_calls, eng.decode_iters
    registry = MetricRegistry(fetch_every=1)
    sched = ContinuousBatchingScheduler(eng, registry=registry)
    sched.submit(Request(prompt=list(range(20)), max_new_tokens=4))
    sched.step()
    pairs, touched = eng.last_moe_counts
    assert 0 < touched <= 6 * 4 and pairs >= touched
    sched.run()
    registry.fetch()
    vals = registry.values()
    assert vals["serve/moe/routed_local_tokens"] >= vals[
        "serve/moe/experts_touched"] > 0
    assert vals["serve/state/slots_in_use"] == 0
    assert vals["serve/latent/pages_in_use"] == 0
    # four programs ran, each read back ONE token array
    assert (eng.prefill_calls - calls[0], eng.decode_iters - calls[1]) \
        == (1, 3)


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
    assert eng.kinds is None and not eng.stateful and not eng.routed
    assert eng.last_moe_counts is None
    assert not any(k.startswith(("serve/moe", "serve/state", "serve/latent"))
                   for k in registry.values())
    assert eng._host_args("prefill", 8).size == 8 + 1 + 3   # no slot field


def test_engine_spans_carry_the_kinds(engine):
    from apex_tpu.observability.spans import SpanRecorder

    eng = engine
    rec = SpanRecorder(256)
    sched = ContinuousBatchingScheduler(eng, spans=rec)
    sched.submit(Request(prompt=[1, 2, 3, 4], max_new_tokens=2))
    sched.run()
    spans = {e["name"]: e for e in rec.snapshot() if "id" in e}
    for name in ("engine/prefill", "engine/decode"):
        assert spans[name]["args"]["kinds"] == "dense,kda,mla,moe"


# -- decode blocks: several iterations a program ------------------------------


def test_decode_block_serves_the_single_step_streams(small):
    """`decode_block = 4`: each program runs four iterations, a slot stops
    at its own budget inside a block, and every stream is the one a token a
    call serves — greedy and at temperature (the keys are the emission
    index's)."""
    from apex_tpu.observability import MetricRegistry

    def serve_all(block):
        eng = make_engine(small, decode_block=block, prefill_buckets=(32,))
        registry = MetricRegistry(fetch_every=1)
        sched = ContinuousBatchingScheduler(eng, registry=registry)
        rs = np.random.RandomState(12)
        reqs = [sched.submit(Request(
            prompt=[int(t) for t in rs.randint(0, 96, rs.randint(5, 30))],
            max_new_tokens=int(rs.randint(2, 12)),
            temperature=0.0 if i % 2 else 0.8, stream_seed=100 + i,
        )) for i in range(6)]
        sched.run()
        assert all(r.status == "done" for r in reqs)
        assert all(len(r.tokens) == r.max_new_tokens for r in reqs)
        assert eng.pool.in_use == 0 and sched.slots_in_use() == 0
        registry.fetch()
        return [r.tokens for r in reqs], eng.decode_iters, registry.values()

    one, calls_one, vals_one = serve_all(1)
    four, calls_four, vals_four = serve_all(4)
    assert four == one
    assert calls_four < calls_one / 2
    # the counts are summed over a block's iterations
    assert vals_four["serve/moe/routed_local_tokens"] == vals_one[
        "serve/moe/routed_local_tokens"]
    assert vals_four["serve/tokens_out"] == vals_one["serve/tokens_out"]


def test_decode_block_is_a_hybrid_stacks(small):
    from apex_tpu.models.gpt import GptConfig, GptModel

    cfg = GptConfig(vocab_size=64, hidden_size=32, num_layers=1, num_heads=2,
                    intermediate_size=64, max_seq_len=64, dtype=jnp.float32)
    params = GptModel(cfg).init(jax.random.PRNGKey(0),
                                jnp.zeros((8, 1), jnp.int32))
    with pytest.raises(ValueError, match="decode_block"):
        InferenceEngine(cfg, params, ServeConfig(decode_block=4))
    with pytest.raises(ValueError, match="decode_block"):
        ServeConfig(decode_block=0)
    eng = make_engine(small, decode_block=4, prefill_buckets=(32,))
    toks, _, finite = eng.probe_stream(list(range(9)), 5)
    ref = make_engine(small, prefill_buckets=(32,)).probe_stream(
        list(range(9)), 5)
    assert toks == ref[0] and finite
