"""GLM-4.7-Flash's stack (latent attention with a low-rank query path in
every layer; a dense first layer, then routed experts top-4 plus a shared
expert) through the serving engine and scheduler, against the plain
reference ``benchmark/reference/glm.py`` — LOGITS, not tokens.  Every
prompt of such a stack is prefilled in page-multiple chunks over the latent
pages (``hybrid_chunk_prefill_body``).

The tiny preset is the configuration file's rehearsal size (five layers,
the published pattern, 8 experts top-4), weights by the benchmark's laws.

Tolerance: ``F32_TOL = 2e-5``.  Program and reference both compute in f32
here; what differs is the order of the sums (absorbed attention over the
pages against the un-absorbed form over the whole sequence, an online
softmax a block of rows at a time, sorted expert rows against dense masked
experts).  Logits are O(0.5); the observed gap is ~2e-7.  A dropped query
norm reads ~2e-3 and attention that forgets the rows before a chunk reads
~0.2 (``test_what_the_tolerance_catches``).
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
    HybridConfig, init_params, param_shapes,
)
from apex_tpu.observability import SpanRecorder  # noqa: E402
from apex_tpu.serve import (  # noqa: E402
    ContinuousBatchingScheduler, InferenceEngine, Request, ServeConfig,
)
from apex_tpu.serve import model as serve_model  # noqa: E402
from apex_tpu.serve.spec import SpecConfig  # noqa: E402
from benchmark.drivers import glm_serve as drv  # noqa: E402
from benchmark.drivers import falcon_h1_serve, ling_serve  # noqa: E402
from benchmark.reference import glm as ref_glm  # noqa: E402

F32_TOL = 2e-5
CONFIGS = os.path.join(ROOT, "benchmark", "configs")
PAGE, CHUNK = 8, 32


def load(name):
    with open(os.path.join(CONFIGS, name)) as f:
        return json.load(f)


def tiny(**kw):
    """The configuration file's own rehearsal size."""
    cfg = load("glm-4.7-flash-5l.json")
    cfg = dict(cfg, **{k: v for k, v in cfg["rehearsal"].items()
                       if k != "serve"})
    return dict(cfg, **kw)


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    pcfg = drv.program_config(cfg)
    params = drv.seeded_weights(param_shapes(pcfg), 3, cfg["hidden_size"])
    params = drv.balance_expert_bias(cfg, params, 3, tokens=64, rounds=20)
    return cfg, pcfg, params, drv.to_reference(params, cfg)


def make_engine(model, **kw):
    _, pcfg, params, _ = model
    serve = dict(page_size=PAGE, num_pages=65, max_batch=3,
                 max_pages_per_seq=24, prefill_buckets=(16, 32),
                 verify=False)
    serve.update(kw)
    return InferenceEngine(pcfg, params, ServeConfig(**serve))


@pytest.fixture(scope="module")
def engine(model):
    return make_engine(model)


_REF = {}


def reference(model, ids):
    """The reference's logits over ``ids``, one forward padded to 192
    positions (causal: the padding changes nothing before it)."""
    cfg, _, _, weights = model
    key = id(weights)
    if key not in _REF:
        _REF[key] = jax.jit(lambda w, ids: ref_glm.logits(w, ids, cfg))
    padded = np.zeros((192,), np.int32)
    padded[: len(ids)] = ids
    with jax.default_matmul_precision("highest"):
        return np.asarray(_REF[key](weights, jnp.asarray(padded)))[: len(ids)]


def chunked(eng, prompt, pages, chunk=CHUNK):
    """The scheduler's chunk walk, by hand: every chunk's logits."""
    out = []
    for start in range(0, len(prompt), chunk):
        end = min(start + chunk, len(prompt))
        logits, tok = eng.chunk_prefill(
            prompt[start:end], start, pages,
            pages[start // PAGE:(end - 1) // PAGE + 1])
        out.append(np.asarray(logits))
    return out, tok


def stream(eng, prompt, slot, pages, n_new, chunk=CHUNK):
    """Chunked prefill then greedy decode: every step's logits."""
    b = eng.serve.max_batch
    rows, tok = chunked(eng, prompt, pages, chunk)
    rows, seq = [rows[-1]], list(prompt)
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


# -- chunked prefill and decode against the reference ------------------------


@pytest.mark.parametrize("n", [29, 64, 157, 150],
                         ids=["1-chunk", "2-chunks", "5-chunks", "mid-page"])
def test_chunked_prefill_then_decode_matches_reference(model, engine, n):
    """1, 2 and 5 chunks; 150 ends inside a page and inside its chunk."""
    rs = np.random.RandomState(n)
    prompt = [int(t) for t in rs.randint(0, 96, size=n)]
    pages = [int(p) for p in rs.permutation(np.arange(1, 65))[:21]]
    got, seq = stream(engine, prompt, 1, pages, 5)
    want = reference(model, seq)[len(prompt) - 1:]
    np.testing.assert_allclose(got, want, atol=F32_TOL)
    assert np.abs(want).max() > 0.1


def test_every_chunk_is_its_last_positions_logits(model, engine):
    """Each chunk's logits are the reference's at the chunk's last row."""
    prompt = [int(t) for t in np.random.RandomState(5).randint(0, 96, 100)]
    pages = list(range(30, 43))
    got, _ = chunked(engine, prompt, pages)
    want = reference(model, prompt)
    ends = [min(s + CHUNK, 100) - 1 for s in range(0, 100, CHUNK)]
    np.testing.assert_allclose(np.stack(got), want[ends], atol=F32_TOL)


def test_chunked_matches_the_single_bucket_prefill(model, engine):
    """One prompt as one chunk at offset 0 and through the un-absorbed
    single-bucket prefill program: the same logits and first token."""
    prompt = [int(t) for t in np.random.RandomState(9).randint(0, 96, 27)]
    chunk_logits, chunk_tok = engine.chunk_prefill(
        prompt, 0, [4, 5, 6, 7], [4, 5, 6, 7])
    logits, tok = engine.prefill(prompt, [8, 9, 10, 11])
    np.testing.assert_allclose(np.asarray(chunk_logits), np.asarray(logits),
                               atol=F32_TOL)
    assert chunk_tok == tok
    # and as three chunks of 16: the chunk grain does not move a logit
    split, split_tok = chunked(engine, prompt, [12, 13, 14, 15], chunk=16)
    np.testing.assert_allclose(split[-1], np.asarray(logits), atol=F32_TOL)
    assert split_tok == tok


# -- the model description ---------------------------------------------------


def test_q_lora_path_and_gate_are_the_configs(model):
    """``q_lora_rank > 0`` holds q_a / q_a_norm / q_b and no q; the gate is
    there only where the config says so (Ling keeps its own)."""
    cfg, pcfg, params, _ = model
    mla = params["params"]["layers"][0]["mla"]
    assert {"q_a", "q_a_norm", "q_b"} <= set(mla) and "q" not in mla
    assert "ogate" not in mla
    assert mla["q_a"]["weight"].shape == (64, 32)
    assert mla["q_b"]["weight"].shape == (32, 4 * (16 + 8))
    assert pcfg.kinds == (("mla", "dense"),) + (("mla", "moe"),) * 4
    ling = param_shapes(ling_serve.program_config(
        dict(load("ling-3.0-flash-vl-ep8.json"), num_hidden_layers=6)))
    lm = ling["params"]["layers"][5]["mla"]
    assert {"q", "ogate"} <= set(lm) and "q_a" not in lm


def test_q_lora_prefill_matches_the_reference(model, engine):
    """The single-bucket prefill's query path (q_a, its norm, q_b) against
    the reference's, at every layer through the stack's last logits."""
    prompt = [int(t) for t in np.random.RandomState(2).randint(0, 96, 31)]
    logits, _ = engine.prefill(prompt, [20, 21, 22, 23])
    np.testing.assert_allclose(np.asarray(logits),
                               reference(model, prompt)[-1], atol=F32_TOL)


def test_what_the_tolerance_catches(model, engine, monkeypatch):
    """A dropped query norm and a chunk that forgets the rows before it
    each move the logits by far more than ``F32_TOL``."""
    prompt = [int(t) for t in np.random.RandomState(4).randint(0, 96, 70)]
    want = reference(model, prompt)[-1]

    def query(cfg, p, y, dtype):
        c_q = serve_model._matmul(y, p["q_a"]["weight"], dtype)
        return serve_model._matmul(c_q, p["q_b"]["weight"], dtype)

    monkeypatch.setattr(serve_model, "_mla_query", query)
    got, _ = chunked(make_engine(model), prompt, list(range(40, 49)))
    assert np.abs(got[-1] - want).max() > 20 * F32_TOL
    monkeypatch.undo()
    real = serve_model.mla_chunk_attention

    def forget(q, pool, table, offset, **k):
        return real(q, pool, jnp.roll(table, -(offset // PAGE)),
                    jnp.zeros_like(offset), **k)

    monkeypatch.setattr(serve_model, "mla_chunk_attention", forget)
    got, _ = chunked(make_engine(model), prompt, list(range(40, 49)))
    assert np.abs(got[-1] - want).max() > 20 * F32_TOL


# -- what serving runs, and refuses, for such a stack ------------------------


def test_validate_features_refuses_by_name(model):
    _, pcfg, params, _ = model
    serve_model.validate_features(pcfg, chunked_prefill=True)
    with pytest.raises(ValueError, match="prefix cache.*latent pages"):
        serve_model.validate_features(pcfg, prefix_cache=True)
    with pytest.raises(ValueError, match="speculative programs.*latent"):
        serve_model.validate_features(pcfg, spec=True)
    eng = make_engine(model)
    with pytest.raises(ValueError, match="prefix cache"):
        ContinuousBatchingScheduler(eng, prefix_cache=True)
    with pytest.raises(ValueError, match="speculative"):
        InferenceEngine(pcfg, params, ServeConfig(
            page_size=PAGE, num_pages=65, max_batch=3, max_pages_per_seq=24,
            verify=False), spec=SpecConfig(draft_params=None, k=2))
    # a stack with a recurrent layer goes on refusing chunked prefill
    ling = ling_serve.program_config(
        dict(load("ling-3.0-flash-vl-ep8.json"), num_hidden_layers=6))
    with pytest.raises(ValueError, match="recurrent.*chunked prefill"):
        serve_model.validate_features(ling, chunked_prefill=True)
    h1 = load("falcon-h1-34b-instruct-4l.json")
    with pytest.raises(ValueError, match="recurrent.*chunked prefill"):
        serve_model.validate_features(
            falcon_h1_serve.program_config(h1), chunked_prefill=True)


def test_engine_has_no_fork_program_and_builds_its_chunks(model):
    eng = make_engine(model)
    kinds = [p.kind for p in eng._kinds()]
    assert "fork_page" not in kinds and "chunk_prefill" in kinds
    eng.build(buckets=(16,), chunked=True)
    assert set(eng.compile_counts) == {
        "prefill_16", "chunk_prefill_16", "decode"}


def test_scheduler_chunks_prompts_past_the_largest_bucket(model):
    """Prompts of up to five chunks (the largest bucket is 32): none is
    shed as oversize, every one ends, no page or slot is left, each
    request's tokens are the reference's greedy stream, and the counters
    say what ran."""
    from apex_tpu.observability import MetricRegistry

    eng = make_engine(model)
    reg = MetricRegistry(fetch_every=1)
    sched = ContinuousBatchingScheduler(
        eng, registry=reg, prefill_chunk_tokens=CHUNK)
    rs = np.random.RandomState(8)
    lens = (150, 40, 97, 16, 128)
    reqs = [sched.submit(Request(
        prompt=[int(t) for t in rs.randint(0, 96, n)], max_new_tokens=4))
        for n in lens]
    sched.run()
    assert [r.status for r in reqs] == ["done"] * len(lens)
    assert not sched.shed and eng.pool.in_use == 0
    assert sched.slots_in_use() == 0
    sched.leak_check()
    for r in reqs[:2]:
        want = reference(model, r.prompt + r.tokens[:-1])
        assert r.tokens == [int(t) for t in np.argmax(
            want[len(r.prompt) - 1:], axis=-1)]
    reg.fetch()
    got = reg.values()
    chunks = sum(-(-n // CHUNK) for n in lens)
    assert got["serve/prefill_chunks"] == chunks
    assert got["serve/shed_oversize"] == 0
    # each chunk reads its rows to its end, each decode its context, in
    # each of the five latent-attention layers
    ends = sum(min(s + CHUNK, n) for n in lens for s in range(0, n, CHUNK))
    decoded = sum(n + 1 + i for n in lens for i in range(3))
    assert got["serve/latent_rows_read"] == 5 * (ends + decoded)


def test_a_chunk_call_sits_under_serve_chunks(model):
    """Under ``serve/chunks``: ``engine/prefill {program =
    chunk_prefill_<bucket>, offset, tokens}``, with its ``engine/dispatch``
    and ``engine/read`` phases under it."""
    eng = make_engine(model)
    rec = SpanRecorder(capacity=4096)
    sched = ContinuousBatchingScheduler(
        eng, spans=rec, prefill_chunk_tokens=CHUNK)
    sched.submit(Request(prompt=list(range(50)), max_new_tokens=2))
    sched.run()
    eng.spans = None
    by_id = {e["id"]: e for e in rec.snapshot() if "id" in e}
    calls = [e for e in by_id.values() if e["name"] == "engine/prefill"]
    assert [(e["args"]["program"], e["args"]["offset"], e["args"]["tokens"])
            for e in calls] == [("chunk_prefill_32", 0, 32),
                                ("chunk_prefill_32", 32, 18)]
    assert {by_id[e["parent"]]["name"] for e in calls} == {"serve/chunks"}
    under = {by_id[e["parent"]]["args"].get("offset")
             for e in by_id.values()
             if e["name"] in ("engine/dispatch", "engine/read")
             and by_id[e["parent"]]["name"] == "engine/prefill"}
    assert under == {0, 32}


# -- the stacks the benchmark already has: streams as recorded --------------

#: greedy token streams of the configurations' rehearsal sizes through the
#: scheduler (four requests, decode blocks of 2), as the parent of this
#: change served them
RECORDED = {
    "ling-3.0-flash-vl-ep8": [
        [11, 52, 59, 12, 76, 12, 40, 38, 74], [61, 42, 56, 56, 23, 29],
        [83, 26, 79, 67, 81, 9, 19, 19, 87, 32, 80], [41, 0, 80, 44, 40]],
    "falcon-h1-34b-instruct-4l": [
        [120, 56, 120, 56, 56, 56, 56, 56, 56], [34, 15, 71, 89, 71, 34],
        [61, 54, 54, 61, 54, 61, 54, 54, 61, 54, 61], [110, 96, 96, 96, 96]],
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_other_hybrid_stacks_serve_their_recorded_streams(name):
    cfg = load(name + ".json")
    cfg = dict(cfg, **{k: v for k, v in cfg["rehearsal"].items()
                       if k != "serve"})
    if name.startswith("ling"):
        pcfg = ling_serve.program_config(cfg)
        params = init_params(pcfg, seed=42)
    else:
        pcfg = falcon_h1_serve.program_config(cfg)
        params = falcon_h1_serve.seeded_weights(param_shapes(pcfg), 42, cfg)
    assert isinstance(pcfg, HybridConfig)
    eng = InferenceEngine(pcfg, params, ServeConfig(
        page_size=8, num_pages=65, max_batch=3, max_pages_per_seq=16,
        prefill_buckets=(32, 64), decode_block=2, verify=False))
    sched = ContinuousBatchingScheduler(eng)
    rs = np.random.RandomState(7)
    reqs = [sched.submit(Request(
        prompt=[int(t) for t in rs.randint(0, cfg["vocab_size"], n)],
        max_new_tokens=m))
        for n, m in ((13, 9), (40, 6), (27, 11), (60, 5))]
    sched.run()
    assert [r.tokens for r in reqs] == RECORDED[name]
