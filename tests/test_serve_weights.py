"""The serving weights are cast to the compute dtype once, when they are
installed (ISSUE 34): the step programs take the engine's *step tree*
(``serve/model.py::step_params``), not the tree the caller handed in.
CPU, tiny bf16-compute configurations; bits and counts only.

The conversion is the step bodies' own ``.astype(cfg.dtype)`` moved out
of the programs, so every logit, token and pool row must be
bit-identical on either tree.  The recorded streams and logit digests
below were read from the PARENT commit (79155cc, the cast at the head
of every program) running the same fixed script on f32 weights at bf16
compute.
"""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.gpt import GptConfig, GptModel
from apex_tpu.observability.metrics import board
from apex_tpu.serve import (
    ContinuousBatchingScheduler,
    InferenceEngine,
    Request,
    ServeConfig,
)
from apex_tpu.serve import cache as cache_lib
from apex_tpu.serve import model as model_lib
from apex_tpu.serve import spec as spec_lib

PAGE = 8
SAMPLE_SEED = 11

#: (prompt length, max_new_tokens, temperature) — the fixed script
SCRIPT = [(5, 4, 0.0), (11, 6, 0.9), (3, 2, 0.0), (17, 5, 1.3), (8, 1, 0.7)]

#: the parent's token streams at bf16 compute, by position kind and
#: scheduler path (chunked: ``prefill_chunk_tokens=8``; spec: self-draft
#: ``k=2`` temperature mode)
PARENT_STREAMS = {
    "learned": {
        "plain": [[4, 61, 61, 0], [39, 14, 55, 26, 5, 52], [46, 19],
                  [42, 47, 24, 18, 31], [36]],
        "chunked": [[4, 61, 61, 0], [22, 14, 55, 26, 5, 52], [46, 19],
                    [6, 47, 24, 18, 31], [49]],
        "spec": [[4, 61, 61, 0], [39, 58, 38, 26, 43, 18], [46, 19],
                 [42, 38, 42, 28, 30], [36]],
    },
    "rotary": {
        "plain": [[42, 42, 51, 42], [39, 50, 55, 26, 5, 52], [27, 27],
                  [42, 47, 1, 28, 31], [36]],
        "chunked": [[42, 42, 51, 42], [22, 50, 55, 26, 5, 52], [27, 27],
                    [6, 47, 1, 28, 31], [49]],
        "spec": [[42, 42, 51, 42], [39, 58, 38, 26, 43, 18], [27, 27],
                 [42, 38, 42, 18, 30], [36]],
    },
}
#: sha256 of the parent's ``probe_stream`` (greedy tokens and the f32
#: bytes of the prefill's last logits) on a 13-token prompt
PARENT_PROBES = {
    "learned":
        "c4d337903b65f2e65ca9d459b1b8e8c42c46292f326d44d78b6e4a8ad91da82d",
    "rotary":
        "12dbc59d59f99fe69af23bbe1b4597089e91be492a980b2cf6058a23041640e6",
}


def tiny_cfg(positions: str, dtype=jnp.bfloat16) -> GptConfig:
    return GptConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        intermediate_size=64, max_seq_len=128, dtype=dtype,
        rotary=positions == "rotary",
    )


def init_params(cfg: GptConfig, seed: int = 0):
    """f32 weights whatever ``cfg.dtype``, LayerNorm and biases moved
    off their ones and zeros so that a cast of them would show."""
    params = GptModel(cfg).init(
        jax.random.PRNGKey(seed), jnp.zeros((8, 1), jnp.int32)
    )
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        leaf + 0.02 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)
    ])


@pytest.fixture(scope="module", params=["learned", "rotary"])
def gpt(request):
    cfg = tiny_cfg(request.param)
    return request.param, cfg, init_params(cfg)


def make_engine(cfg, params, *, spec=None, **serve_kw):
    kw = dict(
        page_size=PAGE, num_pages=32, max_batch=2, max_pages_per_seq=8,
        verify=False, sample_seed=SAMPLE_SEED,
    )
    kw.update(serve_kw)
    return InferenceEngine(cfg, params, ServeConfig(**kw), spec=spec)


def serve_script(cfg, params, path: str):
    """The fixed script's token streams through the scheduler."""
    spec, kw = None, {}
    if path == "spec":
        spec = spec_lib.SpecConfig(draft_params=None, k=2, mode="temperature")
    if path == "chunked":
        kw["prefill_chunk_tokens"] = 8
    sched = ContinuousBatchingScheduler(
        make_engine(cfg, params, spec=spec), registry=None, **kw
    )
    rs = np.random.RandomState(5)
    reqs = [
        sched.submit(Request(
            prompt=[int(t) for t in rs.randint(0, 64, size=n)],
            max_new_tokens=m, temperature=t, stream_seed=100 + i,
        ))
        for i, (n, m, t) in enumerate(SCRIPT)
    ]
    sched.run()
    return [list(r.tokens) for r in reqs]


def probe_digest(engine) -> str:
    rs = np.random.RandomState(21)
    prompt = [int(t) for t in rs.randint(0, 64, size=13)]
    tokens, logits_bytes, finite = engine.probe_stream(prompt, 6)
    assert finite
    return hashlib.sha256(
        np.asarray(tokens, np.int32).tobytes() + logits_bytes
    ).hexdigest()


def leaves_with_path(tree):
    return {
        jax.tree_util.keystr(path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=lambda x: isinstance(x, model_lib.PackedWeight)
        )[0]
    }


def is_block_matmul(path: str) -> bool:
    """A weight or bias of ``qkv`` / ``out`` / ``fc1`` / ``fc2``: what
    the step tree holds in the compute dtype.  Every other leaf —
    LayerNorm's, the token table, the learned positions — stays the
    caller's array."""
    return any(f"'{k}'" in path for k in ("qkv", "out", "fc1", "fc2"))


# ---------------------------------------------------------------------------
# the step tree
# ---------------------------------------------------------------------------


class TestStepTree:
    def test_block_matmul_leaves_are_cast_once(self, gpt):
        _, cfg, params = gpt
        before, after = (
            leaves_with_path(t)
            for t in (params, model_lib.step_params(cfg, params))
        )
        assert before.keys() == after.keys()
        cast = {p for p in before if is_block_matmul(p)}
        assert len(cast) == 8
        for path in cast:
            assert before[path].dtype == jnp.float32
            assert after[path].dtype == jnp.bfloat16, path
            np.testing.assert_array_equal(
                np.asarray(after[path]),
                np.asarray(before[path].astype(jnp.bfloat16)),
            )

    def test_every_other_leaf_is_the_callers_f32_array(self, gpt):
        """LayerNorm's leaves (the fused LayerNorm reads them in f32)
        and the embedding tables (XLA:TPU fuses their casts into the
        consumers: rounded ahead, they moved every logit on the chip)."""
        kind, cfg, params = gpt
        before, after = (
            leaves_with_path(t)
            for t in (params, model_lib.step_params(cfg, params))
        )
        kept = [p for p in before if not is_block_matmul(p)]
        assert len(kept) == 6 + (1 if kind == "rotary" else 2)
        for path in kept:
            assert after[path] is before[path], path
            assert after[path].dtype == jnp.float32

    def test_f32_compute_gets_its_own_tree_back(self, gpt):
        kind, _, params = gpt
        cfg = tiny_cfg(kind, jnp.float32)
        assert model_lib.step_params(cfg, params) is params
        eng = make_engine(cfg, params)
        assert eng.step_params is eng.params is params
        assert board.get("serve/weights/step_bytes") == 0

    def test_a_step_tree_is_its_own_step_tree(self, gpt):
        _, cfg, params = gpt
        step = model_lib.step_params(cfg, params)
        assert model_lib.step_params(cfg, step) is step

    def test_int8_wire_leaves_stay_packed(self, gpt):
        kind, _, params = gpt
        cfg = tiny_cfg(kind, jnp.float32)
        eng = make_engine(cfg, params, weight_wire="int8")
        installed = leaves_with_path(eng.params)
        step = leaves_with_path(eng.step_params)
        packed = [
            p for p, leaf in installed.items()
            if isinstance(leaf, model_lib.PackedWeight)
        ]
        assert packed
        for path in installed:
            assert step[path] is installed[path], path
        assert board.get("serve/weights/step_bytes") == 0

    def test_int8_wire_at_bf16_compute_casts_only_the_dense_leaves(self, gpt):
        _, cfg, params = gpt
        packed = model_lib.quantize_params(params)
        before, after = (
            leaves_with_path(t)
            for t in (packed, model_lib.step_params(cfg, packed))
        )
        for path, leaf in before.items():
            if isinstance(leaf, model_lib.PackedWeight) or not (
                is_block_matmul(path)
            ):
                assert after[path] is leaf, path
            else:
                assert after[path].dtype == jnp.bfloat16, path


# ---------------------------------------------------------------------------
# bit identity: the bodies on either tree, the engine against the parent
# ---------------------------------------------------------------------------


def _pool(cfg, key):
    """A pool with something in every page, so a dataflow that reads
    history reads numbers."""
    pool = cache_lib.init_kv_pages(
        cfg.num_layers, 16, cfg.num_heads, PAGE,
        cfg.hidden_size // cfg.num_heads, dtype=cfg.dtype,
    )
    keys = jax.random.split(key, len(pool))
    return {
        name: 0.3 * jax.random.normal(k, arr.shape, jnp.float32).astype(
            arr.dtype)
        for (name, arr), k in zip(sorted(pool.items()), keys)
    }


def _body_call(cfg, body: str):
    """``fn(params, pool)``: one step body on fixed inputs, sampling
    with a key."""
    rs = np.random.RandomState(3)
    key = jax.random.PRNGKey(7)
    if body == "decode":
        tokens = jnp.asarray(rs.randint(0, 64, size=2), jnp.int32)
        lengths = jnp.asarray([11, 20], jnp.int32)
        tables = jnp.asarray([[1, 2, 0, 0], [3, 4, 5, 0]], jnp.int32)
        temps = jnp.asarray([0.0, 0.8], jnp.float32)
        keys = jax.random.split(key, 2)
        return lambda params, pool: model_lib.decode_body(
            cfg, params, pool, tokens, lengths, tables, temps, keys,
            page_size=PAGE,
        )
    tokens = jnp.asarray(rs.randint(0, 64, size=(16, 1)), jnp.int32)
    if body == "prefill":
        return lambda params, pool: model_lib.prefill_body(
            cfg, params, pool, tokens, jnp.int32(13),
            jnp.asarray([6, 7], jnp.int32), jnp.float32(0.9), key,
            page_size=PAGE,
        )
    return lambda params, pool: model_lib.chunk_prefill_body(
        cfg, params, pool, tokens, jnp.int32(13), jnp.int32(16),
        jnp.asarray([8, 9], jnp.int32),
        jnp.asarray([1, 2, 8, 9], jnp.int32), jnp.float32(0.9), key,
        page_size=PAGE,
    )


class TestBitIdentity:
    @pytest.mark.parametrize("body", ["decode", "prefill", "chunk_prefill"])
    def test_a_body_reads_the_same_on_either_tree(self, gpt, body):
        """Logits, tokens, finite screen and pool: bitwise equal on the
        f32 tree and on its step tree."""
        _, cfg, params = gpt
        fn = jax.jit(_body_call(cfg, body))
        pool = _pool(cfg, jax.random.PRNGKey(1))
        on_f32 = fn(params, pool)
        on_step = fn(model_lib.step_params(cfg, params), pool)
        flat_a, tree_a = jax.tree_util.tree_flatten(on_f32)
        flat_b, tree_b = jax.tree_util.tree_flatten(on_step)
        assert tree_a == tree_b
        for a, b in zip(flat_a, flat_b):
            assert a.dtype == b.dtype and a.shape == b.shape
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
        logits = np.asarray(on_f32[0])
        assert np.isfinite(logits).all() and np.ptp(logits) > 0

    @pytest.mark.parametrize("path", ["plain", "chunked", "spec"])
    def test_served_streams_equal_the_parents(self, gpt, path):
        kind, cfg, params = gpt
        assert serve_script(cfg, params, path) == PARENT_STREAMS[kind][path]

    def test_probe_logits_equal_the_parents(self, gpt):
        kind, cfg, params = gpt
        assert probe_digest(make_engine(cfg, params)) == PARENT_PROBES[kind]


# ---------------------------------------------------------------------------
# install and swap
# ---------------------------------------------------------------------------


def _cast_bytes(params) -> int:
    """What the step tree of f32 ``params`` holds at bf16 compute."""
    return sum(
        leaf.size * 2 for path, leaf in leaves_with_path(params).items()
        if is_block_matmul(path)
    )


class TestInstallAndSwap:
    def test_the_engine_steps_on_the_step_tree(self, gpt):
        _, cfg, params = gpt
        eng = make_engine(cfg, params)
        assert eng.params is params
        assert eng.weight_casts == 1
        assert board.get("serve/weights/casts") == 1
        assert board.get("serve/weights/step_bytes") == _cast_bytes(params)
        for prog in ("prefill", "decode"):
            _, args = eng._trace_args(prog, 16 if prog == "prefill" else None)
            assert args[0] is eng.step_params
        want = leaves_with_path(model_lib.step_params(cfg, params))
        got = leaves_with_path(eng.step_params)
        for path, leaf in want.items():
            assert got[path].dtype == leaf.dtype
            np.testing.assert_array_equal(
                np.asarray(got[path]), np.asarray(leaf)
            )

    def test_a_swap_serves_the_new_weights_without_a_compile(self, gpt):
        _, cfg, params = gpt
        other = init_params(cfg, seed=3)
        eng = make_engine(cfg, params).build(buckets=(16,))
        mine = probe_digest(eng)
        theirs = probe_digest(make_engine(cfg, other))
        assert mine != theirs
        compiled, casts = dict(eng.compile_counts), eng.weight_casts

        eng.params = other
        assert eng.params is other
        assert eng.weight_casts == casts + 1
        assert board.get("serve/weights/casts") == casts + 1
        assert board.get("serve/weights/step_bytes") == _cast_bytes(other)
        assert probe_digest(eng) == theirs

        eng.params = params
        assert eng.params is params
        assert probe_digest(eng) == mine
        assert eng.weight_casts == casts + 2
        assert eng.compile_counts == compiled
        assert eng.retraces == 0

    def test_a_self_draft_steps_on_the_targets_step_tree(self, gpt):
        _, cfg, params = gpt
        spec = spec_lib.SpecConfig(draft_params=None, k=2)
        eng = make_engine(cfg, params, spec=spec)
        assert eng.draft_params is params
        assert eng.draft_step_params is eng.step_params
        # one tree installed, one cast, counted once
        assert eng.weight_casts == 1
        assert board.get("serve/weights/step_bytes") == _cast_bytes(params)
        other = init_params(cfg, seed=3)
        eng.params = other
        eng.update_draft_params(None)
        assert eng.draft_params is other
        assert eng.draft_step_params is eng.step_params
        assert eng.weight_casts == 2

    def test_a_distinct_draft_gets_a_step_tree_of_its_own(self, gpt):
        _, cfg, params = gpt
        dcfg = dataclasses.replace(cfg, num_layers=1)
        draft = spec_lib.draft_from_params(params, 1)
        spec = spec_lib.SpecConfig(draft_params=draft, draft_cfg=dcfg, k=2)
        eng = make_engine(cfg, params, spec=spec)
        assert eng.draft_params is draft and eng.params is params
        assert eng.weight_casts == 2
        assert board.get("serve/weights/step_bytes") == (
            _cast_bytes(params) + _cast_bytes(draft)
        )
        step = leaves_with_path(eng.draft_step_params)
        for path, leaf in leaves_with_path(draft).items():
            if not is_block_matmul(path):
                assert step[path] is leaf
            else:
                assert step[path].dtype == jnp.bfloat16
        # "no new draft shipped": the draft and its step tree stay
        kept = eng.draft_step_params
        eng.update_draft_params(None)
        assert eng.draft_params is draft and eng.draft_step_params is kept
        fresh = spec_lib.draft_from_params(init_params(cfg, seed=3), 1)
        eng.update_draft_params(fresh)
        assert eng.draft_params is fresh
        assert eng.draft_step_params is not kept
        assert eng.weight_casts == 3
