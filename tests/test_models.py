"""Model-level tests: BERT/GPT tp+sp invariance (≙ the reference's
standalone_gpt/standalone_bert pipeline smoke tests, test_gpt_minimal /
test_bert_minimal), ResNet forward, and the driver entry points."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu import parallel_state as ps
from apex_tpu.models import (
    BertConfig,
    BertForPreTraining,
    GptConfig,
    GptModel,
    bert_pretrain_loss,
    gpt_lm_loss,
    resnet50,
)

BERT_KW = dict(
    vocab_size=128, hidden_size=64, num_layers=2, num_heads=8,
    intermediate_size=128, max_position_embeddings=64, dtype=jnp.float32,
)
S, B = 16, 2


def _bert_batch():
    ids = jax.random.randint(jax.random.PRNGKey(42), (S, B), 0, 128)
    return {
        "input_ids": ids,
        "attention_mask": jnp.ones((B, S), jnp.int32),
        "mlm_labels": jnp.where(ids % 5 == 0, ids, -1),
        "nsp_labels": jnp.zeros((B,), jnp.int32),
    }


def _pack_batch(batch, k):
    """Packed batch + the raw (positions, ids, weights) triple."""
    from apex_tpu.data import pack_mlm_predictions

    pos, ids, w = pack_mlm_predictions(batch["mlm_labels"], k)
    packed = dict(
        batch, mlm_positions=jnp.asarray(pos),
        mlm_label_ids=jnp.asarray(ids), mlm_weights=jnp.asarray(w),
    )
    return packed, (pos, ids, w)


def _recipe_schedule_fields(recipe):
    """The checkpoint / layer-loop fields of the BertConfig the recipe
    trains (its full-size branch), to put on a small model."""
    cfg = recipe.model_config(recipe.parse_args([]))
    return {
        k: getattr(cfg, k)
        for k in ("remat", "remat_policy", "remat_attention",
                  "remat_prevent_cse", "scan_layers")
    }


def _dense_dots(jaxpr):
    """dot_generals without a batch dimension in ``jaxpr``, a scan's body
    counted once per iteration."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (_, _), (lhs_batch, _) = eqn.params["dimension_numbers"]
            n += not lhs_batch
        times = eqn.params["length"] if eqn.primitive.name == "scan" else 1
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += times * _dense_dots(sub)
    return n


def _sharded_bert_loss(sp, tp=8, packed=False):
    mesh = ps.initialize_model_parallel(tensor_model_parallel_size=tp)
    m = BertForPreTraining(BertConfig(sequence_parallel=sp, **BERT_KW))
    batch = _bert_batch()
    if packed:
        batch, _ = _pack_batch(batch, 8)

    def f(key, batch):
        params = m.init(key, batch["input_ids"])
        return bert_pretrain_loss(params, m, batch)

    return float(
        jax.jit(
            jax.shard_map(
                f, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                check_vma=False,
            )
        )(jax.random.PRNGKey(0), batch)
    )


class TestBert:
    def test_unsharded_loss_and_grads(self):
        m = BertForPreTraining(BertConfig(**BERT_KW))
        batch = _bert_batch()
        params = m.init(jax.random.PRNGKey(0), batch["input_ids"])
        loss = bert_pretrain_loss(params, m, batch)
        grads = jax.grad(lambda p: bert_pretrain_loss(p, m, batch))(params)
        assert np.isfinite(float(loss))
        assert all(
            bool(jnp.all(jnp.isfinite(g)))
            for g in jax.tree_util.tree_leaves(grads)
        )

    def test_chunked_mlm_loss_matches_unchunked(self):
        """mlm_loss_chunks must not change values or grads — only memory."""
        m = BertForPreTraining(BertConfig(**BERT_KW))
        batch = _bert_batch()
        params = m.init(jax.random.PRNGKey(0), batch["input_ids"])
        l1, g1 = jax.value_and_grad(
            lambda p: bert_pretrain_loss(p, m, batch)
        )(params)
        l4, g4 = jax.value_and_grad(
            lambda p: bert_pretrain_loss(p, m, batch, mlm_loss_chunks=4)
        )(params)
        np.testing.assert_allclose(float(l1), float(l4), rtol=1e-5)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=2e-4, atol=2e-5,
            ),
            g1, g4,
        )
        with pytest.raises(ValueError):
            bert_pretrain_loss(params, m, batch, mlm_loss_chunks=7)

    def test_packed_mlm_matches_dense(self):
        """The fixed-K masked-position path (mlm_positions/label_ids/
        weights, ≙ the reference recipe's max_predictions_per_seq input)
        must reproduce the dense-label loss and grads exactly when K covers
        every masked position."""
        m = BertForPreTraining(BertConfig(**BERT_KW))
        batch = _bert_batch()
        params = m.init(jax.random.PRNGKey(0), batch["input_ids"])
        n_masked = int(jnp.max(jnp.sum(batch["mlm_labels"] >= 0, axis=0)))
        packed, (pos, ids, w) = _pack_batch(batch, n_masked)
        assert int(w.sum()) == int(jnp.sum(batch["mlm_labels"] >= 0))
        l1, g1 = jax.value_and_grad(
            lambda p: bert_pretrain_loss(p, m, batch)
        )(params)
        l2, g2 = jax.value_and_grad(
            lambda p: bert_pretrain_loss(p, m, packed)
        )(params)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=2e-4, atol=2e-5,
            ),
            g1, g2,
        )

    def test_packed_mlm_truncates_and_chunks(self):
        """K smaller than the masked count truncates in position order (the
        reference behavior); chunking composes with the packed path."""
        from apex_tpu.data import pack_mlm_predictions

        m = BertForPreTraining(BertConfig(**BERT_KW))
        batch = _bert_batch()
        params = m.init(jax.random.PRNGKey(0), batch["input_ids"])
        packed, (pos, ids, w) = _pack_batch(batch, 2)
        assert pos.shape == (2, B) and w.sum() <= 2 * B
        # truncation keeps the first masked positions per sequence
        labels_np = np.asarray(batch["mlm_labels"])
        for b in range(B):
            want = np.nonzero(labels_np[:, b] >= 0)[0][:2]
            got = pos[: len(want), b]
            np.testing.assert_array_equal(got, want)
        l1 = bert_pretrain_loss(params, m, packed)
        l2 = bert_pretrain_loss(params, m, packed, mlm_loss_chunks=2)
        assert np.isfinite(float(l1))
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
        # K > S keeps the documented fixed-(K, B) shape, zero-padded
        pos, ids, w = pack_mlm_predictions(batch["mlm_labels"], S + 4)
        assert pos.shape == ids.shape == w.shape == (S + 4, B)
        assert not w[S:].any() and not pos[S:].any()
        assert int(w.sum()) == int(jnp.sum(batch["mlm_labels"] >= 0))

    @pytest.fixture(scope="class")
    def no_remat_reference(self):
        """(params, loss, grads) of the no-remat model — shared across the
        policy parametrizations (policy-independent, compile once)."""
        m_ref = BertForPreTraining(BertConfig(**BERT_KW))
        batch = _bert_batch()
        params = m_ref.init(jax.random.PRNGKey(0), batch["input_ids"])
        l_r, g_r = jax.value_and_grad(
            lambda p: bert_pretrain_loss(p, m_ref, batch)
        )(params)
        return params, l_r, g_r

    @pytest.mark.parametrize("policy", ["full", "dots", "sums", "recipe"])
    def test_remat_policy_preserves_values(
        self, policy, no_remat_reference, bert_recipe
    ):
        """Remat policies (incl. the named-saves 'sums' policy that frees
        raw matmul outputs for epilogue fusion) are pure schedule knobs:
        loss and grads must match the no-remat model exactly.  "recipe"
        is the combination examples/bert/pretrain_bert.py trains with."""
        params, l_r, g_r = no_remat_reference
        fields = (
            _recipe_schedule_fields(bert_recipe) if policy == "recipe"
            else dict(remat=True, remat_policy=policy)
        )
        m_pol = BertForPreTraining(BertConfig(**fields, **BERT_KW))
        batch = _bert_batch()
        l_p, g_p = jax.value_and_grad(
            lambda p: bert_pretrain_loss(p, m_pol, batch)
        )(params)
        np.testing.assert_allclose(float(l_r), float(l_p), rtol=1e-6)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=1e-5, atol=1e-6,
            ),
            g_r, g_p,
        )

    def test_recipe_backward_reruns_no_dense_matmul(self, bert_recipe):
        """The recipe's per-layer checkpoint keeps what the four dense
        matmuls (qkv, out, fc1, fc2) made: a layer costs 3 dense
        dot_generals a matmul in value_and_grad (forward, dgrad, wgrad),
        where "full" costs 4 (the backward runs the forward's again).
        Counted as the difference two more layers make, so the heads drop
        out."""
        batch = _bert_batch()

        def dense_dots(num_layers, **fields):
            m = BertForPreTraining(
                BertConfig(**fields, **dict(BERT_KW, num_layers=num_layers))
            )
            params = m.init(jax.random.PRNGKey(0), batch["input_ids"])
            return _dense_dots(jax.make_jaxpr(jax.value_and_grad(
                lambda p: bert_pretrain_loss(p, m, batch)
            ))(params).jaxpr)

        def per_layer(**fields):
            return (dense_dots(4, **fields) - dense_dots(2, **fields)) // 2

        assert per_layer(remat=True) == 4 * 4
        assert per_layer(**_recipe_schedule_fields(bert_recipe)) == 4 * 3

    def test_recipe_tree_is_the_stacked_one(self, bert_recipe):
        """The recipe's full-size model keeps every encoder leaf stacked
        ``(24, ...)`` under ``bert/encoder/layers/layer`` — the paths
        ``benchmark/drivers/bert_recipe.py::to_reference`` reads and the
        recipe's checkpoints hold."""
        cfg = bert_recipe.model_config(bert_recipe.parse_args([]))
        shapes = jax.eval_shape(
            BertForPreTraining(cfg).init, jax.random.PRNGKey(0),
            jnp.zeros((128, 128), jnp.int32),
        )
        lay = shapes["params"]["bert"]["encoder"]["layers"]["layer"]
        h, f = cfg.hidden_size, cfg.intermediate_size
        want = {
            ("attention", "qkv", "weight"): (24, h, 3 * h),
            ("attention", "qkv", "bias"): (24, 3 * h),
            ("attention", "out", "weight"): (24, h, h),
            ("attention", "out", "bias"): (24, h),
            ("mlp", "fc1", "weight"): (24, h, f),
            ("mlp", "fc1", "bias"): (24, f),
            ("mlp", "fc2", "weight"): (24, f, h),
            ("mlp", "fc2", "bias"): (24, h),
            ("ln_attn", "scale"): (24, h), ("ln_attn", "bias"): (24, h),
            ("ln_mlp", "scale"): (24, h), ("ln_mlp", "bias"): (24, h),
        }
        got = {
            tuple(k.key for k in path): leaf.shape
            for path, leaf in jax.tree_util.tree_leaves_with_path(lay)
        }
        assert got == want
        assert set(shapes["params"]["bert"]["encoder"]) == {"layers"}

    def test_unrolled_matches_scanned(self):
        """scan_layers / remat_attention are pure schedule knobs: the same
        stacked ``(L, ...)`` tree, same loss, same grads as the scanned
        encoder."""
        m_scan = BertForPreTraining(BertConfig(**BERT_KW))
        m_unroll = BertForPreTraining(
            BertConfig(
                scan_layers=False, remat=True, remat_policy="dots",
                remat_attention=True, **BERT_KW,
            )
        )
        batch = _bert_batch()
        params = m_scan.init(jax.random.PRNGKey(0), batch["input_ids"])
        # the unrolled model lays out (and draws) the very same tree
        params_u = m_unroll.init(jax.random.PRNGKey(0), batch["input_ids"])
        jax.tree_util.tree_map(
            np.testing.assert_array_equal, params, params_u
        )
        l_s, g_s = jax.value_and_grad(
            lambda p: bert_pretrain_loss(p, m_scan, batch)
        )(params)
        l_u, g_u = jax.value_and_grad(
            lambda p: bert_pretrain_loss(p, m_unroll, batch)
        )(params)
        np.testing.assert_allclose(float(l_s), float(l_u), rtol=1e-5)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-5
            ),
            g_s, g_u,
        )

    def test_unrolled_dropout_and_sequence_parallel_marks(self, eight_devices):
        """The unrolled loop applies the layer on its own: dropout still
        draws a key a layer, and the sequence-parallel leaves are marked at
        the paths the stacked tree has them (what the tp gradient sync
        matches, strictly)."""
        batch = _bert_batch()
        marks = {}
        for scan in (True, False):
            mesh = ps.initialize_model_parallel(tensor_model_parallel_size=8)
            m = BertForPreTraining(BertConfig(
                sequence_parallel=True, scan_layers=scan, **BERT_KW))

            def f(key, batch):
                params = m.init(key, batch["input_ids"])
                return bert_pretrain_loss(params, m, batch)

            jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                check_vma=False,
            )).lower(jax.random.PRNGKey(0), batch)
            marks[scan] = ps.sequence_parallel_param_paths()
            ps.destroy_model_parallel()
        assert marks[False] == marks[True] and marks[True]
        m = BertForPreTraining(BertConfig(scan_layers=False, **BERT_KW))
        params = m.init(jax.random.PRNGKey(0), batch["input_ids"])

        def noisy(seed):
            return float(bert_pretrain_loss(
                params, m, batch, deterministic=False,
                rngs={"dropout": jax.random.PRNGKey(seed)},
            ))

        assert noisy(1) == noisy(1) != noisy(2)
        assert noisy(1) != float(bert_pretrain_loss(params, m, batch))

    def test_tp_matches_unsharded(self, eight_devices):
        """sharded_init + per-head QKV layout ⇒ tp changes nothing."""
        l_tp = _sharded_bert_loss(sp=False)
        ps.destroy_model_parallel()
        m1 = BertForPreTraining(BertConfig(**BERT_KW))
        batch = _bert_batch()
        p1 = m1.init(jax.random.PRNGKey(0), batch["input_ids"])
        l1 = float(bert_pretrain_loss(p1, m1, batch))
        assert abs(l_tp - l1) < 2e-3, (l_tp, l1)

    def test_sp_matches_tp(self, eight_devices):
        l_tp = _sharded_bert_loss(sp=False)
        ps.destroy_model_parallel()
        l_sp = _sharded_bert_loss(sp=True)
        assert abs(l_tp - l_sp) < 1e-4, (l_tp, l_sp)

    def test_packed_mlm_tp_sp_matches_unsharded(self, eight_devices):
        """The masked-position gather sits above the tp/SP grad boundaries
        (copy_to / SP gather), so the packed loss must agree across
        unsharded, tp, and tp+SP runs."""
        m1 = BertForPreTraining(BertConfig(**BERT_KW))
        batch, _ = _pack_batch(_bert_batch(), 8)
        p1 = m1.init(jax.random.PRNGKey(0), batch["input_ids"])
        l1 = float(bert_pretrain_loss(p1, m1, batch))
        l_tp = _sharded_bert_loss(sp=False, packed=True)
        ps.destroy_model_parallel()
        l_sp = _sharded_bert_loss(sp=True, packed=True)
        assert abs(l_tp - l1) < 2e-3, (l_tp, l1)
        assert abs(l_sp - l_tp) < 1e-4, (l_sp, l_tp)

    def test_training_descends(self):
        m = BertForPreTraining(BertConfig(**BERT_KW))
        batch = _bert_batch()
        params = m.init(jax.random.PRNGKey(0), batch["input_ids"])

        from apex_tpu.optimizers import fused_lamb

        tx = fused_lamb(learning_rate=5e-3)
        opt = tx.init(params)

        @jax.jit
        def step(params, opt):
            loss, grads = jax.value_and_grad(
                lambda p: bert_pretrain_loss(p, m, batch)
            )(params)
            upd, opt = tx.update(grads, opt, params)
            return jax.tree_util.tree_map(jnp.add, params, upd), opt, loss

        params, opt, l0 = step(params, opt)
        for _ in range(10):
            params, opt, loss = step(params, opt)
        assert float(loss) < float(l0)


class TestGpt:
    def test_tp_sp_matches_unsharded(self, eight_devices):
        kw = dict(
            vocab_size=128, hidden_size=64, num_layers=2, num_heads=8,
            intermediate_size=128, max_seq_len=64, dtype=jnp.float32,
        )
        ids = jax.random.randint(jax.random.PRNGKey(7), (S, B), 0, 128)
        m1 = GptModel(GptConfig(**kw))
        p1 = m1.init(jax.random.PRNGKey(1), ids)
        l1 = float(gpt_lm_loss(p1, m1, ids))

        mesh = ps.initialize_model_parallel(tensor_model_parallel_size=8)
        m8 = GptModel(GptConfig(sequence_parallel=True, **kw))

        def f(key, ids):
            params = m8.init(key, ids)
            return gpt_lm_loss(params, m8, ids)

        l8 = float(
            jax.jit(
                jax.shard_map(
                    f, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                    check_vma=False,
                )
            )(jax.random.PRNGKey(1), ids)
        )
        assert abs(l1 - l8) < 2e-3, (l1, l8)

    @pytest.mark.parametrize("policy", ["dots", "sums"])
    def test_gpt_remat_policy_preserves_values(self, policy):
        """remat=True with 'dots'/'sums' reproduces the no-remat loss and
        grads (the gpt_* named tags mirror the BERT sums save set)."""
        kw = dict(
            vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
            intermediate_size=64, max_seq_len=16, dtype=jnp.float32,
        )
        ids = jax.random.randint(jax.random.PRNGKey(2), (16, 2), 0, 64)

        def loss_and_grads(**extra):
            m = GptModel(GptConfig(**kw, **extra))
            params = m.init(jax.random.PRNGKey(3), ids)
            return jax.value_and_grad(
                lambda p: gpt_lm_loss(p, m, ids)
            )(params)

        l_ref, g_ref = loss_and_grads()
        l_p, g_p = loss_and_grads(remat=True, remat_policy=policy)
        np.testing.assert_allclose(float(l_ref), float(l_p), rtol=1e-6)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            ),
            g_ref, g_p,
        )

    def test_causality(self):
        """Changing a future token must not change earlier losses' inputs:
        logits at position t depend only on ids[:t+1]."""
        kw = dict(
            vocab_size=64, hidden_size=32, num_layers=1, num_heads=4,
            intermediate_size=64, max_seq_len=32, dtype=jnp.float32,
        )
        m = GptModel(GptConfig(**kw))
        ids = jax.random.randint(jax.random.PRNGKey(0), (8, 1), 0, 64)
        params = m.init(jax.random.PRNGKey(1), ids)
        h1 = m.apply(params, ids)
        ids2 = ids.at[-1, 0].set((ids[-1, 0] + 1) % 64)
        h2 = m.apply(params, ids2)
        np.testing.assert_allclose(
            np.asarray(h1[:-1]), np.asarray(h2[:-1]), atol=1e-5
        )


class TestResNet:
    def test_forward_and_grad(self):
        m = resnet50(num_classes=10, dtype=jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 32, 3))
        variables = m.init(jax.random.PRNGKey(1), x, train=False)
        logits, new_state = m.apply(
            x=x, train=True, mutable=["batch_stats"], variables=variables
        )
        assert logits.shape == (2, 10)
        assert logits.dtype == jnp.float32

    def test_syncbn_variant_runs(self, eight_devices):
        mesh = ps.initialize_model_parallel()  # dp=8
        m = resnet50(num_classes=4, dtype=jnp.float32, use_syncbn=True)
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 16, 16, 3))

        def f(key, x):
            variables = m.init(key, x, train=False)
            logits, _ = m.apply(
                x=x, train=True, mutable=["batch_stats"], variables=variables
            )
            return logits

        logits = jax.jit(
            jax.shard_map(
                f, mesh=mesh, in_specs=(P(), P("dp")), out_specs=P("dp"),
                check_vma=False,
            )
        )(jax.random.PRNGKey(1), x)
        assert logits.shape == (16, 4)
        assert bool(jnp.all(jnp.isfinite(logits)))


class TestGraftEntry:
    def _load(self):
        spec = importlib.util.spec_from_file_location(
            "__graft_entry__",
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "__graft_entry__.py",
            ),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_dryrun_multichip(self, eight_devices):
        ge = self._load()
        ge.dryrun_multichip(8)
