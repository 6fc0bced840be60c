"""Each plain reference against the program's own model at a tiny size, on
the CPU in float32: they have to agree to rounding, or the reference (or
the adapter between the two layouts) is wrong."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.drivers import bert_recipe, gpt_serve
from benchmark.reference import bert as ref_bert
from benchmark.reference import gpt2 as ref_gpt2
from benchmark.reference import lamb as ref_lamb
from benchmark.reference import precision

BERT = {"vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "intermediate_size": 128,
        "max_position_embeddings": 32, "layer_norm_eps": 1e-12}
GPT = {"vocab_size": 512, "n_positions": 64, "n_embd": 64, "n_layer": 2,
       "n_head": 4, "n_inner": None, "layer_norm_epsilon": 1e-5,
       "compute_dtype": "float32"}


def _bert_batch(rng, b=4, s=32, k=5, vocab=512):
    return {
        "input_ids": rng.integers(0, vocab, (b, s)).astype(np.int32),
        "token_type_ids": rng.integers(0, 2, (b, s)).astype(np.int32),
        "attention_mask": np.ones((b, s), np.int32),
        "mlm_positions": rng.integers(0, s, (b, k)).astype(np.int32),
        "mlm_label_ids": rng.integers(0, vocab, (b, k)).astype(np.int32),
        "mlm_weights": (rng.random((b, k)) < 0.8).astype(np.float32),
        "nsp_labels": rng.integers(0, 2, (b,)).astype(np.int32),
    }


@pytest.fixture(scope="module")
def bert_setup():
    from apex_tpu.models import BertConfig, BertForPreTraining

    pcfg = BertConfig(
        vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
        intermediate_size=128, max_position_embeddings=32,
        dtype=jnp.float32, remat=True,
    )
    model = BertForPreTraining(pcfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((32, 4), jnp.int32))
    tree = weights.seeded_weights(shapes, 5, 0.05)
    return model, tree


def test_bert_loss_and_gradient_match_the_program(bert_setup):
    from apex_tpu.models import bert_pretrain_loss

    model, tree = bert_setup
    b = _bert_batch(np.random.default_rng(0))
    prog_batch = {
        "input_ids": b["input_ids"].T, "token_type_ids": b["token_type_ids"].T,
        "attention_mask": b["attention_mask"],
        "mlm_positions": b["mlm_positions"].T,
        "mlm_label_ids": b["mlm_label_ids"].T,
        "mlm_weights": b["mlm_weights"].T, "nsp_labels": b["nsp_labels"],
    }
    loss_p, grad_p = jax.value_and_grad(
        lambda p: bert_pretrain_loss(p, model, prog_batch))(tree)
    loss_r, grad_r = ref_bert.loss_and_grad(
        bert_recipe.to_reference(tree), b, BERT, micro=2)
    assert abs(float(loss_p) - float(loss_r)) < 2e-5
    flat = bert_recipe.to_reference(grad_p)
    for k in flat:
        np.testing.assert_allclose(flat[k], grad_r[k], rtol=2e-3, atol=2e-6,
                                   err_msg=k)


def test_lamb_matches_the_program(bert_setup):
    from apex_tpu.optimizers import fused_lamb

    _, tree = bert_setup
    p = bert_recipe.to_reference(tree)
    rng = np.random.default_rng(1)
    tx = fused_lamb(learning_rate=1e-3, weight_decay=0.01)
    st_p, st_r, p_p = tx.init(p), ref_lamb.init(p), p
    p_r = p
    for _ in range(3):
        g = {k: jnp.asarray(rng.normal(size=v.shape), jnp.float32) * 0.3
             for k, v in p.items()}
        up, st_p = tx.update(g, st_p, p_p)
        p_p = jax.tree_util.tree_map(jnp.add, p_p, up)
        p_r, st_r = ref_lamb.step(p_r, g, st_r, lr=1e-3, weight_decay=0.01)
    for k in p:
        np.testing.assert_allclose(p_p[k], p_r[k], rtol=1e-5, atol=1e-7,
                                   err_msg=k)
        np.testing.assert_allclose(st_p.m[k], st_r["m"][k], rtol=1e-5,
                                   atol=1e-8)


def test_gpt2_logits_match_the_program():
    from apex_tpu.models.gpt import GptModel, _tied_vocab_logits

    pcfg = gpt_serve.program_config(GPT)
    model = GptModel(pcfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((8, 1), jnp.int32))
    tree = weights.seeded_weights(shapes, 9, 0.05)
    ids = np.random.default_rng(2).integers(0, 512, (48,)).astype(np.int32)
    h = model.apply(tree, jnp.asarray(ids)[:, None])
    want = _tied_vocab_logits(tree, model, h, sp_gathered=False)[:, 0]
    got = ref_gpt2.logits(gpt_serve.to_reference(tree), jnp.asarray(ids), GPT)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)


def test_fp8_rounding_keeps_four_significant_bits():
    x = jnp.asarray([1.0, 1.0625, 1.09, 0.3, -5.3, 0.0], jnp.float32)
    got = np.asarray(precision.rounded(x, "fp8"))
    np.testing.assert_allclose(got, [1.0, 1.0, 1.125, 0.3125, -5.5, 0.0])
    assert np.all(np.asarray(precision.rounded(x, "f32")) == np.asarray(x))
