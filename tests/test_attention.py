"""Flash attention vs unfused reference — ≙ apex/contrib/test/fmha and
multihead_attn tests (fused kernel vs plain torch attention composition)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import _dispatch
from apex_tpu.ops.attention import flash_attention, fmha_qkvpacked, mha_reference


@pytest.fixture
def force_pallas():
    _dispatch.set_use_pallas(True)
    yield
    _dispatch.set_use_pallas(None)


def _rand_qkv(key, b=2, h=2, sq=128, sk=128, d=64, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, h, sq, d), dtype)
    k = jax.random.normal(kk, (b, h, sk, d), dtype)
    v = jax.random.normal(kv, (b, h, sk, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference(force_pallas, causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0))
    out = flash_attention(q, k, v, causal=causal)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_forward_bias(force_pallas):
    q, k, v = _rand_qkv(jax.random.PRNGKey(1))
    # key-padding-style additive mask: last 32 keys masked out for batch 1
    bias = np.zeros((2, 1, 1, 128), np.float32)
    bias[1, :, :, 96:] = -1e9
    bias = jnp.asarray(np.broadcast_to(bias, (2, 1, 128, 128)))
    out = flash_attention(q, k, v, bias)
    ref = mha_reference(q, k, v, bias)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_forward_shared_bias(force_pallas):
    q, k, v = _rand_qkv(jax.random.PRNGKey(5))
    bias = jax.random.normal(jax.random.PRNGKey(6), (1, 1, 128, 128))
    out = flash_attention(q, k, v, bias)
    ref = mha_reference(q, k, v, bias)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_reference(force_pallas, causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), b=1, h=2, sq=128, sk=128, d=64)

    def loss_fused(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def test_grads_with_bias(force_pallas):
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), b=1, h=1)
    bias = jax.random.normal(jax.random.PRNGKey(4), (1, 1, 128, 128)) * 0.1

    gf = jax.grad(lambda q: jnp.sum(flash_attention(q, k, v, bias)))(q)
    gr = jax.grad(lambda q: jnp.sum(mha_reference(q, k, v, bias)))(q)
    np.testing.assert_allclose(gf, gr, atol=5e-4, rtol=5e-4)


def test_cross_attention_shapes(force_pallas):
    q, k, v = _rand_qkv(jax.random.PRNGKey(7), sq=128, sk=256)
    out = flash_attention(q, k, v)
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_multi_block_long_seq(force_pallas):
    # >1 block in both q and k (blocks are 128): exercises the online-softmax
    # carry across the key grid dimension.
    q, k, v = _rand_qkv(jax.random.PRNGKey(8), b=1, h=1, sq=256, sk=384)
    out = flash_attention(q, k, v, causal=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_bf16_io(force_pallas):
    q, k, v = _rand_qkv(jax.random.PRNGKey(9), dtype=jnp.bfloat16)
    out = flash_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    ref = mha_reference(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
    )
    np.testing.assert_allclose(
        out.astype(np.float32), ref, atol=3e-2, rtol=3e-2
    )


def test_dropout_falls_back_and_runs():
    q, k, v = _rand_qkv(jax.random.PRNGKey(10))
    rng = jax.random.PRNGKey(11)
    out = flash_attention(q, k, v, dropout_p=0.5, dropout_rng=rng)
    assert out.shape == q.shape
    # dropout is a no-op in expectation direction check: zero-prob path equals ref
    out0 = flash_attention(q, k, v, dropout_p=0.0)
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(out0, ref, atol=2e-5, rtol=2e-5)


def test_fmha_qkvpacked(force_pallas):
    b, s, h, d = 2, 128, 2, 64
    qkv = jax.random.normal(jax.random.PRNGKey(12), (b, s, 3, h, d))
    out = fmha_qkvpacked(qkv, causal=False)
    assert out.shape == (b, s, h, d)
    q, k, v = (jnp.moveaxis(qkv[:, :, i], 1, 2) for i in range(3))
    ref = jnp.moveaxis(mha_reference(q, k, v), 1, 2)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_odd_seq_uses_reference_path():
    # Non-tile-friendly seq length must still work (jnp fallback).
    q, k, v = _rand_qkv(jax.random.PRNGKey(13), sq=37, sk=53)
    out = flash_attention(q, k, v)
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_neg_inf_bias_first_block_fully_masked(force_pallas):
    """-inf additive bias (torch convention) on a whole leading key block.

    Regression: with the first 128-key block fully masked at -inf, the
    online softmax's running max stayed -inf and alpha = exp(-inf - -inf)
    poisoned the row with NaN.  The kernel clamps bias to MASK_VALUE.
    """
    q, k, v = _rand_qkv(jax.random.PRNGKey(7), sq=256, sk=256)
    bias = np.zeros((2, 1, 1, 256), np.float32)
    bias[:, :, :, :128] = -np.inf  # left padding: whole first k-block masked
    bias = jnp.asarray(np.broadcast_to(bias, (2, 1, 256, 256)))
    out = flash_attention(q, k, v, bias)
    assert bool(jnp.all(jnp.isfinite(out)))
    ref = mha_reference(q, k, v, jnp.maximum(bias, -1e9))
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    # gradients stay finite too (bwd recompute uses the same clamp)
    g = jax.grad(lambda q_: jnp.sum(flash_attention(q_, k, v, bias) ** 2))(q)
    assert bool(jnp.all(jnp.isfinite(g)))


def test_neg_inf_bias_fallback_path_matches():
    """The jnp fallback (non-tile-friendly S) must share the clamp
    semantics: same -inf mask, S=120 routes to mha_reference internally."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(8), sq=120, sk=120)
    bias = np.zeros((2, 1, 1, 120), np.float32)
    bias[1, :, :, :60] = -np.inf
    bias = jnp.asarray(bias)
    out = flash_attention(q, k, v, bias)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_key_padding_bias_not_materialized(force_pallas):
    """(B, 1, 1, Sk) key-padding bias stays a single row per batch on the
    Pallas path (G=B, RS=1) — and matches the reference numerics."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(9), b=3, h=4, sq=256, sk=256)
    bias = np.zeros((3, 1, 1, 256), np.float32)
    bias[0, :, :, 200:] = -1e9
    bias[2, :, :, 100:] = -1e9
    bias = jnp.asarray(bias)
    out = flash_attention(q, k, v, bias)
    ref = mha_reference(q, k, v, bias)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    gf = jax.grad(lambda q_: jnp.sum(flash_attention(q_, k, v, bias) ** 2))(q)
    gr = jax.grad(lambda q_: jnp.sum(mha_reference(q_, k, v, bias) ** 2))(q)
    np.testing.assert_allclose(gf, gr, atol=5e-4, rtol=1e-3)


def test_per_batch_full_bias_grouped(force_pallas):
    """(B, 1, Sq, Sk) bias uses the grouped index map (G=B) — no H-fold."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(10), b=2, h=3, sq=128, sk=128)
    bias = jax.random.normal(jax.random.PRNGKey(11), (2, 1, 128, 128))
    out = flash_attention(q, k, v, bias)
    ref = mha_reference(q, k, v, bias)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize(
    "bias_shape",
    [
        (1, 1, 128, 128),   # G=1,  RS=Sq (shared relative-position bias)
        (2, 1, 128, 128),   # G=B,  RS=Sq
        (2, 2, 128, 128),   # G=BH, RS=Sq (per-head bias)
        (1, 2, 128, 128),   # broadcast B -> G=BH with B-sum unbroadcast
        (1, 1, 1, 128),     # G=1,  RS=1  (shared key bias row)
        (2, 1, 1, 128),     # G=B,  RS=1  (key-padding-style trainable)
        (2, 2, 1, 128),     # G=BH, RS=1
    ],
)
@pytest.mark.parametrize("causal", [False, True])
def test_trainable_bias_grad_matches_reference(
    force_pallas, bias_shape, causal
):
    """dbias through the flash path (dedicated dbias kernel) vs the jnp
    composition, across every (G, RS) bias-group layout (VERDICT r2 #3;
    ≙ the reference's self_attn_bias additive-bias backward)."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(20), b=2, h=2, sq=128, sk=128)
    bias = jax.random.normal(jax.random.PRNGKey(21), bias_shape) * 0.3

    def loss_fused(bias, q):
        return jnp.sum(
            flash_attention(q, k, v, bias, causal=causal, bias_grad=True)
            ** 2
        )

    def loss_ref(bias, q):
        return jnp.sum(mha_reference(q, k, v, bias, causal=causal) ** 2)

    db_f, dq_f = jax.grad(loss_fused, argnums=(0, 1))(bias, q)
    db_r, dq_r = jax.grad(loss_ref, argnums=(0, 1))(bias, q)
    assert db_f.shape == bias.shape
    np.testing.assert_allclose(db_f, db_r, atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(dq_f, dq_r, atol=5e-4, rtol=5e-4)
    # the cotangent is genuinely nonzero — the parity is not vacuous
    assert float(jnp.max(jnp.abs(db_f))) > 1e-6


def test_trainable_bias_multiblock(force_pallas):
    """dbias with a multi-block grid (Sq=Sk=256, blocks of 128) exercises
    the scratch accumulation across the inner group dim."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(22), b=2, h=2, sq=256, sk=256)
    bias = jax.random.normal(jax.random.PRNGKey(23), (1, 2, 256, 256)) * 0.3

    db_f = jax.grad(
        lambda b_: jnp.sum(
            flash_attention(q, k, v, b_, causal=True, bias_grad=True) ** 2
        )
    )(bias)
    db_r = jax.grad(
        lambda b_: jnp.sum(mha_reference(q, k, v, b_, causal=True) ** 2)
    )(bias)
    np.testing.assert_allclose(db_f, db_r, atol=5e-4, rtol=5e-4)


def test_nontrainable_bias_zero_grad_on_flash_path(force_pallas):
    """Default (bias_grad=False) keeps the documented zero-cotangent
    contract on the flash path."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(24), b=1, h=1)
    bias = jax.random.normal(jax.random.PRNGKey(25), (1, 1, 128, 128))
    db = jax.grad(
        lambda b_: jnp.sum(flash_attention(q, k, v, b_) ** 2)
    )(bias)
    np.testing.assert_allclose(np.asarray(db), 0.0)


@pytest.mark.parametrize("sq,sk", [(100, 100), (1000, 1000), (333, 259)])
@pytest.mark.parametrize("causal", [False, True])
def test_arbitrary_seq_kernel_parity(force_pallas, sq, sk, causal):
    """Arbitrary (non-tile-multiple) S runs the kernel via padding with
    masked keys (VERDICT r2 #4) and matches the unfused reference."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(30), b=1, h=2, sq=sq, sk=sk)
    out = flash_attention(q, k, v, causal=causal)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("sq,sk", [(100, 100), (333, 259)])
def test_arbitrary_seq_grads_parity(force_pallas, sq, sk):
    q, k, v = _rand_qkv(jax.random.PRNGKey(31), b=1, h=1, sq=sq, sk=sk)
    gf = jax.grad(
        lambda q_, k_, v_: jnp.sum(flash_attention(q_, k_, v_) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    gr = jax.grad(
        lambda q_, k_, v_: jnp.sum(mha_reference(q_, k_, v_) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def test_arbitrary_seq_with_bias_parity(force_pallas):
    """User bias + padding compose: padded key columns stay masked, bias
    cotangent keeps the user's shape."""
    sq = sk = 100
    q, k, v = _rand_qkv(jax.random.PRNGKey(32), b=2, h=2, sq=sq, sk=sk)
    bias = jax.random.normal(jax.random.PRNGKey(33), (2, 1, sq, sk)) * 0.3
    out = flash_attention(q, k, v, bias)
    ref = mha_reference(q, k, v, bias)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    db_f = jax.grad(
        lambda b_: jnp.sum(
            flash_attention(q, k, v, b_, bias_grad=True) ** 2
        )
    )(bias)
    db_r = jax.grad(lambda b_: jnp.sum(mha_reference(q, k, v, b_) ** 2))(
        bias
    )
    assert db_f.shape == bias.shape
    np.testing.assert_allclose(db_f, db_r, atol=5e-4, rtol=5e-4)


def test_fully_masked_row_with_padded_keys(force_pallas):
    """A batch row whose key-padding bias masks EVERY real key, at an Sk
    that needs tile padding: the output must average V over the REAL keys
    (padded keys sit at PAD_VALUE < MASK_VALUE and underflow out), matching
    the unpadded reference."""
    sq = sk = 100  # pads to 104
    q, k, v = _rand_qkv(jax.random.PRNGKey(35), b=2, h=1, sq=sq, sk=sk)
    bias = np.zeros((2, 1, 1, sk), np.float32)
    bias[1] = -np.inf  # batch 1: all real keys masked
    bias = jnp.asarray(bias)
    out = flash_attention(q, k, v, bias)
    ref = mha_reference(q, k, v, jnp.maximum(bias, -1e9))
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_causal_short_keys_unaligned_falls_back(force_pallas):
    """The one documented jnp corner: causal, Sq > Sk, Sk needs padding —
    fully-masked rows average V over the REAL Sk."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(34), b=1, h=1, sq=100, sk=50)
    out = flash_attention(q, k, v, causal=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


class TestFusedDropout:
    """In-kernel attention dropout (≙ the reference's philox dropout in
    the fused MHA kernels).  The mask is extracted exactly by setting
    V = I, which makes o = D ⊙ softmax(s): each output element IS the
    dropped, rescaled probability."""

    def _qkv_ident(self, key, s=128):
        kq, kk = jax.random.split(key)
        q = jax.random.normal(kq, (1, 1, s, s))
        k = jax.random.normal(kk, (1, 1, s, s))
        v = jnp.eye(s)[None, None]
        return q, k, v

    def test_mask_semantics_and_rate(self, force_pallas):
        p = 0.15
        q, k, v = self._qkv_ident(jax.random.PRNGKey(40))
        rng = jax.random.PRNGKey(41)
        probs = flash_attention(q, k, v)  # = softmax(s), no dropout
        out = flash_attention(q, k, v, dropout_p=p, dropout_rng=rng)
        mask = np.asarray(out) != 0.0
        rate = mask.mean()
        assert abs(rate - (1 - p)) < 0.03, rate  # binomial, 16k draws
        # kept entries are exactly probs/(1-p); dropped are exactly 0
        np.testing.assert_allclose(
            np.asarray(out),
            np.where(mask, np.asarray(probs) / (1 - p), 0.0),
            atol=1e-6, rtol=1e-5,
        )

    def test_deterministic_and_rng_dependent(self, force_pallas):
        q, k, v = self._qkv_ident(jax.random.PRNGKey(42))
        r1, r2 = jax.random.PRNGKey(1), jax.random.PRNGKey(2)
        a = flash_attention(q, k, v, dropout_p=0.3, dropout_rng=r1)
        b = flash_attention(q, k, v, dropout_p=0.3, dropout_rng=r1)
        c = flash_attention(q, k, v, dropout_p=0.3, dropout_rng=r2)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert not np.array_equal(np.asarray(a), np.asarray(c))

    def test_mask_varies_per_batch_head(self, force_pallas):
        s = 128
        q = jax.random.normal(jax.random.PRNGKey(43), (2, 2, s, s))
        k = jax.random.normal(jax.random.PRNGKey(44), (2, 2, s, s))
        v = jnp.broadcast_to(jnp.eye(s), (2, 2, s, s))
        out = np.asarray(
            flash_attention(
                q, k, v, dropout_p=0.3, dropout_rng=jax.random.PRNGKey(3)
            )
        )
        masks = (out != 0.0).reshape(4, -1)
        for i in range(4):
            for j in range(i + 1, 4):
                assert not np.array_equal(masks[i], masks[j]), (i, j)

    def test_grads_consistent_with_forward(self, force_pallas):
        """The hand-written backward (mask regenerated in dkdv/dq kernels)
        must match numerical differentiation of the actual forward."""
        from jax.test_util import check_grads

        kq, kk, kv = jax.random.split(jax.random.PRNGKey(45), 3)
        q = jax.random.normal(kq, (1, 1, 128, 32))
        k = jax.random.normal(kk, (1, 1, 128, 32))
        v = jax.random.normal(kv, (1, 1, 128, 32))
        rng = jax.random.PRNGKey(7)

        def f(q, k, v):
            return flash_attention(
                q, k, v, dropout_p=0.25, dropout_rng=rng
            ).astype(jnp.float32)

        check_grads(f, (q, k, v), order=1, modes=["rev"],
                    atol=1e-2, rtol=1e-2)

    def test_dropout_with_trainable_bias_grads(self, force_pallas):
        """dropout + bias_grad compose: dbias kernel applies the same
        mask (checked against numerical diff)."""
        from jax.test_util import check_grads

        kq, kk, kv, kb = jax.random.split(jax.random.PRNGKey(46), 4)
        q = jax.random.normal(kq, (1, 2, 128, 32))
        k = jax.random.normal(kk, (1, 2, 128, 32))
        v = jax.random.normal(kv, (1, 2, 128, 32))
        bias = jax.random.normal(kb, (1, 2, 128, 128)) * 0.3
        rng = jax.random.PRNGKey(8)

        def f(bias):
            return flash_attention(
                q, k, v, bias, dropout_p=0.2, dropout_rng=rng,
                bias_grad=True,
            ).astype(jnp.float32)

        check_grads(f, (bias,), order=1, modes=["rev"],
                    atol=1e-2, rtol=1e-2)

    def test_keep_mask_hash_no_long_context_aliasing(self):
        """The keyed pair-hash must not correlate positions at long-
        context coordinates (the linear-counter scheme aliased
        (r, c+65537) with (r+1, c)); also sane keep-rate far from the
        origin."""
        from apex_tpu.ops.pallas.flash_attention import (
            _dropout_keep_block,
        )

        seed = jnp.asarray(1234, jnp.int32)
        bh = jnp.asarray(3, jnp.int32)
        bq = bk = 128
        # two tiles starting beyond the 2^16 boundary in both dims
        i1, j1 = 512, 513  # rows/cols ~65.5k
        m1 = np.asarray(
            _dropout_keep_block(seed, bh, i1, j1, bq, bk, 0.5)
        )
        # the tile one row down, one "aliasing constant" right — under
        # the old scheme shifted copies of the same mask appear
        m2 = np.asarray(
            _dropout_keep_block(seed, bh, i1 + 1, j1, bq, bk, 0.5)
        )
        assert not np.array_equal(m1, m2)
        # no shifted-copy correlation: agreement stays near 50% for a
        # p=0.5 mask (aliasing would give long identical runs)
        agree = (m1[1:, :] == m2[:-1, :]).mean()
        assert 0.4 < agree < 0.6, agree
        # keep-rate far from origin within binomial noise
        rate = m1.mean()
        assert abs(rate - 0.5) < 0.04, rate

    def test_dropout_with_causal_and_padding(self, force_pallas):
        """dropout composes with the causal mask and arbitrary-S padding:
        zero positions stay a superset of the causal zeros, kept entries
        scale by 1/(1-p)."""
        s = 100  # pads to 104
        q, k, v = self._qkv_ident(jax.random.PRNGKey(47), s=s)
        rng = jax.random.PRNGKey(9)
        probs = flash_attention(q, k, v, causal=True)
        out = flash_attention(
            q, k, v, causal=True, dropout_p=0.2, dropout_rng=rng
        )
        mask = np.asarray(out) != 0.0
        np.testing.assert_allclose(
            np.asarray(out),
            np.where(mask, np.asarray(probs) / 0.8, 0.0),
            atol=1e-6, rtol=1e-5,
        )
        # upper triangle (causal-masked) stays all zero
        upper = np.triu(np.ones((s, s), bool), k=1)
        assert not np.asarray(out)[0, 0][upper].any()


class TestFlashAttentionWithLse:
    """flash_attention_with_lse: (o, lse) values AND the dlse backward
    (the ring-attention merge differentiates through lse)."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_values_match_reference(self, force_pallas, causal):
        from apex_tpu.ops.attention import (
            flash_attention_with_lse,
            mha_reference_with_lse,
        )

        q, k, v = _rand_qkv(jax.random.PRNGKey(3))
        o, lse = jax.jit(
            lambda q, k, v: flash_attention_with_lse(q, k, v, causal=causal)
        )(q, k, v)
        _dispatch.set_use_pallas(False)
        ow, lw = mha_reference_with_lse(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(ow), atol=2e-5, rtol=2e-5
        )
        np.testing.assert_allclose(
            np.asarray(lse), np.asarray(lw), atol=2e-5, rtol=2e-5
        )

    def test_key_padding_bias_matches_reference(self, force_pallas):
        """(B, 1, 1, Sk) key-padding bias on the with-lse path: kernel
        vs jnp composition for (o, lse) AND grads (the bias is the
        additive-mask form — its own cotangent is zero)."""
        from apex_tpu.ops.attention import (
            flash_attention_with_lse,
            mha_reference_with_lse,
        )
        from apex_tpu.ops.pallas.flash_attention import MASK_VALUE

        q, k, v = _rand_qkv(jax.random.PRNGKey(11))
        keep = jax.random.bernoulli(
            jax.random.PRNGKey(12), 0.8, (2, 1, 1, 128)
        ).at[..., 0].set(True)  # every row keeps key 0
        bias = jnp.where(keep, 0.0, MASK_VALUE)

        def loss(fn, q, k, v):
            o, lse = fn(q, k, v, bias)
            return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(lse), (o, lse)

        (_, (o, lse)), g = jax.value_and_grad(
            lambda q, k, v: loss(flash_attention_with_lse, q, k, v),
            argnums=(0, 1, 2), has_aux=True,
        )(q, k, v)
        _dispatch.set_use_pallas(False)
        (_, (ow, lw)), gw = jax.value_and_grad(
            lambda q, k, v: loss(mha_reference_with_lse, q, k, v),
            argnums=(0, 1, 2), has_aux=True,
        )(q, k, v)
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(ow), atol=2e-5, rtol=2e-5
        )
        np.testing.assert_allclose(
            np.asarray(lse), np.asarray(lw), atol=2e-5, rtol=2e-5
        )
        for a, b_ in zip(g, gw):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=5e-5, rtol=5e-5
            )
        # masked keys contribute nothing: their dk/dv are exactly zero
        dk = np.asarray(g[1])
        masked_cols = ~np.asarray(keep)[:, 0, 0]  # (B, Sk)
        for bi in range(2):
            np.testing.assert_allclose(
                dk[bi][:, masked_cols[bi]], 0.0, atol=1e-6
            )

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_include_lse_cotangent(self, force_pallas, causal):
        """A loss that consumes BOTH outputs — the lse term exercises the
        delta - dlse folding in flash_bwd."""
        from apex_tpu.ops.attention import (
            flash_attention_with_lse,
            mha_reference_with_lse,
        )

        q, k, v = _rand_qkv(jax.random.PRNGKey(4))

        def loss(fn, q, k, v):
            o, lse = fn(q, k, v, causal=causal)
            return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(
                jnp.sin(lse)
            )

        got = jax.jit(
            jax.grad(
                lambda q, k, v: loss(flash_attention_with_lse, q, k, v),
                argnums=(0, 1, 2),
            )
        )(q, k, v)
        _dispatch.set_use_pallas(False)
        want = jax.grad(
            lambda q, k, v: loss(mha_reference_with_lse, q, k, v),
            argnums=(0, 1, 2),
        )(q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), atol=5e-5, rtol=5e-5
            )


class TestIndependentDqTiles:
    """flash_bwd's dq pallas_call can take tile sizes independent of the
    dkdv one (block_q_dq/block_k_dq — the tuner's backward lever); the
    results must be bitwise-insensitive to the tile choice."""

    @pytest.mark.parametrize("dropout_p", [0.0, 0.2])
    def test_dq_tiles_do_not_change_grads(self, force_pallas, dropout_p):
        from apex_tpu.ops.pallas import flash_attention as fa

        sq = 256
        q, k, v = _rand_qkv(jax.random.PRNGKey(9), b=1, h=2, sq=sq, sk=sq)
        q, k, v = (x.reshape(2, sq, 64) for x in (q, k, v))
        scale = 64 ** -0.5
        kw = dict(scale=scale, causal=True, dropout_p=dropout_p)
        seed = dict(dropout_seed=7) if dropout_p else {}
        o, lse = fa.flash_fwd(
            q, k, v, None, block_q=128, block_k=128, **kw, **seed
        )
        do = 2.0 * o
        base = fa.flash_bwd(
            q, k, v, o, lse, do, None, block_q=128, block_k=128,
            **kw, **seed,
        )
        for bq_dq, bk_dq in ((256, 128), (128, 256), (256, 256)):
            alt = fa.flash_bwd(
                q, k, v, o, lse, do, None, block_q=128, block_k=128,
                block_q_dq=bq_dq, block_k_dq=bk_dq, **kw, **seed,
            )
            # dq numerics may differ only by f32 accumulation order
            np.testing.assert_allclose(
                np.asarray(alt[0]), np.asarray(base[0]),
                atol=2e-5, rtol=2e-5,
            )
            # dk/dv come from the UNCHANGED dkdv call: bit-identical
            for a, b in zip(alt[1:], base[1:]):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestTunedTileTable:
    """_TUNED_TILES (the attn_tune → kernel landing table, ≙ the
    reference's per-shape kernel-traits tables): entries route tile
    selection away from the _auto_block heuristic without changing
    numerics."""

    def test_table_entries_are_consulted_and_numerics_unchanged(
        self, force_pallas, monkeypatch
    ):
        from apex_tpu.ops.pallas import flash_attention as fa

        sq, d = 256, 64
        q, k, v = _rand_qkv(jax.random.PRNGKey(12), b=1, h=2, sq=sq, sk=sq)
        q, k, v = (x.reshape(2, sq, d) for x in (q, k, v))
        kw = dict(scale=d ** -0.5, causal=True)
        o_ref, lse_ref = fa.flash_fwd(q, k, v, None, **kw)
        base = fa.flash_bwd(q, k, v, o_ref, lse_ref, 2.0 * o_ref, None, **kw)

        monkeypatch.setitem(
            fa._TUNED_TILES, (sq, d, True),
            {"fwd": (128, 128), "bwd": (128, 128), "bwd_dq": (256, 128)},
        )

        def boom(*a, **k):
            raise AssertionError(
                "_auto_block consulted despite a tuned-table entry"
            )

        monkeypatch.setattr(fa, "_auto_block", boom)
        # fresh shapes would hit the jit cache of the un-patched trace;
        # clear so the lookup runs under the patched table — and ALWAYS
        # clear again on exit so a failing assert can't leave
        # tuned-tile traces live for later tests of the same shape
        fa.flash_fwd.clear_cache()
        fa.flash_bwd.clear_cache()
        try:
            o, lse = fa.flash_fwd(q, k, v, None, **kw)
            alt = fa.flash_bwd(q, k, v, o, lse, 2.0 * o, None, **kw)
            np.testing.assert_allclose(
                np.asarray(o), np.asarray(o_ref), atol=2e-5, rtol=2e-5
            )
            for a, b in zip(alt, base):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5
                )
        finally:
            fa.flash_fwd.clear_cache()
            fa.flash_bwd.clear_cache()

    def test_cross_attention_nondividing_tuned_tile_falls_back(
        self, force_pallas, monkeypatch
    ):
        """A tuned entry measured on self-attention must not hand a
        non-dividing bk to a cross-attention call's sk (the kernels
        have no partial-tile masking): the per-axis divisibility guard
        drops the tile and numerics stay correct."""
        from apex_tpu.ops.pallas import flash_attention as fa

        sq, sk, d = 256, 384, 64  # sk % 256 != 0
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(13), 3)
        q = jax.random.normal(kq, (2, sq, d))
        k = jax.random.normal(kk, (2, sk, d))
        v = jax.random.normal(kv, (2, sk, d))
        kw = dict(scale=d ** -0.5, causal=False)
        base, _ = fa.flash_fwd(q, k, v, None, block_q=128, block_k=128, **kw)
        monkeypatch.setitem(
            fa._TUNED_TILES, (sq, d, False), {"fwd": (256, 256)}
        )
        fa.flash_fwd.clear_cache()
        try:
            o, _ = fa.flash_fwd(q, k, v, None, **kw)
            np.testing.assert_allclose(
                np.asarray(o), np.asarray(base), atol=2e-5, rtol=2e-5
            )
        finally:
            fa.flash_fwd.clear_cache()


# ---------------------------------------------------------------------------
# Paged single-query decode attention (the serving kernel,
# ops/pallas/decode_attention.py — docs/serving.md)
# ---------------------------------------------------------------------------


class TestPagedDecodeAttention:
    """The decode kernel must agree with its gather-based jnp reference
    AND with plain full-context attention on the equivalent contiguous
    history — paging and online softmax are layout, not math."""

    def _paged_case(self, key, b=3, h=4, d=32, page=8, pool=12, np_=3,
                    lengths=(17, 9, 0)):
        import numpy as np_mod

        rs = np_mod.random.RandomState(int(key))
        k_pages = jnp.asarray(rs.randn(pool, h, page, d), jnp.float32)
        v_pages = jnp.asarray(rs.randn(pool, h, page, d), jnp.float32)
        q = jnp.asarray(rs.randn(b, h, d), jnp.float32)
        # distinct non-null pages per live sequence
        table = jnp.asarray(
            rs.permutation(pool - 1)[: b * np_].reshape(b, np_) + 1,
            jnp.int32,
        )
        return q, k_pages, v_pages, table, jnp.asarray(lengths, jnp.int32)

    def test_kernel_matches_reference(self, force_pallas):
        from apex_tpu.ops.paged_attention import (
            paged_decode_attention,
            paged_decode_attention_reference,
        )

        q, kp, vp, table, lengths = self._paged_case(0)
        out = paged_decode_attention(q, kp, vp, table, lengths)
        ref = paged_decode_attention_reference(q, kp, vp, table, lengths)
        np.testing.assert_allclose(out, ref, atol=2e-6, rtol=2e-6)

    def test_matches_contiguous_attention(self, force_pallas):
        """Sequence 0's paged output == mha_reference over the pages
        gathered back into a contiguous (1, H, S, D) history."""
        from apex_tpu.ops.paged_attention import paged_decode_attention

        q, kp, vp, table, lengths = self._paged_case(1)
        out = paged_decode_attention(q, kp, vp, table, lengths)
        s0 = int(lengths[0])
        page = kp.shape[2]
        kc = jnp.moveaxis(kp[table[0]], 0, 1).reshape(
            kp.shape[1], -1, kp.shape[3]
        )[None, :, :s0]
        vc = jnp.moveaxis(vp[table[0]], 0, 1).reshape(
            vp.shape[1], -1, vp.shape[3]
        )[None, :, :s0]
        ref = mha_reference(
            q[0][None, :, None, :], kc, vc, scale=q.shape[-1] ** -0.5
        )
        np.testing.assert_allclose(
            out[0], ref[0, :, 0], atol=2e-6, rtol=2e-6
        )
        del page

    def test_fused_rope_matches_pre_rotated_query(self, force_pallas):
        """In-kernel q RoPE == rotating q first and attending plain."""
        from apex_tpu.ops.paged_attention import paged_decode_attention
        from apex_tpu.ops.rope import rotate_half

        q, kp, vp, table, lengths = self._paged_case(2)
        rs = np.random.RandomState(9)
        cos = jnp.asarray(rs.randn(q.shape[0], q.shape[2]), jnp.float32)
        sin = jnp.asarray(rs.randn(q.shape[0], q.shape[2]), jnp.float32)
        fused = paged_decode_attention(
            q, kp, vp, table, lengths, rope_cos=cos, rope_sin=sin
        )
        q_rot = q * cos[:, None, :] + rotate_half(q) * sin[:, None, :]
        plain = paged_decode_attention(q_rot, kp, vp, table, lengths)
        np.testing.assert_allclose(fused, plain, atol=2e-6, rtol=2e-6)
        # a half-width table must be refused, not compiled
        with pytest.raises(ValueError, match=r"must be \(B, D\)"):
            paged_decode_attention(
                q, kp, vp, table, lengths,
                rope_cos=cos[:, ::2], rope_sin=sin[:, ::2],
            )

    def test_int8_kv_dequant_matches_reference(self, force_pallas):
        """In-kernel int8 dequant == the reference's gather+dequant,
        and both sit near the f32 cache (codec quantization noise
        only)."""
        from apex_tpu.ops.paged_attention import (
            paged_decode_attention,
            paged_decode_attention_reference,
        )
        from apex_tpu.serve.cache import encode_kv

        q, kp, vp, table, lengths = self._paged_case(3)
        kq, ks = encode_kv(kp)
        vq, vs = encode_kv(vp)
        out = paged_decode_attention(
            q, kq, vq, table, lengths, k_scale=ks, v_scale=vs
        )
        ref = paged_decode_attention_reference(
            q, kq, vq, table, lengths, k_scale=ks, v_scale=vs
        )
        np.testing.assert_allclose(out, ref, atol=2e-6, rtol=2e-6)
        f32 = paged_decode_attention(q, kp, vp, table, lengths)
        assert float(jnp.abs(out - f32).max()) < 5e-2

    @staticmethod
    def _to_pool(pages, g):
        """Per-head pages ``(L, P, H, page, W)`` -> the serving layout
        ``(L, P, H/G, page, W*G)``: ``G`` heads side by side in a row."""
        l, p, h, page, w = pages.shape
        return jnp.transpose(
            pages.reshape(l, p, h // g, g, page, w), (0, 1, 2, 4, 3, 5)
        ).reshape(l, p, h // g, page, g * w)

    @staticmethod
    def _scale_pool(scales):
        """Per-head scales ``(L, P, H, page)`` -> the serving layout
        ``(L, P, 1, page, H)``: a token a row, a head a lane."""
        return jnp.swapaxes(scales, 2, 3)[:, :, None]

    @pytest.mark.parametrize("int8", [False, True], ids=["f32kv", "int8kv"])
    @pytest.mark.parametrize("rope", [False, True], ids=["plain", "rope"])
    @pytest.mark.parametrize("h,d,g", [
        (2, 128, 1), (4, 64, 2), (8, 32, 4), (3, 64, 1),
    ])
    def test_pool_layout_matches_per_head_pages(
        self, force_pallas, h, d, g, rope, int8
    ):
        """The kernel over the WHOLE pool ``(L, P, H/G, page, D*G)`` at
        a layer index == the reference over that layer's plain
        ``(P, H, page, D)`` pages, for every ``G`` the shapes give
        (``heads_per_row``; 3 heads of 64 lanes do not pair up), with
        and without fused RoPE and int8 scales."""
        from apex_tpu.ops.paged_attention import (
            heads_per_row,
            paged_decode_attention,
            paged_decode_attention_reference,
        )
        from apex_tpu.serve.cache import encode_kv

        assert heads_per_row(h, d) == g
        rs = np.random.RandomState(h * d)
        layers, pool, page, np_, b = 2, 10, 8, 3, 3
        kp = jnp.asarray(rs.randn(layers, pool, h, page, d), jnp.float32)
        vp = jnp.asarray(rs.randn(layers, pool, h, page, d), jnp.float32)
        q = jnp.asarray(rs.randn(b, h, d), jnp.float32)
        table = jnp.asarray(
            rs.permutation(pool - 1)[: b * np_].reshape(b, np_) + 1,
            jnp.int32,
        )
        lengths = jnp.asarray([19, 8, 0], jnp.int32)
        kw, pool_kw = {}, {}
        if rope:
            kw["rope_cos"] = jnp.asarray(rs.randn(b, d), jnp.float32)
            kw["rope_sin"] = jnp.asarray(rs.randn(b, d), jnp.float32)
        if int8:
            kp, ks = encode_kv(kp)
            vp, vs = encode_kv(vp)
            kw.update(k_scale=ks[1], v_scale=vs[1])
            pool_kw.update(
                k_scale=self._scale_pool(ks),
                v_scale=self._scale_pool(vs),
            )
        want = paged_decode_attention_reference(
            q, kp[1], vp[1], table, lengths, **kw
        )
        kw.update(pool_kw)
        got = paged_decode_attention(
            q, self._to_pool(kp, g), self._to_pool(vp, g), table, lengths,
            layer=jnp.asarray(1, jnp.int32), **kw
        )
        assert _dispatch.last_paths()["paged_decode_attention"] == "pallas"
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)
        assert float(jnp.abs(got[2]).max()) == 0.0  # the idle slot
        # the jnp path reads the same layout
        ref = paged_decode_attention_reference(
            q, self._to_pool(kp, g), self._to_pool(vp, g), table, lengths,
            layer=1, **kw
        )
        np.testing.assert_allclose(ref, want, atol=2e-6, rtol=2e-6)

    # -- the walk: K pages a step, clamped to each sequence's live pages --

    #: page 8 gives the table's K = 16 pages (128 positions) a step; 40
    #: table entries are then two whole steps and a ragged third
    WALK_PAGE, WALK_NP = 8, 40

    def _walk_case(self, h, d, dtype, *, rope, int8, seed=0):
        """One sequence for every boundary of the walk, its live pages
        scattered through the pool and its dead table entries left at the
        null page.  Returns the pool-layout operands and keywords."""
        from apex_tpu.ops.paged_attention import heads_per_row, pages_per_step
        from apex_tpu.serve.cache import encode_kv

        page, np_ = self.WALK_PAGE, self.WALK_NP
        g = heads_per_row(h, d)
        k = pages_per_step(page, h * d * (1 if int8 else 4), np_)
        assert k == 16 and np_ % k
        lengths = np.asarray([
            0, 1, page, page + 1, k * page - 1, k * page, k * page + 1,
            np_ * page,
        ], np.int32)
        live = -(-lengths // page)
        rs = np.random.RandomState(seed)
        layers, pool, b = 2, int(live.sum()) + 1, len(lengths)
        kp = jnp.asarray(rs.randn(layers, pool, h, page, d), jnp.float32)
        vp = jnp.asarray(rs.randn(layers, pool, h, page, d), jnp.float32)
        q = jnp.asarray(rs.randn(b, h, d), dtype)
        ids = list(rs.permutation(pool - 1) + 1)
        table = np.zeros((b, np_), np.int32)
        for row, n in enumerate(live):
            table[row, :n] = [ids.pop() for _ in range(n)]
        kw = {}
        if rope:
            kw["rope_cos"] = jnp.asarray(rs.randn(b, d), dtype)
            kw["rope_sin"] = jnp.asarray(rs.randn(b, d), dtype)
        if int8:
            kp, ks = encode_kv(kp)
            vp, vs = encode_kv(vp)
            kw["k_scale"] = self._scale_pool(ks)
            kw["v_scale"] = self._scale_pool(vs)
        else:
            kp, vp = kp.astype(dtype), vp.astype(dtype)
        args = (q, self._to_pool(kp, g), self._to_pool(vp, g),
                jnp.asarray(table), jnp.asarray(lengths))
        return args, dict(layer=jnp.asarray(1, jnp.int32), **kw)

    def _check_walk(self, h, d, dtype, rope, int8):
        from apex_tpu.ops.paged_attention import (
            paged_decode_attention,
            paged_decode_attention_reference,
        )

        args, kw = self._walk_case(h, d, dtype, rope=rope, int8=int8)
        got = paged_decode_attention(*args, **kw)
        assert _dispatch.last_paths()["paged_decode_attention"] == "pallas"
        want = paged_decode_attention_reference(*args, **kw)
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=tol, rtol=tol,
        )
        assert not np.asarray(got[0], np.float32).any()  # the idle slot

    @pytest.mark.parametrize("int8", [False, True], ids=["kv", "int8kv"])
    @pytest.mark.parametrize("rope", [False, True], ids=["plain", "rope"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("h,d", [(2, 128), (4, 64)], ids=["G1", "G2"])
    def test_walk_boundaries_match_reference(
        self, force_pallas, h, d, dtype, rope, int8
    ):
        """Lengths 0, 1, page, page + 1, K*page - 1, K*page, K*page + 1
        and NP*page (NP not a multiple of K) against the gather
        reference, one or two heads a lane row, with and without fused
        RoPE and int8 scales, f32 and bf16."""
        self._check_walk(h, d, dtype, rope, int8)

    @pytest.mark.parametrize("dtype,rope,int8", [
        (jnp.float32, True, False), (jnp.float32, True, True),
        (jnp.bfloat16, False, True),
    ], ids=["f32-rope-kv", "f32-rope-int8kv", "bf16-plain-int8kv"])
    @pytest.mark.parametrize("h,d", [(3, 64), (2, 80), (2, 192)],
                             ids=["3x64", "d80", "d192"])
    def test_walk_over_rows_padded_to_whole_tiles(
        self, force_pallas, h, d, dtype, rope, int8
    ):
        """The same boundaries over pools whose rows do not fill their
        128-lane tiles — heads that do not pair up, heads of 80 and of
        192 lanes: one head a row, zero lanes up to the tile, the fused
        rotation inside the head's own lanes."""
        self._check_walk(h, d, dtype, rope, int8)

    @pytest.mark.parametrize("int8", [False, True], ids=["kv", "int8kv"])
    def test_dead_table_entries_are_never_read(self, force_pallas, int8):
        """Entries past a sequence's live pages may point anywhere in the
        pool: here at pages full of NaN (and NaN scales).  The output is
        bit-for-bit what null entries give, so nothing of them was read —
        codes, values or scales."""
        from apex_tpu.ops.paged_attention import (
            paged_decode_attention,
            paged_decode_attention_reference,
        )

        (q, kp, vp, table, lengths), kw = self._walk_case(
            4, 64, jnp.float32, rope=False, int8=int8, seed=1
        )
        want = paged_decode_attention(q, kp, vp, table, lengths, **kw)
        # two more pages at the pool's end, poisoned in every layer
        def poisoned(x, value):
            pad = jnp.full((x.shape[0], 2) + x.shape[2:], value, x.dtype)
            return jnp.concatenate([x, pad], axis=1)

        bad = kp.shape[1] + np.arange(2)
        live = -(-np.asarray(lengths) // self.WALK_PAGE)
        dead = np.arange(self.WALK_NP)[None, :] >= live[:, None]
        dirty = np.where(
            dead, bad[np.arange(dead.size).reshape(dead.shape) % 2], table
        )
        if int8:
            kw.update(
                k_scale=poisoned(kw["k_scale"], jnp.nan),
                v_scale=poisoned(kw["v_scale"], jnp.nan),
            )
            kp, vp = poisoned(kp, 127), poisoned(vp, 127)
        else:
            kp, vp = poisoned(kp, jnp.nan), poisoned(vp, jnp.nan)
        got = paged_decode_attention(
            q, kp, vp, jnp.asarray(dirty, jnp.int32), lengths, **kw
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        ref = paged_decode_attention_reference(
            q, kp, vp, table, lengths, **kw
        )
        np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("page,row_bytes,np_,want", [
        (16, 10 * 128 * 2, 64, 8),    # GPT-2 Large's bf16 pool: 128 rows
        (16, 10 * 128 * 1, 64, 8),    # the same pool on the int8 wire
        (128, 8 * 128 * 4, 2, 1),     # a page of 128 rows is a step
        (8, 4 * 128 * 4, 3, 3),       # a table narrower than a step
        (16, 64 * 128 * 4, 64, 2),    # a row so wide the bytes bound it
    ])
    def test_pages_per_step_table(self, page, row_bytes, np_, want):
        from apex_tpu.ops.paged_attention import pages_per_step

        assert pages_per_step(page, row_bytes, np_) == want

    def test_walk_live_share(self):
        """Live pages over pages copied: a step's tail re-reads its last
        live page, an idle slot copies nothing."""
        from apex_tpu.ops.paged_attention import walk_live_share

        # 11 live pages in 2 steps of 8, 47 in 6, an idle slot
        pool = jax.ShapeDtypeStruct((36, 1201, 10, 16, 128), jnp.bfloat16)
        lengths = np.asarray([176, 0, 740], np.int32)
        assert walk_live_share(lengths, pool, 64) == pytest.approx(58 / 64)
        assert walk_live_share(np.asarray([128, 256]), pool, 64) == 1.0
        assert walk_live_share(np.zeros(4, np.int32), pool, 64) is None
        # K is the kernel's own: a table of 4 entries is one step of 4
        assert walk_live_share(np.asarray([16]), pool, 4) == 0.25

    def test_pool_operands_are_checked(self, force_pallas):
        from apex_tpu.ops.paged_attention import paged_decode_attention

        q, kp, vp, table, lengths = self._paged_case(6)
        with pytest.raises(ValueError, match="needs its layer index"):
            paged_decode_attention(q, kp[None], vp[None], table, lengths)
        with pytest.raises(ValueError, match="pages here are 4-D"):
            paged_decode_attention(q, kp, vp, table, lengths, layer=0)
        with pytest.raises(ValueError, match="do not hold"):
            paged_decode_attention(
                q, kp[None, :, :3], vp[None, :, :3], table, lengths, layer=0
            )

    def test_idle_slot_returns_zeros(self, force_pallas):
        from apex_tpu.ops.paged_attention import paged_decode_attention

        q, kp, vp, table, lengths = self._paged_case(4)
        out = paged_decode_attention(q, kp, vp, table, lengths)
        assert float(jnp.abs(out[2]).max()) == 0.0  # lengths[2] == 0

    def test_narrow_rows_are_padded_to_whole_tiles(self, force_pallas,
                                                   monkeypatch):
        """Mosaic copies out of HBM by whole 128-lane rows, so the kernel
        only ever sees lane-dense rows: pages of 32 lanes reach it
        zero-padded to 128, on the interpreter as on the chip, and a pool
        whose rows are not whole tiles is refused by the kernel itself."""
        from apex_tpu.ops import paged_attention as pa
        from apex_tpu.ops.pallas import decode_attention as da

        seen = []
        fwd = pa.paged_decode_fwd
        monkeypatch.setattr(pa, "paged_decode_fwd", lambda q, k, *a, **kw: (
            seen.append(k.shape) or fwd(q, k, *a, **kw)))
        q, kp, vp, table, lengths = self._paged_case(7)  # rows of 32 lanes
        out = pa.paged_decode_attention(q, kp, vp, table, lengths)
        assert _dispatch.last_paths()["paged_decode_attention"] == "pallas"
        assert seen == [(1,) + kp.shape[:3] + (128,)]
        want = pa.paged_decode_attention_reference(q, kp, vp, table, lengths)
        np.testing.assert_allclose(out, want, atol=2e-6, rtol=2e-6)
        assert da.lane_width(1) == da.lane_width(128) == 128
        assert da.lane_width(129) == da.lane_width(192) == 256
        with pytest.raises(ValueError, match="whole 128-lane tiles"):
            da.paged_decode_fwd(
                q, kp[None], vp[None], table, lengths, 0, scale=1.0
            )

    def test_jnp_dispatch_default_off_tpu(self):
        """Auto mode off-TPU routes to the gather-based jnp path (the
        kernel runs interpret-mode only when forced or on real TPU)."""
        from apex_tpu.ops import paged_attention as pa

        q, kp, vp, table, lengths = self._paged_case(5)
        pa.paged_decode_attention(q, kp, vp, table, lengths)
        assert _dispatch.last_paths()["paged_decode_attention"] == "jnp"
