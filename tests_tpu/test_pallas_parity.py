"""Compiled Pallas kernels vs the jnp reference path, on the real chip.

Each case computes the op twice — ``set_use_pallas(True)`` (Mosaic-compiled
kernel) and ``set_use_pallas(False)`` (XLA jnp path, the correctness
reference) — on identical inputs, for forward values AND input cotangents.
≙ the reference's contrib/test pattern (CUDA kernel vs torch composition),
SURVEY §4(1).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import _dispatch
from apex_tpu.ops.attention import flash_attention, mha_reference
from apex_tpu.ops.layer_norm import (
    fused_layer_norm_affine,
    fused_rms_norm_affine,
)

# bf16 inputs, f32 kernel-internal compute on both paths: outputs agree to
# ~1e-2 absolute (bf16 rounding of the result), f32 to ~1e-5.
TOL = {jnp.bfloat16: dict(atol=2e-2, rtol=2e-2),
       jnp.float32: dict(atol=2e-5, rtol=2e-5)}


def _both_paths(fn, *args):
    # "highest" pins the XLA reference's f32 dots to true-f32 multi-pass
    # form, matching the kernels' explicit f32 HIGHEST precision — at
    # DEFAULT both sides do single-pass-bf16 mults with *different*
    # summation structure, and f32 parity would be bf16-grade.  (bf16
    # inputs are unaffected: their products are exact in f32 either way.)
    with jax.default_matmul_precision("highest"):
        _dispatch.set_use_pallas(True)
        got = jax.jit(fn)(*args)
        _dispatch.set_use_pallas(False)
        want = jax.jit(fn)(*args)
        _dispatch.set_use_pallas(None)
        return got, want


def _assert_close(got, want, dtype):
    jax.tree_util.tree_map(
        lambda g, w: np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            **TOL[dtype],
        ),
        got, want,
    )


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("memory_efficient", [False, True])
@pytest.mark.parametrize("rows,hidden", [(512, 1024), (64, 4096), (128, 768)])
def test_layer_norm_fwd_bwd(dtype, memory_efficient, rows, hidden):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (rows, hidden), dtype)
    w = jax.random.normal(k2, (hidden,), jnp.float32) * 0.1 + 1.0
    b = jnp.linspace(-1.0, 1.0, hidden, dtype=jnp.float32)

    def f(x, w, b):
        y = fused_layer_norm_affine(
            x, w, b, (hidden,), memory_efficient=memory_efficient
        )
        return jnp.sum(y.astype(jnp.float32) ** 2)

    fn = jax.value_and_grad(f, argnums=(0, 1, 2))
    got, want = _both_paths(fn, x, w, b)
    _assert_close(got, want, dtype)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_rms_norm_fwd_bwd(dtype):
    hidden = 1024
    x = jax.random.normal(jax.random.PRNGKey(0), (256, hidden), dtype)
    w = jnp.ones((hidden,), jnp.float32)

    def f(x, w):
        y = fused_rms_norm_affine(x, w, (hidden,))
        return jnp.sum(y.astype(jnp.float32) ** 2)

    got, want = _both_paths(jax.value_and_grad(f, argnums=(0, 1)), x, w)
    _assert_close(got, want, dtype)


def _qkv(b, h, sq, sk, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, h, sq, d), dtype)
    k = jax.random.normal(ks[1], (b, h, sk, d), dtype)
    v = jax.random.normal(ks[2], (b, h, sk, d), dtype)
    return q, k, v


def _attn_loss(attn_fn, q, k, v, bias=None, **kw):
    y = attn_fn(q, k, v, bias, **kw)
    return jnp.sum(y.astype(jnp.float32) ** 2)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize(
    "b,h,sq,sk,d,causal",
    [
        (2, 4, 256, 256, 128, False),   # lane-native head dim
        (2, 4, 256, 256, 128, True),    # causal
        (2, 4, 256, 256, 64, False),    # D=64 (padded inside the kernel)
        (1, 8, 128, 512, 128, False),   # enc-dec (Sq != Sk)
        (1, 8, 512, 256, 128, True),    # causal, bottom-right aligned
        (1, 2, 4096, 4096, 128, True),  # long context (multi-KV-block path)
    ],
)
def test_flash_attention_fwd_bwd(dtype, b, h, sq, sk, d, causal):
    q, k, v = _qkv(b, h, sq, sk, d, dtype)

    # Pallas flash kernel (forced) vs the unfused composition evaluated in
    # FULL f32 — the ground truth.  Comparing same-dtype against the bf16
    # reference would gate the kernel on the *reference's* noise: e.g. its
    # softmax-backward suffers bf16 cancellation at single-visible-key rows
    # (true gradient exactly 0, reference ~1e-1), where the kernel's
    # closed-form delta is exact.  "highest" pins the f32 dots of both
    # sides to true-f32 multi-pass MXU form.
    grad_fn = jax.value_and_grad(
        functools.partial(_attn_loss, flash_attention, causal=causal),
        argnums=(0, 1, 2),
    )
    with jax.default_matmul_precision("highest"):
        _dispatch.set_use_pallas(True)
        got = jax.jit(grad_fn)(q, k, v)
        _dispatch.set_use_pallas(None)
        want = jax.jit(
            jax.value_and_grad(
                functools.partial(_attn_loss, mha_reference, causal=causal),
                argnums=(0, 1, 2),
            )
        )(
            q.astype(jnp.float32),
            k.astype(jnp.float32),
            v.astype(jnp.float32),
        )
    # measured-on-chip error vs f32 truth across this matrix: f32 <= 4e-4
    # (causal dk worst: recompute + per-block accumulation order), bf16
    # <= 4e-2; 2.5x headroom on each
    tol = (
        dict(atol=1e-3, rtol=1e-3)
        if dtype == jnp.float32
        else dict(atol=1e-1, rtol=1e-1)
    )
    jax.tree_util.tree_map(
        lambda g, w: np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32), **tol
        ),
        got, want,
    )


@pytest.mark.parametrize("rs", [1, None])  # key-padding row vs full rows
def test_flash_attention_bias(rs):
    """Additive key-padding bias (the (B,1,1,Sk) mask path)."""
    b, h, s, d = 2, 4, 256, 128
    dtype = jnp.bfloat16
    q, k, v = _qkv(b, h, s, s, d, dtype)
    if rs == 1:
        keep = jax.random.bernoulli(jax.random.PRNGKey(3), 0.8, (b, 1, 1, s))
    else:
        keep = jax.random.bernoulli(
            jax.random.PRNGKey(3), 0.8, (b, 1, s, s)
        )
    bias = jnp.where(keep, 0.0, -1e9).astype(jnp.float32)

    _dispatch.set_use_pallas(True)
    got = jax.jit(functools.partial(flash_attention))(q, k, v, bias)
    _dispatch.set_use_pallas(None)
    want = jax.jit(functools.partial(mha_reference))(q, k, v, bias)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=8e-2, rtol=8e-2,
    )


@pytest.mark.parametrize(
    "bias_shape",
    [
        (1, 1, 256, 256),  # G=1,  RS=Sq
        (2, 1, 256, 256),  # G=B,  RS=Sq
        (2, 4, 256, 256),  # G=BH, RS=Sq
        (1, 4, 256, 256),  # B-broadcast -> G=BH + unbroadcast sum
        (2, 1, 1, 256),    # G=B,  RS=1 (key row)
        (1, 1, 1, 256),    # G=1,  RS=1
    ],
)
@pytest.mark.parametrize("causal", [False, True])
def test_flash_dbias_on_chip(bias_shape, causal):
    """Trainable-bias backward (flash_dbias kernel) vs the f32 unfused
    reference on the real chip, across the (G, RS) group-layout matrix
    (VERDICT r2 #3)."""
    b, h, s, d = 2, 4, 256, 64
    q, k, v = _qkv(b, h, s, s, d, jnp.float32)
    bias = (
        jax.random.normal(jax.random.PRNGKey(9), bias_shape, jnp.float32)
        * 0.3
    )

    def loss(attn_fn, bias, **kw):
        return jnp.sum(attn_fn(q, k, v, bias, **kw) ** 2)

    with jax.default_matmul_precision("highest"):
        _dispatch.set_use_pallas(True)
        got = jax.jit(
            jax.grad(
                functools.partial(
                    loss, flash_attention, causal=causal, bias_grad=True
                )
            )
        )(bias)
        _dispatch.set_use_pallas(None)
        want = jax.jit(
            jax.grad(functools.partial(loss, mha_reference, causal=causal))
        )(bias)
    assert got.shape == bias.shape
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-3, rtol=1e-3
    )
    assert float(jnp.max(jnp.abs(got))) > 1e-6


@pytest.mark.parametrize(
    "sq,sk", [(100, 100), (1000, 1000), (4100, 4100), (333, 259)]
)
@pytest.mark.parametrize("causal", [False, True])
def test_flash_arbitrary_seq_on_chip(sq, sk, causal):
    """Arbitrary S on the kernel path via padding+key-masking (VERDICT r2
    #4): fwd+bwd parity at S ∈ {100, 1000, ~4k, mixed} on the real chip."""
    b, h, d = 1, 2, 64
    q, k, v = _qkv(b, h, sq, sk, d, jnp.float32)

    grad_fn = jax.value_and_grad(
        functools.partial(_attn_loss, flash_attention, causal=causal),
        argnums=(0, 1, 2),
    )
    with jax.default_matmul_precision("highest"):
        _dispatch.set_use_pallas(True)
        got = jax.jit(grad_fn)(q, k, v)
        _dispatch.set_use_pallas(None)
        want = jax.jit(
            jax.value_and_grad(
                functools.partial(_attn_loss, mha_reference, causal=causal),
                argnums=(0, 1, 2),
            )
        )(q, k, v)
    jax.tree_util.tree_map(
        lambda g, w: np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=2e-3, rtol=2e-3
        ),
        got, want,
    )


def test_scaled_softmax_compiled_matches_jnp():
    """The megatron softmax quartet is pure jnp (no Pallas kernel) but the
    custom VJP must agree with autodiff of the plain composition when
    compiled for TPU."""
    from apex_tpu.ops.scaled_softmax import scaled_masked_softmax

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 128, 128), jnp.bfloat16)
    mask = jax.random.bernoulli(jax.random.PRNGKey(1), 0.2, (2, 1, 128, 128))

    def fused(x):
        return jnp.sum(
            scaled_masked_softmax(x, mask, 0.5).astype(jnp.float32) ** 2
        )

    def ref(x):
        xs = x.astype(jnp.float32) * 0.5
        xs = jnp.where(mask, -10000.0, xs)
        y = jax.nn.softmax(xs, axis=-1)
        all_masked = jnp.all(mask, axis=-1, keepdims=True)
        y = jnp.where(all_masked, 0.0, y).astype(x.dtype)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    gv = jax.jit(jax.value_and_grad(fused))(x)
    wv = jax.jit(jax.value_and_grad(ref))(x)
    _assert_close(gv, wv, jnp.bfloat16)


def _kernel_keep_mask_full(seed, b, h, sq, sk, p):
    """Full (B,H,Sq,Sk) keep mask of the kernel's counter-based PRNG —
    `_dropout_keep_block` is a pure function of (seed, bh, absolute
    coords), so tile (0,0) at full size reproduces every kernel tile
    (identical on Mosaic and the host: pure uint32 arithmetic)."""
    from apex_tpu.ops.pallas.flash_attention import _dropout_keep_block

    return jnp.stack([
        _dropout_keep_block(seed, jnp.asarray(bh, jnp.int32), 0, 0, sq, sk, p)
        for bh in range(b * h)
    ]).reshape(b, h, sq, sk)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_dropout_on_chip(causal):
    """Compiled fused dropout vs the keep-mask golden: the Mosaic kernel
    must regenerate the identical mask the host-side hash predicts
    (values AND grads), and be deterministic across calls.  The jnp
    dispatch path draws a DIFFERENT stream by documented contract, so
    kernel-vs-jnp comparison is only valid through the shared mask."""
    from apex_tpu.ops.attention import _derive_dropout_seed, _scores

    b, h, s, d, p = 1, 2, 256, 64, 0.2
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(kq, (b, h, s, d), jnp.float32)
    k = jax.random.normal(kk, (b, h, s, d), jnp.float32)
    v = jax.random.normal(kv, (b, h, s, d), jnp.float32)
    rng = jax.random.PRNGKey(12)
    scale = 1.0 / (d ** 0.5)
    keep = _kernel_keep_mask_full(
        _derive_dropout_seed(rng, p)[0], b, h, s, s, p
    )

    def kernel_loss(q, k, v):
        o = flash_attention(
            q, k, v, causal=causal, dropout_p=p, dropout_rng=rng
        )
        return jnp.sum(o.astype(jnp.float32) ** 2), o

    def golden_loss(q, k, v):
        s_ = _scores(q, k, None, causal, scale)
        probs = jax.nn.softmax(s_, axis=-1)
        pd = jnp.where(keep, probs / (1.0 - p), 0.0)
        o = jnp.einsum("bhqk,bhkd->bhqd", pd.astype(q.dtype), v)
        return jnp.sum(o.astype(jnp.float32) ** 2), o

    with jax.default_matmul_precision("highest"):
        _dispatch.set_use_pallas(True)
        try:
            (l_k, o_k), g_k = jax.jit(jax.value_and_grad(
                kernel_loss, argnums=(0, 1, 2), has_aux=True
            ))(q, k, v)
            (_, o_k2), _ = jax.jit(jax.value_and_grad(
                kernel_loss, argnums=(0, 1, 2), has_aux=True
            ))(q, k, v)
        finally:
            _dispatch.set_use_pallas(None)
        (l_g, o_g), g_g = jax.jit(jax.value_and_grad(
            golden_loss, argnums=(0, 1, 2), has_aux=True
        ))(q, k, v)

    np.testing.assert_array_equal(np.asarray(o_k), np.asarray(o_k2))
    np.testing.assert_allclose(
        np.asarray(o_k), np.asarray(o_g), atol=2e-5, rtol=2e-5
    )
    # The flash backward recomputes p and groups the ds = p*(dp - delta)
    # cancellation differently from the golden einsum, and causal
    # near-diagonal rows (few visible keys, true grad ~0) amplify it.
    # Measured max deviation: 4.4e-4 abs / 7.8e-4 rel on v5e Mosaic
    # (libtpu 0.0.34, PR 21: 1 element of 32768 over the old 2e-4 TPU
    # bound, which dated from the 2026-08-01 toolchain's 6.9e-5 rel) and
    # 4.8e-4 abs on CPU interpret — one bound, ~2x both.  A keep-mask
    # flip would show O(|grad|)≈1e-2+ diffs, well above it; mask
    # identity is already pinned by the 2e-5 forward check above.
    grad_atol = 1e-3
    for a, b_ in zip(g_k, g_g):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=grad_atol, rtol=2e-4
        )


def test_with_lse_dropout_on_chip():
    """Compiled with-lse dropout: lse stays the undropped statistic and
    the dlse cotangent bypasses the keep mask (the ring-attention
    building block) — vs the keep-mask golden."""
    from apex_tpu.ops.attention import (
        _derive_dropout_seed,
        _scores,
        flash_attention_with_lse,
    )

    b, h, s, d, p = 1, 2, 256, 64, 0.25
    kq, kk, kv, kc = jax.random.split(jax.random.PRNGKey(21), 4)
    q = jax.random.normal(kq, (b, h, s, d), jnp.float32)
    k = jax.random.normal(kk, (b, h, s, d), jnp.float32)
    v = jax.random.normal(kv, (b, h, s, d), jnp.float32)
    dlse_w = jax.random.normal(kc, (b, h, s), jnp.float32)
    rng = jax.random.PRNGKey(22)
    scale = 1.0 / (d ** 0.5)
    keep = _kernel_keep_mask_full(
        _derive_dropout_seed(rng, p)[0], b, h, s, s, p
    )

    def kernel_loss(q, k, v):
        o, lse = flash_attention_with_lse(
            q, k, v, dropout_p=p, dropout_rng=rng
        )
        return (
            jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(lse * dlse_w),
            (o, lse),
        )

    def golden_loss(q, k, v):
        s_ = _scores(q, k, None, False, scale)
        m = jnp.max(s_, axis=-1, keepdims=True)
        pe = jnp.exp(s_ - m)
        l = jnp.sum(pe, axis=-1, keepdims=True)
        pd = jnp.where(keep, (pe / l) / (1.0 - p), 0.0)
        o = jnp.einsum("bhqk,bhkd->bhqd", pd.astype(q.dtype), v)
        lse = (m + jnp.log(l))[..., 0]
        return (
            jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(lse * dlse_w),
            (o, lse),
        )

    with jax.default_matmul_precision("highest"):
        _dispatch.set_use_pallas(True)
        try:
            (_, (o_k, lse_k)), g_k = jax.jit(jax.value_and_grad(
                kernel_loss, argnums=(0, 1, 2), has_aux=True
            ))(q, k, v)
        finally:
            _dispatch.set_use_pallas(None)
        (_, (o_g, lse_g)), g_g = jax.jit(jax.value_and_grad(
            golden_loss, argnums=(0, 1, 2), has_aux=True
        ))(q, k, v)

    np.testing.assert_allclose(
        np.asarray(o_k), np.asarray(o_g), atol=2e-5, rtol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(lse_k), np.asarray(lse_g), atol=1e-5, rtol=1e-5
    )
    for a, b_ in zip(g_k, g_g):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=5e-5, rtol=5e-5
        )


def test_sums_remat_policy_on_chip():
    """remat_policy='sums' (named saves freeing matmul epilogues, r3) must
    compile under Mosaic/XLA-TPU and reproduce the 'dots' loss and grads
    numerically on the real chip — guards against TPU-specific issues
    with save_only_these_names before the policy is benched.  Unlike the
    CPU parity test (bit-identical), the chip cannot be: the two save
    sets draw different fusion boundaries, so bf16 rounding differs
    (measured loss rel dev 4.9e-5 on v5e)."""
    from apex_tpu.models import (
        BertConfig,
        BertForPreTraining,
        bert_pretrain_loss,
    )

    kw = dict(
        vocab_size=512, hidden_size=128, num_layers=2, num_heads=8,
        intermediate_size=256, max_position_embeddings=64,
        dtype=jnp.bfloat16,
    )
    ids = jax.random.randint(jax.random.PRNGKey(1), (64, 8), 0, 512)
    batch = {
        "input_ids": ids,
        "attention_mask": jnp.ones((8, 64), jnp.int32),
        "mlm_labels": jnp.where(ids % 5 == 0, ids, -1),
        "nsp_labels": jnp.zeros((8,), jnp.int32),
    }

    def loss_and_grads(policy):
        m = BertForPreTraining(
            BertConfig(remat=True, remat_policy=policy, **kw)
        )
        params = m.init(jax.random.PRNGKey(0), ids)
        return jax.jit(
            jax.value_and_grad(lambda p: bert_pretrain_loss(p, m, batch))
        )(params)

    l_d, g_d = loss_and_grads("dots")
    l_s, g_s = loss_and_grads("sums")
    np.testing.assert_allclose(float(l_d), float(l_s), rtol=2e-4)

    # Per-leaf relative L2, not elementwise rel: this model is bf16, and
    # the two policies recompute different subgraphs, so near-zero grad
    # elements carry cancellation noise that elementwise relative error
    # amplifies without bound (measured: 6.6% rel on a 0.007-magnitude
    # element).  Worst measured leaf rel-L2: 9.7e-3 (CPU interpret) —
    # the bf16 noise floor (eps ~ 8e-3); bound at 2x.  Exact f32 parity
    # vs no-remat is pinned separately in
    # tests/test_models.py::test_remat_policy_preserves_values.
    def _leaf_rel_l2(path, a, b):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        denom = max(float(np.linalg.norm(a)), 1e-12)
        rel = float(np.linalg.norm(a - b)) / denom
        assert rel < 2e-2, (
            f"grad leaf {jax.tree_util.keystr(path)} rel-L2 {rel:.2e}"
            f" >= 2e-2 (dots {a.ravel()[:4]}... vs sums {b.ravel()[:4]}...)"
        )

    jax.tree_util.tree_map_with_path(_leaf_rel_l2, g_d, g_s)


def test_flash_bwd_independent_dq_tiles_on_chip():
    """block_q_dq/block_k_dq (the r5 backward-tuning lever): compiled
    Mosaic results must be insensitive to the dq call's tile choice —
    dk/dv bit-identical (unchanged dkdv program), dq within f32
    accumulation-order tolerance."""
    from apex_tpu.ops.pallas import flash_attention as fa

    sq, d = 512, 64
    key = jax.random.PRNGKey(11)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (4, sq, d), jnp.bfloat16)
    k = jax.random.normal(kk, (4, sq, d), jnp.bfloat16)
    v = jax.random.normal(kv, (4, sq, d), jnp.bfloat16)
    kw = dict(scale=d ** -0.5, causal=True, block_q=256, block_k=256)
    o, lse = fa.flash_fwd(q, k, v, None, **kw)
    do = 2.0 * o
    base = fa.flash_bwd(q, k, v, o, lse, do, None, **kw)
    for bq_dq, bk_dq in ((512, 256), (128, 512)):
        alt = fa.flash_bwd(
            q, k, v, o, lse, do, None, block_q_dq=bq_dq,
            block_k_dq=bk_dq, **kw,
        )
        np.testing.assert_allclose(
            np.asarray(alt[0], np.float32), np.asarray(base[0], np.float32),
            atol=2e-2, rtol=2e-2,
        )
        for a, b in zip(alt[1:], base[1:]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# paged single-query decode attention (serving kernel, docs/serving.md)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_int8", [False, True])
@pytest.mark.parametrize("d", [128, 64])
def test_paged_decode_attention_on_chip(kv_int8, d):
    """Compiled page-walk kernel (page copies through the scalar-prefetched
    table + fused q-RoPE + optional int8 scales) vs the jnp gather
    reference, on the real chip, over plain ``(P, H, page, D)`` pages:
    page=128 rows, H=8 heads, D=128 lanes (one head a lane row) and D=64
    (rows narrower than a tile: the op pads them to whole 128-lane tiles
    and the kernel walks them like any pool — no shape falls back)."""
    from apex_tpu.ops.paged_attention import (
        paged_decode_attention,
        paged_decode_attention_reference,
    )
    from apex_tpu.serve.cache import encode_kv

    b, h, page, pool, np_ = 2, 8, 128, 8, 2
    rs = np.random.RandomState(0)
    k_pages = jnp.asarray(rs.randn(pool, h, page, d), jnp.float32)
    v_pages = jnp.asarray(rs.randn(pool, h, page, d), jnp.float32)
    q = jnp.asarray(rs.randn(b, h, d), jnp.float32)
    cos = jnp.asarray(rs.randn(b, d), jnp.float32)
    sin = jnp.asarray(rs.randn(b, d), jnp.float32)
    table = jnp.asarray([[1, 3], [5, 2]], jnp.int32)
    lengths = jnp.asarray([200, 37], jnp.int32)
    kw = dict(rope_cos=cos, rope_sin=sin)
    if kv_int8:
        k_pages, ks = encode_kv(k_pages)
        v_pages, vs = encode_kv(v_pages)
        kw.update(k_scale=ks, v_scale=vs)

    # "highest" pins the XLA reference's f32 einsums to true-f32 form,
    # matching the kernel's explicit HIGHEST for f32 queries (the
    # _both_paths convention above): at DEFAULT both sides multiply in
    # one bf16 pass with different summation structure — measured
    # 3.5e-3 abs apart on v5e (libtpu 0.0.34, PR 21), bf16-grade.
    with jax.default_matmul_precision("highest"):
        _dispatch.set_use_pallas(True)
        try:
            got = paged_decode_attention(
                q, k_pages, v_pages, table, lengths, **kw
            )
            assert (
                _dispatch.last_paths()["paged_decode_attention"] == "pallas"
            )
        finally:
            _dispatch.set_use_pallas(None)
        want = paged_decode_attention_reference(
            q, k_pages, v_pages, table, lengths, **kw
        )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("kv_int8", [False, True])
def test_paged_decode_walk_at_the_cells_shape_on_chip(kv_int8):
    """The walk at the serving cells' own shape — 32 slots, 20 heads of
    64 lanes, 16-row pages, 64 table entries, so 8 pages a step — over a
    mix of idle, short and 960-token rows whose dead table entries point
    at a page full of NaN: the compiled kernel against the jnp reference
    on the clean table, and nothing of a dead entry read."""
    from apex_tpu.ops.paged_attention import (
        paged_decode_attention,
        paged_decode_attention_reference,
        pages_per_step,
    )
    b, h, d, page, np_, layers, pool = 32, 20, 64, 16, 64, 3, 1201
    assert pages_per_step(page, 10 * 128 * (1 if kv_int8 else 2), np_) == 8
    rs = np.random.RandomState(2)
    lengths = np.zeros(b, np.int32)
    lengths[[1, 2, 5, 7, 8, 13]] = [1, 16, 17, 127, 128, 129]
    lengths[[3, 9, 20, 31]] = [176, 37, 251, 1024]
    lengths[[4, 11, 12, 17, 18, 19, 25, 30]] = 960
    live = -(-lengths // page)
    ids = list(rs.permutation(pool - 2) + 1)  # page 0 null, the last poison
    clean = np.zeros((b, np_), np.int32)
    for row, n in enumerate(live):
        clean[row, :n] = [ids.pop() for _ in range(n)]
    dead = np.arange(np_)[None, :] >= live[:, None]
    dirty = np.where(dead, pool - 1, clean).astype(np.int32)

    shape = (layers, pool, h // 2, page, 2 * d)
    k = jnp.asarray(rs.randn(*shape), jnp.float32)
    v = jnp.asarray(rs.randn(*shape), jnp.float32)
    kw = dict(layer=jnp.asarray(1, jnp.int32))
    if kv_int8:
        # the codec at block = D: one scale per (head, token), stored a
        # token a row and a head a lane, (L, P, 1, page, 128)
        def encode(x):
            heads = x.reshape(shape[:-1] + (2, d))
            scale = jnp.max(jnp.abs(heads), axis=-1) / 127.0
            codes = jnp.round(heads / scale[..., None]).astype(jnp.int8)
            rows = jnp.swapaxes(scale, 2, 3).reshape(layers, pool, page, h)
            rows = jnp.pad(rows, [(0, 0)] * 3 + [(0, 128 - h)])
            return codes.reshape(shape), rows[:, :, None]

        (k, ks), (v, vs) = encode(k), encode(v)
        kw.update(k_scale=ks.at[:, -1].set(jnp.nan),
                  v_scale=vs.at[:, -1].set(jnp.nan))
        k, v = k.at[:, -1].set(127), v.at[:, -1].set(127)
    else:
        k = k.astype(jnp.bfloat16).at[:, -1].set(jnp.nan)
        v = v.astype(jnp.bfloat16).at[:, -1].set(jnp.nan)
    q = jnp.asarray(rs.randn(b, h, d), jnp.bfloat16)
    lengths = jnp.asarray(lengths)
    _dispatch.set_use_pallas(True)
    try:
        got = paged_decode_attention(q, k, v, jnp.asarray(dirty), lengths, **kw)
        assert _dispatch.last_paths()["paged_decode_attention"] == "pallas"
        same = paged_decode_attention(q, k, v, jnp.asarray(clean), lengths, **kw)
    finally:
        _dispatch.set_use_pallas(None)
    want = paged_decode_attention_reference(
        q, k, v, jnp.asarray(clean), lengths, **kw
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(same))
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=2e-2, rtol=2e-2,
    )
    assert not np.asarray(got, np.float32)[np.asarray(lengths) == 0].any()


@pytest.mark.parametrize("kv_int8", [False, True])
@pytest.mark.parametrize("h,d,rows", [
    (20, 64, (10, 128)), (25, 64, (25, 128)), (20, 80, (20, 128)),
    (12, 96, (12, 128)), (8, 192, (8, 256)),
], ids=["gpt2-large", "25x64", "d80", "d96", "d192"])
def test_paged_decode_pool_layout_on_chip(kv_int8, h, d, rows):
    """The serving form: the whole bf16 pool ``(L, P, H/G, 16, W)`` read
    at a layer index through the engine's own helpers (``init_kv_pages``
    / ``write_prompt_kv``), Mosaic kernel against the jnp reference on
    the same pool — at GPT-2 Large's row shape (two heads a 128-lane
    row) and at rows that do not fill their tiles (25 heads of 64, heads
    of 80, 96 and 192 lanes: one head a row, padded), with fused RoPE."""
    from apex_tpu.ops.paged_attention import (
        paged_decode_attention,
        paged_decode_attention_reference,
    )
    from apex_tpu.serve import cache as cache_lib

    b, page, np_, layers = 4, 16, 8, 3
    rs = np.random.RandomState(1)
    kv = cache_lib.init_kv_pages(
        layers, 1 + b * np_, h, page, d, dtype=jnp.bfloat16,
        kv_wire="int8" if kv_int8 else "f32",
    )
    assert kv["k"].shape == (layers, 1 + b * np_) + rows[:1] + (page,) + rows[1:]
    table = jnp.arange(1, 1 + b * np_, dtype=jnp.int32).reshape(b, np_)
    for layer in range(layers):
        for seq in range(b):
            k = jnp.asarray(rs.randn(np_ * page, h, d), jnp.bfloat16)
            v = jnp.asarray(rs.randn(np_ * page, h, d), jnp.bfloat16)
            kv = cache_lib.write_prompt_kv(kv, layer, table[seq], k, v)
    q = jnp.asarray(rs.randn(b, h, d), jnp.bfloat16)
    lengths = jnp.asarray([np_ * page, 37, 1, 0], jnp.int32)
    args = (q, kv["k"], kv["v"], table, lengths)
    kw = dict(layer=jnp.asarray(1, jnp.int32),
              k_scale=kv.get("k_scale"), v_scale=kv.get("v_scale"),
              rope_cos=jnp.asarray(rs.randn(b, d), jnp.bfloat16),
              rope_sin=jnp.asarray(rs.randn(b, d), jnp.bfloat16))
    _dispatch.set_use_pallas(True)
    try:
        got = paged_decode_attention(*args, **kw)
        assert _dispatch.last_paths()["paged_decode_attention"] == "pallas"
    finally:
        _dispatch.set_use_pallas(None)
    want = paged_decode_attention_reference(*args, **kw)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=2e-2, rtol=2e-2,
    )
    assert not np.asarray(got[3], np.float32).any()
