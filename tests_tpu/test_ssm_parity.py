"""The Mamba-2 kernels and the grouped-query paged decode walk, compiled by
Mosaic on the chip, against their jnp forms at Falcon-H1's widths (32 heads
of 128 channels, state 256, 2 groups; 20 query heads on 4 KV heads of 128).

Tolerances: the state-space kernels keep ~16 mantissa bits of an f32 state
(operands split into exact bf16 parts) or multiply at HIGHEST: 1e-4 of the
values' scale.  The paged walk on bf16 pages agrees with the gathered
reference to bf16 rounding of the output (2e-2 on values O(1))."""

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.ops import _dispatch, ssm
from apex_tpu.ops.paged_attention import (
    paged_decode_attention, paged_decode_attention_reference,
)
from apex_tpu.serve import cache as cache_lib

H, P, G, N = 32, 128, 2, 256


def _inputs(rows, seed):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (rows, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(k[1], (rows, H)) - 3.0)
    a = -jax.random.uniform(k[2], (H,), jnp.float32, 1.0, 16.0)
    b = jax.random.normal(k[3], (rows, G, N), jnp.float32)
    c = jax.random.normal(k[4], (rows, G, N), jnp.float32)
    return x, dt, a, b, c


def _both(fn):
    out = []
    for force in (False, True):
        _dispatch.set_use_pallas(force)
        try:
            out.append(jax.block_until_ready(fn()))
        finally:
            _dispatch.set_use_pallas(None)
    return out


def test_ssm_step_on_chip():
    slots = 8
    x, dt, a, b, c = _inputs(slots, 0)
    dt = dt.at[3].set(0.0)               # an idle row
    slab = jax.random.normal(
        jax.random.PRNGKey(9), (2, slots, H, P, N), jnp.float32)
    (y0, s0), (y1, s1) = _both(
        lambda: ssm.ssm_step(slab, 1, x, dt, a, b, c))
    assert _dispatch.last_paths()["ssm_step"] == "pallas"
    scale = float(jnp.abs(y0).max())
    np.testing.assert_allclose(y1, y0, atol=1e-4 * scale)
    np.testing.assert_allclose(s1, s0, atol=1e-4 * float(jnp.abs(s0).max()))
    np.testing.assert_array_equal(s1[1, 3], slab[1, 3])   # idle: untouched
    np.testing.assert_array_equal(s1[0], slab[0])         # the other layer


def test_ssd_chunked_on_chip():
    s = 512
    x, dt, a, b, c = _inputs(s, 1)
    dt = jnp.where(jnp.arange(s)[:, None] < 300, dt, 0.0)  # bucket padding
    want_y, want_s = jax.jit(ssm.ssm_recurrent)(
        x[:300], dt[:300], a, b[:300], c[:300])
    _dispatch.set_use_pallas(True)
    try:
        y, st = jax.block_until_ready(
            jax.jit(ssm.ssd_chunked)(x, dt, a, b, c))
    finally:
        _dispatch.set_use_pallas(None)
    assert _dispatch.last_paths()["ssd_chunk"] == "pallas"
    scale = float(jnp.abs(want_y).max())
    np.testing.assert_allclose(y[:300], want_y, atol=1e-4 * scale)
    np.testing.assert_allclose(
        st, want_s, atol=1e-4 * float(jnp.abs(want_s).max()))


def test_gqa_paged_decode_on_chip():
    slots, heads, kv, d, page, np_ = 16, 20, 4, 128, 16, 80
    k = jax.random.split(jax.random.PRNGKey(2), 4)
    pool = cache_lib.init_kv_pages(2, 16 * np_ + 1, kv, page, d)
    pool = {n: jax.random.normal(k[i], v.shape, jnp.float32).astype(v.dtype)
            for i, (n, v) in enumerate(pool.items())}
    q = jax.random.normal(k[2], (slots, heads, d), jnp.float32).astype(
        jnp.bfloat16)
    rs = np.random.RandomState(0)
    lengths = rs.randint(1, page * np_, size=slots).astype(np.int32)
    lengths[5] = 0
    table = (1 + rs.permutation(16 * np_)[: slots * np_]).reshape(
        slots, np_).astype(np.int32)
    args = (q, pool["k"], pool["v"], jnp.asarray(table), jnp.asarray(lengths))
    want = paged_decode_attention_reference(*args, layer=1, kv_heads=kv)
    got = jax.block_until_ready(
        paged_decode_attention(*args, layer=1, kv_heads=kv))
    assert _dispatch.last_paths()["paged_decode_attention"] == "pallas"
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=2e-2)
    assert not np.asarray(got[5], np.float32).any()
