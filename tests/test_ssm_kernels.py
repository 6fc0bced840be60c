"""The Mamba-2 state-space ops (``apex_tpu.ops.ssm``) and the grouped-query
head map of the paged decode kernel: each Pallas kernel in interpret mode
against its jnp form, and both forms against the token-by-token recurrence.

Tolerances: everything is f32.  The chunked (SSD) form differs from the
recurrence in the order of its sums: 1e-5 of the values' scale.  The decode
kernel keeps ~16 mantissa bits of its operands (exact bf16 parts through the
matmul unit): 1e-4 of the scale.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import _dispatch, ssm
from apex_tpu.ops.paged_attention import (
    gather_history, paged_decode_attention,
    paged_decode_attention_reference,
)
from apex_tpu.ops.pallas import decode_attention
from apex_tpu.serve import cache as cache_lib


@pytest.fixture
def pallas():
    _dispatch.set_use_pallas(True)
    yield
    _dispatch.set_use_pallas(None)


def inputs(rows, h, p, g, n, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (rows, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(k[1], (rows, h)) - 2.0)
    a = -jax.random.uniform(k[2], (h,), jnp.float32, 1.0, 16.0)
    b = jax.random.normal(k[3], (rows, g, n), jnp.float32)
    c = jax.random.normal(k[4], (rows, g, n), jnp.float32)
    return x, dt, a, b, c


def close(got, want, rel):
    np.testing.assert_allclose(
        got, want, atol=rel * max(1.0, float(jnp.abs(want).max())))


# -- the chunked prompt form against the recurrence ---------------------------


@pytest.mark.parametrize("length", [1, 5, 16, 17, 40, 63, 64])
@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "pallas"])
def test_ssd_chunked_matches_recurrence_at_any_length(length, kernel):
    """A prompt of ``length`` rows in a bucket of 64, chunks of 16: the
    padding rows take no step (``dt = 0``), so outputs and final state are
    those of the recurrence over the true length — for lengths that are not
    multiples of the chunk too."""
    bucket, h, p, g, n = 64, 4, 8, 2, 16
    x, dt, a, b, c = inputs(bucket, h, p, g, n, seed=length)
    dt = jnp.where(jnp.arange(bucket)[:, None] < length, dt, 0.0)
    want_y, want_s = ssm.ssm_recurrent(
        x[:length], dt[:length], a, b[:length], c[:length])
    _dispatch.set_use_pallas(kernel)
    try:
        y, st = ssm.ssd_chunked(x, dt, a, b, c, chunk=16)
    finally:
        _dispatch.set_use_pallas(None)
    assert _dispatch.last_paths()["ssd_chunk"] == (
        "pallas" if kernel else "jnp")
    close(y[:length], want_y, 1e-5)
    close(st, want_s, 1e-5)


def test_ssd_chunk_kernel_matches_its_jnp_form(pallas):
    from apex_tpu.ops.pallas.ssm import ssd_chunk_fwd

    x, dt, a, b, c = inputs(96, 8, 16, 2, 32, seed=3)
    _, cd, own, gam = ssm._intra_chunk(x, dt, a, b, c, 32)
    y0, s0 = ssm._chunk_scan(cd, own, gam)
    y1, s1 = ssd_chunk_fwd(cd, own, gam)
    close(y1, y0, 1e-5)
    close(s1, s0, 1e-5)


def test_ssd_refuses_a_ragged_sequence():
    x, dt, a, b, c = inputs(40, 4, 8, 2, 16)
    with pytest.raises(ValueError, match="whole chunks"):
        ssm.ssd_chunked(x, dt, a, b, c, chunk=16)


def test_heads_split_over_the_groups_in_order():
    """Heads 0..H/G-1 read group 0's B and C, the rest group 1's."""
    x, dt, a, b, c = inputs(12, 4, 8, 2, 16, seed=5)
    y, _ = ssm.ssm_recurrent(x, dt, a, b, c)
    for grp in range(2):
        sel = slice(2 * grp, 2 * grp + 2)
        y_g, _ = ssm.ssm_recurrent(
            x[:, sel], dt[:, sel], a[sel], b[:, grp:grp + 1],
            c[:, grp:grp + 1])
        close(y[:, sel], y_g, 1e-6)


# -- the decode step against the slab -----------------------------------------


@pytest.mark.parametrize("h,p,g,n", [(4, 8, 2, 16), (8, 128, 2, 256)],
                         ids=["tiny", "falcon-h1-head"])
@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "pallas"])
def test_ssm_step_advances_each_slot_in_place(h, p, g, n, kernel):
    slots = 3
    x, dt, a, b, c = inputs(slots, h, p, g, n, seed=7)
    dt = dt.at[1].set(0.0)                       # an idle row
    slab = jax.random.normal(
        jax.random.PRNGKey(1), (2, slots, h, p, n), jnp.float32)
    _dispatch.set_use_pallas(kernel)
    try:
        y, out = ssm.ssm_step(slab, 1, x, dt, a, b, c)
    finally:
        _dispatch.set_use_pallas(None)
    for i in range(slots):
        want_y, want_s = ssm.ssm_recurrent(
            x[i:i + 1], dt[i:i + 1], a, b[i:i + 1], c[i:i + 1], slab[1, i])
        close(y[i], want_y[0], 1e-4)
        close(out[1, i], want_s, 1e-4)
    # the idle row's state is bit for bit what it was; so is the other layer
    np.testing.assert_array_equal(out[1, 1], slab[1, 1])
    np.testing.assert_array_equal(out[0], slab[0])


def test_bf16_state_would_drift():
    """What the f32 slab is there for: a state rounded to bf16 after every
    token leaves the recurrence's output by far more than the tolerances
    above within a few hundred tokens."""
    x, dt, a, b, c = inputs(256, 4, 8, 2, 16, seed=9)
    dt = dt * 0.05                               # long memories
    want, _ = ssm.ssm_recurrent(x, dt, a, b, c)
    state, rows = None, []
    for t in range(256):
        y, state = ssm.ssm_recurrent(
            x[t:t + 1], dt[t:t + 1], a, b[t:t + 1], c[t:t + 1], state)
        state = state.astype(jnp.bfloat16).astype(jnp.float32)
        rows.append(y[0])
    err = float(jnp.abs(jnp.stack(rows) - want).max())
    assert err > 100 * 1e-5 * float(jnp.abs(want).max())


# -- grouped-query heads in the paged decode kernel ---------------------------


@pytest.mark.parametrize("heads,kv,d", [
    (10, 2, 16),      # 5 query heads a KV head, a row padded to its tile
    (20, 4, 128),     # Falcon-H1's
    (8, 4, 64),       # two KV heads side by side in a lane row
    (6, 1, 32),       # multi-query
], ids=["10q2kv-d16", "20q4kv-d128", "8q4kv-d64", "6q1kv-d32"])
def test_paged_decode_maps_query_heads_onto_kv_heads(pallas, heads, kv, d):
    slots, layers, pages, page, np_ = 3, 2, 12, 4, 3
    k = jax.random.split(jax.random.PRNGKey(heads), 3)
    pool = cache_lib.init_kv_pages(layers, pages, kv, page, d,
                                   dtype=jnp.float32)
    assert kv % pool["k"].shape[2] == 0     # the pool is laid at KV heads
    pool = {n: jax.random.normal(k[i], v.shape, jnp.float32)
            for i, (n, v) in enumerate(pool.items())}
    q = jax.random.normal(k[2], (slots, heads, d), jnp.float32)
    table = jnp.array([[1, 2, 3], [4, 5, 0], [0, 0, 0]], jnp.int32)
    lengths = jnp.array([11, 5, 0], jnp.int32)
    args = (q, pool["k"], pool["v"], table, lengths)
    got = paged_decode_attention(*args, layer=1, kv_heads=kv)
    assert _dispatch.last_paths()["paged_decode_attention"] == "pallas"
    want = paged_decode_attention_reference(*args, layer=1, kv_heads=kv)
    np.testing.assert_allclose(got, want, atol=2e-6)
    # the map itself, by hand: query head i reads KV head i // (H / kv)
    keys = gather_history(pool["k"], None, 1, table, kv, d)
    vals = gather_history(pool["v"], None, 1, table, kv, d)
    for i in (0, heads - 1):
        j = i // (heads // kv)
        s = jnp.einsum("d,td->t", q[0, i], keys[0, j]) * d ** -0.5
        s = jnp.where(jnp.arange(s.shape[0]) < 11, s, -1e30)
        np.testing.assert_allclose(
            got[0, i], jax.nn.softmax(s) @ vals[0, j], atol=2e-6)
    assert not np.asarray(got[2]).any()          # the idle slot: zeros


def test_kv_heads_equal_to_heads_is_the_plain_call(pallas):
    """``kv_heads == num_heads`` (or None) takes the kernel exactly as it
    was: same operands, same layout, same result."""
    slots, heads, d = 2, 4, 64
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    pool = cache_lib.init_kv_pages(1, 9, heads, 4, d, dtype=jnp.float32)
    pool = {n: jax.random.normal(k[i], v.shape, jnp.float32)
            for i, (n, v) in enumerate(pool.items())}
    q = jax.random.normal(k[2], (slots, heads, d), jnp.float32)
    args = (q, pool["k"], pool["v"], jnp.array([[1, 2], [3, 0]], jnp.int32),
            jnp.array([7, 3], jnp.int32))
    plain = paged_decode_attention(*args, layer=0)
    same = paged_decode_attention(*args, layer=0, kv_heads=heads)
    np.testing.assert_array_equal(plain, same)
    jaxprs = [
        str(jax.make_jaxpr(lambda *a: decode_attention.paged_decode_fwd(
            *a, jnp.int32(0), scale=0.125, **kw))(*args))
        for kw in ({}, {"kv_heads": None})
    ]
    assert jaxprs[0] == jaxprs[1]


def test_paged_decode_refuses_what_it_cannot_map():
    pool = cache_lib.init_kv_pages(1, 5, 4, 4, 64, dtype=jnp.float32)
    q = jnp.zeros((1, 6, 64))
    with pytest.raises(ValueError, match="do not hold"):
        decode_attention.paged_decode_fwd(
            q, pool["k"], pool["v"], jnp.zeros((1, 2), jnp.int32),
            jnp.ones((1,), jnp.int32), jnp.int32(0), scale=1.0, kv_heads=4)
    int8 = cache_lib.init_kv_pages(1, 5, 2, 4, 128, kv_wire="int8")
    with pytest.raises(ValueError, match="grouped-query"):
        decode_attention.paged_decode_fwd(
            jnp.zeros((1, 4, 128)), int8["k"], int8["v"],
            jnp.zeros((1, 2), jnp.int32), jnp.ones((1,), jnp.int32),
            jnp.int32(0), scale=1.0, kv_heads=2,
            k_scale=int8["k_scale"], v_scale=int8["v_scale"])


def test_kernel_specs_describe_the_grouped_query_walk():
    (spec,) = decode_attention.kernel_specs(
        4, 20, 128, pool_pages=41, page=16, pages_per_seq=10, kv_heads=4,
        rope=False)
    plan = decode_attention._decode_plan(
        4, 20, 128, 1, 41, 16, 10, jnp.bfloat16, jnp.bfloat16,
        groups=1, has_scales=False, has_rope=False, rep=5)
    # five query rows a KV head, four KV heads a page row block
    assert plan["in_shapes"][0] == (4, 5, 4, 128)
    assert plan["in_shapes"][1] == (1, 41, 4, 16, 128)
    assert plan["out_shape"][0].shape == (4, 5, 4, 128)
    assert spec.meta["pages_per_step"] == 8
