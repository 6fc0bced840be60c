"""The hybrid stack's kernels (interpret mode) against their jnp forms and
against plain oracles: KDA chunked and single-step against the per-token
recurrence, the grouped expert matmul against a dense masked sum, absorbed
MLA decode against un-absorbed attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import _dispatch, kda, mla
from apex_tpu.ops.moe_grouped import grouped_swiglu
from apex_tpu.transformer.moe import dropless_moe, route_group_limited


@pytest.fixture(params=[False, True], ids=["jnp", "pallas"])
def path(request):
    _dispatch.set_use_pallas(request.param)
    yield request.param
    _dispatch.set_use_pallas(None)


def kda_inputs(rs, s, h=4, d=16, floor=True):
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    q, k = (jnp.asarray(unit(rs.randn(s, h, d)), jnp.float32) for _ in "qk")
    v = jnp.asarray(rs.randn(s, h, d), jnp.float32)
    # decays from "remembers everything" to the floor of -5 a row, where
    # e^{-G} alone would overflow f32 inside one 64-row chunk
    shift = rs.uniform(-6, 6 if floor else 0, size=(1, h, d))
    g = -5 * jax.nn.sigmoid(jnp.asarray(rs.randn(s, h, d) + shift,
                                        jnp.float32))
    beta = jax.nn.sigmoid(jnp.asarray(rs.randn(s, h), jnp.float32))
    return q, k, v, g, beta


@pytest.mark.parametrize("s,chunk", [(192, 64), (64, 64), (32, 64), (8, 64),
                                     (128, 32)])
def test_kda_chunked_matches_the_recurrence(path, s, chunk):
    """Several chunks, one chunk, a chunk shorter than the default and one
    shorter than a sub-block; f32 sums in another order: 2e-5 on outputs of
    O(1)."""
    q, k, v, g, beta = kda_inputs(np.random.RandomState(s), s)
    want_o, want_s = kda.kda_recurrent(q, k, v, g, beta)
    o, st = kda.kda_chunked(q, k, v, g, beta, chunk=chunk)
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    np.testing.assert_allclose(st, want_s, atol=2e-5)
    assert _dispatch.last_paths()["kda_chunk"] == ("pallas" if path else "jnp")


def test_kda_padding_rows_are_identities(path):
    """beta = 0, g = 0 past the true length: state and live outputs are the
    unpadded sequence's, bit for bit in the chunks before the padding."""
    q, k, v, g, beta = kda_inputs(np.random.RandomState(3), 128)
    n = 83
    live = (jnp.arange(128) < n)
    o, st = kda.kda_chunked(q, k, v, jnp.where(live[:, None, None], g, 0.0),
                            jnp.where(live[:, None], beta, 0.0))
    want_o, want_s = kda.kda_recurrent(q[:n], k[:n], v[:n], g[:n], beta[:n])
    np.testing.assert_allclose(o[:n], want_o, atol=2e-5)
    np.testing.assert_allclose(st, want_s, atol=2e-5)


def test_kda_step_matches_the_recurrence_and_keeps_idle_rows(path):
    rs = np.random.RandomState(5)
    layers, b, h, d = 3, 5, 4, 16
    slab = jnp.asarray(rs.randn(layers, b, h, d, d), jnp.float32)
    q, k, v, g, beta = kda_inputs(rs, b)
    idle = jnp.arange(b) == 2
    g = jnp.where(idle[:, None, None], 0.0, g)
    beta = jnp.where(idle[:, None], 0.0, beta)
    o, out = kda.kda_step(slab, 1, q, k, v, g, beta)
    for i in range(b):
        want_o, want_s = kda.kda_recurrent(
            q[i:i + 1], k[i:i + 1], v[i:i + 1], g[i:i + 1], beta[i:i + 1],
            slab[1, i])
        np.testing.assert_allclose(o[i], want_o[0], atol=1e-5)
        np.testing.assert_allclose(out[1, i], want_s, atol=1e-5)
    np.testing.assert_array_equal(out[1, 2], slab[1, 2])    # idle: untouched
    np.testing.assert_array_equal(out[0], slab[0])
    np.testing.assert_array_equal(out[2], slab[2])


def experts(rs, e, h, i):
    return dict(
        gate=jnp.asarray(rs.randn(e, h, i) * 0.1, jnp.float32),
        up=jnp.asarray(rs.randn(e, h, i) * 0.1, jnp.float32),
        down=jnp.asarray(rs.randn(e, i, h) * 0.1, jnp.float32),
    )


def test_grouped_matmul_with_empty_experts_and_skipped_tiles(path):
    rs = np.random.RandomState(7)
    e, h, i, tile = 6, 32, 16, 8
    w = experts(rs, e, h, i)
    x = jnp.asarray(rs.randn(6 * tile, h), jnp.float32)
    # experts 0 and 3 get two tiles and one; 1, 2, 4, 5 none; the last
    # three tiles are past the live count (their expert repeats the last)
    tile_expert = jnp.asarray([0, 0, 3, 3, 3, 3], jnp.int32)
    y = grouped_swiglu(x, tile_expert, jnp.int32(3), w["gate"], w["up"],
                       w["down"], tile=tile)
    for t, ex in enumerate([0, 0, 3]):
        rows = x[t * tile:(t + 1) * tile]
        a, b = rows @ w["gate"][ex], rows @ w["up"][ex]
        want = (a * jax.nn.sigmoid(a) * b) @ w["down"][ex]
        np.testing.assert_allclose(
            y[t * tile:(t + 1) * tile], want, atol=1e-5)


@pytest.mark.parametrize("held", [(0, 4), (8, 4), (0, 16)])
def test_dropless_layer_against_dense_masked_experts(path, held):
    """Top-4 of 16 in 4 groups; this chip's share against every held
    expert applied to every token and masked by the routing weight."""
    rs = np.random.RandomState(9)
    t, h, i, e = 41, 32, 16, 16
    w = experts(rs, e, h, i)
    x = jnp.asarray(rs.randn(t, h), jnp.float32)
    router = jnp.asarray(rs.randn(h, e) / 6, jnp.float32)
    idx, wt = route_group_limited(
        x, router, jnp.zeros((e,)), top_k=4, n_group=4, topk_group=2,
        scale=2.5)
    assert idx.shape == (t, 4)
    np.testing.assert_allclose(wt.sum(-1), 2.5, rtol=1e-5)
    # group-limited: a token's experts lie in at most 2 of the 4 groups
    assert max(len(set(r // 4)) for r in np.asarray(idx)) <= 2
    lo, n = held
    live = jnp.arange(t) != 7
    out, stats = dropless_moe(
        x, idx, wt, {k: v[lo:lo + n] for k, v in w.items()}, held=held,
        live=live, tile=8)
    dense = jnp.zeros((t, e)).at[jnp.arange(t)[:, None], idx].set(wt)
    want = jnp.zeros((t, h))
    for ex in range(lo, lo + n):
        a, b = x @ w["gate"][ex], x @ w["up"][ex]
        want += dense[:, ex:ex + 1] * ((a * jax.nn.sigmoid(a) * b)
                                       @ w["down"][ex])
    want = jnp.where(live[:, None], want, 0.0)
    np.testing.assert_allclose(out, want, atol=1e-5)
    here = (np.asarray(idx) >= lo) & (np.asarray(idx) < lo + n) \
        & np.asarray(live)[:, None]
    assert int(stats[0]) == here.sum()
    assert int(stats[1]) == len(set(np.asarray(idx)[here]))


def test_mla_absorbed_decode_matches_unabsorbed_attention(path):
    """Absorbed scores over the cached rows [c | k_r | 0] against per-head
    keys and values rebuilt from the latent; bf16 pages and probabilities:
    2e-2 on contexts of O(1)."""
    rs = np.random.RandomState(11)
    b, n, r, dr, dn, dv, page, np_ = 3, 4, 32, 8, 16, 16, 8, 16
    w = mla.latent_row_width(r, dr)
    assert w == 128 and mla.latent_row_width(512, 64) == 640
    pool = np.zeros((2, 40, 1, page, w), np.float32)
    pool[..., : r + dr] = rs.randn(2, 40, 1, page, r + dr)
    pool = jnp.asarray(pool, jnp.bfloat16)
    w_b = jnp.asarray(rs.randn(r, n, dn + dv) / 6, jnp.float32)
    q = jnp.asarray(rs.randn(b, n, dn + dr), jnp.float32)
    lengths = np.array([37, 0, 128], np.int32)
    table = np.zeros((b, np_), np.int32)
    for i, length in enumerate(lengths):
        k = -(-length // page)
        table[i, :k] = rs.choice(np.arange(1, 40), k, replace=False)
    scale = (dn + dr) ** -0.5
    q_abs = jnp.einsum("bnd,rnd->bnr", q[..., :dn], w_b[..., :dn])
    q_row = jnp.concatenate(
        [q_abs, q[..., dn:], jnp.zeros((b, n, w - r - dr))], -1)
    ctx = mla.mla_decode_attention(
        q_row, pool, jnp.asarray(table), jnp.asarray(lengths), layer=1,
        scale=scale)
    got = jnp.einsum("bnr,rnd->bnd", ctx[..., :r], w_b[..., dn:])
    rows = np.asarray(pool[1].astype(jnp.float32))
    for i, length in enumerate(lengths):
        if not length:
            assert not np.asarray(got[i]).any()      # idle: zeros
            continue
        lat = rows[table[i]][:, 0].reshape(-1, w)[:length]
        c, k_r = lat[:, :r], lat[:, r:r + dr]
        kv = np.einsum("tr,rnd->tnd", c, np.asarray(w_b))
        s = (np.einsum("nd,tnd->nt", np.asarray(q[i, :, :dn]), kv[..., :dn])
             + np.asarray(q[i, :, dn:]) @ k_r.T) * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = np.einsum("nt,tnd->nd", p, kv[..., dn:])
        np.testing.assert_allclose(got[i], want, atol=2e-2)


@pytest.mark.parametrize("offset,c", [(0, 32), (40, 32), (96, 16), (120, 64)])
def test_mla_absorbed_chunk_matches_unabsorbed_attention(path, offset, c):
    """A prefill chunk of ``c`` queries at ``offset``: query t over rows 0
    .. offset + t, the chunk's own rows already in the pages, against
    per-head keys and values rebuilt from the latent with a causal mask;
    bf16 pages and probabilities: 2e-2 on contexts of O(1).  Offsets at
    zero, mid-page, on a page and past several query blocks."""
    rs = np.random.RandomState(offset + c)
    n, r, dr, dn, dv, page, np_ = 4, 32, 8, 16, 16, 8, 24
    w = mla.latent_row_width(r, dr)
    pool = np.zeros((2, 40, 1, page, w), np.float32)
    pool[..., : r + dr] = rs.randn(2, 40, 1, page, r + dr)
    pool = jnp.asarray(pool, jnp.bfloat16)
    w_b = jnp.asarray(rs.randn(r, n, dn + dv) / 6, jnp.float32)
    q = jnp.asarray(rs.randn(c, n, dn + dr), jnp.float32)
    live = -(-(offset + c) // page)
    table = np.zeros((np_,), np.int32)
    table[:live] = rs.choice(np.arange(1, 40), live, replace=False)
    scale = (dn + dr) ** -0.5
    q_abs = jnp.einsum("cnd,rnd->cnr", q[..., :dn], w_b[..., :dn])
    q_row = jnp.concatenate(
        [q_abs, q[..., dn:], jnp.zeros((c, n, w - r - dr))], -1)
    ctx = mla.mla_chunk_attention(
        q_row, pool, jnp.asarray(table), jnp.int32(offset), layer=1,
        scale=scale)
    assert _dispatch.last_paths()["mla_chunk"] == (
        "pallas" if path else "jnp")
    got = np.asarray(jnp.einsum("cnr,rnd->cnd", ctx[..., :r], w_b[..., dn:]))
    lat = np.asarray(pool[1].astype(jnp.float32))[table][:, 0].reshape(-1, w)
    lat = lat[: offset + c]
    c_, k_r = lat[:, :r], lat[:, r:r + dr]
    kv = np.einsum("tr,rnd->tnd", c_, np.asarray(w_b))
    s = (np.einsum("cnd,tnd->cnt", np.asarray(q[..., :dn]), kv[..., :dn])
         + np.einsum("cnd,td->cnt", np.asarray(q[..., dn:]), k_r)) * scale
    pos = np.arange(offset + c)
    s = np.where(pos[None, None, :] <= offset + np.arange(c)[:, None, None],
                 s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("cnt,tnd->cnd", p, kv[..., dn:])
    np.testing.assert_allclose(got, want, atol=2e-2)
