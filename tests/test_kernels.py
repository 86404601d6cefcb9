"""Per-kernel allclose vs ref.py oracles: shape/dtype sweeps + hypothesis."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import (
    block_histogram, fennel_choose_batch, embedding_bag, swa_attention_decode,
)
from repro.kernels import ref

RNG = np.random.default_rng(0)


# ------------------------------------------------------------ histogram

@pytest.mark.parametrize("b,w,k", [(1, 1, 2), (7, 13, 4), (64, 32, 16),
                                   (130, 7, 32), (256, 8, 32), (256, 32, 32),  # mesh tiles
                                   (100, 64, 256),
                                   (64, 16, 1000)])  # k > MAX_KC: 2-D grid
def test_histogram_shapes(b, w, k):
    blk = RNG.integers(-1, k, (b, w)).astype(np.int32)
    wts = (RNG.random((b, w)) * (blk >= 0)).astype(np.float32)
    out = block_histogram(jnp.asarray(blk), jnp.asarray(wts), k, use_kernel=True)
    want = ref.ell_histogram_ref(jnp.asarray(blk), jnp.asarray(wts), k)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-5)


@given(st.integers(1, 50), st.integers(1, 20), st.integers(2, 33),
       st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_histogram_property(b, w, k, seed):
    rng = np.random.default_rng(seed)
    blk = rng.integers(-1, k, (b, w)).astype(np.int32)
    wts = (rng.random((b, w)) * (blk >= 0)).astype(np.float32)
    out = np.asarray(block_histogram(jnp.asarray(blk), jnp.asarray(wts), k, use_kernel=True))
    # row sums equal the valid weight mass
    np.testing.assert_allclose(out.sum(1), (wts * (blk >= 0)).sum(1), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------- fennel gain

@pytest.mark.parametrize("b,w,k", [(4, 5, 3), (33, 17, 8), (128, 40, 64)])
def test_fennel_gain(b, w, k):
    blk = RNG.integers(-1, k, (b, w)).astype(np.int32)
    wts = (RNG.random((b, w)) * (blk >= 0)).astype(np.float32)
    loads = (RNG.random(k) * 10).astype(np.float32)
    node_w = np.ones(b, np.float32)
    args = (jnp.asarray(blk), jnp.asarray(wts), jnp.asarray(loads), jnp.asarray(node_w))
    kw = dict(alpha=0.4, gamma=1.5, cap=11.0)
    best_k, sc_k = fennel_choose_batch(*args, use_kernel=True, **kw)
    best_r, sc_r = ref.fennel_gain_ref(*args, **kw)
    np.testing.assert_array_equal(np.asarray(best_k), np.asarray(best_r))
    np.testing.assert_allclose(np.asarray(sc_k), np.asarray(sc_r), rtol=1e-4, atol=1e-4)


def test_fennel_gain_infeasible_fallback():
    """All blocks over cap -> least-loaded fallback (matches numpy driver)."""
    blk = np.zeros((8, 4), np.int32)
    wts = np.ones((8, 4), np.float32)
    loads = np.array([5.0, 3.0, 4.0], np.float32)
    node_w = np.ones(8, np.float32)
    best, _ = fennel_choose_batch(
        jnp.asarray(blk), jnp.asarray(wts), jnp.asarray(loads), jnp.asarray(node_w),
        alpha=0.1, gamma=1.5, cap=2.0, use_kernel=True,
    )
    assert (np.asarray(best) == 1).all()


def test_fennel_gain_matches_sequential_choice():
    """Kernel wavefront choice == core.fennel.fennel_choose per row when
    loads are frozen."""
    from repro.core.fennel import FennelParams, fennel_choose
    from repro.graphs import rmat_graph

    g = rmat_graph(64, 4, seed=5)
    k = 4
    block = np.arange(g.n) % k
    block[32:] = -1
    p = FennelParams(k=k, n_total=float(g.n), m_total=g.total_edge_weight(), eps=0.5)
    loads = np.bincount(block[block >= 0], minlength=k).astype(np.float64)
    nodes = np.arange(32, 48)
    nbr, wts, mask = g.ell_block(nodes)
    nbr_blk = np.where(mask, block[np.clip(nbr, 0, g.n - 1)], -1).astype(np.int32)
    best_k, _ = fennel_choose_batch(
        jnp.asarray(nbr_blk), jnp.asarray(wts), jnp.asarray(loads, dtype=np.float32),
        jnp.asarray(g.node_w[nodes]),
        alpha=p.alpha, gamma=p.gamma, cap=p.cap, use_kernel=True,
    )
    for i, v in enumerate(nodes):
        want = fennel_choose(
            g.neighbors(int(v)), g.neighbor_weights(int(v)),
            float(g.node_w[v]), block, loads, p,
        )
        assert int(best_k[i]) == want, (v, int(best_k[i]), want)


# -------------------------------------------------------- embedding bag

@pytest.mark.parametrize("v,d,b,l", [(16, 8, 4, 1), (64, 96, 32, 5),
                                     (128, 128, 16, 3), (32, 200, 8, 7)])
def test_embedding_bag(v, d, b, l):
    table = RNG.standard_normal((v, d)).astype(np.float32)
    idx = RNG.integers(0, v, (b, l)).astype(np.int32)
    mask = (RNG.random((b, l)) > 0.3).astype(np.float32)
    out = embedding_bag(jnp.asarray(table), jnp.asarray(idx), jnp.asarray(mask),
                        use_kernel=True)
    want = ref.embedding_bag_ref(jnp.asarray(table), jnp.asarray(idx), jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-5)


@given(st.integers(2, 40), st.integers(1, 6), st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_embedding_bag_property(v, l, seed):
    rng = np.random.default_rng(seed)
    d, b = 16, 8
    table = rng.standard_normal((v, d)).astype(np.float32)
    idx = rng.integers(0, v, (b, l)).astype(np.int32)
    mask = np.ones((b, l), np.float32)
    out = np.asarray(embedding_bag(jnp.asarray(table), jnp.asarray(idx),
                                   jnp.asarray(mask), use_kernel=True))
    want = table[idx].sum(1)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)


# ------------------------------------------------------- SWA attention

@pytest.mark.parametrize("dh,s,win,pos", [
    (64, 256, 64, (100, 200)), (80, 512, 128, (0, 512)),
    (128, 128, 256, (64, 127)),  # window larger than cache
])
def test_swa_decode(dh, s, win, pos):
    b, kvh, g = 2, 4, 3
    q = RNG.standard_normal((b, kvh, g, dh)).astype(np.float32)
    kc = RNG.standard_normal((b, s, kvh, dh)).astype(np.float32)
    vc = RNG.standard_normal((b, s, kvh, dh)).astype(np.float32)
    p = np.asarray(pos, np.int32)
    out = swa_attention_decode(jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
                               jnp.asarray(p), window=win, use_kernel=True)
    want = swa_attention_decode(jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
                                jnp.asarray(p), window=win, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=3e-4, atol=3e-4)


def test_swa_matches_full_attention_when_window_covers():
    """window >= pos: SWA == ordinary causal decode attention."""
    from repro.models.attention import decode_attention
    b, kvh, g, dh, s = 2, 2, 2, 32, 64
    q = RNG.standard_normal((b, kvh, g, dh)).astype(np.float32)
    kc = RNG.standard_normal((b, s, kvh, dh)).astype(np.float32)
    vc = RNG.standard_normal((b, s, kvh, dh)).astype(np.float32)
    pos = np.array([40, 64], np.int32)
    out = swa_attention_decode(jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
                               jnp.asarray(pos), window=s, use_kernel=True)
    qfull = jnp.asarray(q.reshape(b, 1, kvh * g, dh))
    want = decode_attention(qfull, jnp.asarray(kc), jnp.asarray(vc), jnp.asarray(pos))
    np.testing.assert_allclose(
        np.asarray(out).reshape(b, kvh * g, dh),
        np.asarray(want)[:, 0], rtol=3e-4, atol=3e-4,
    )
