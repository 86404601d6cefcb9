"""Per-kernel allclose vs ref.py oracles: shape/dtype sweeps + hypothesis."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import block_histogram
from repro.kernels import ref

RNG = np.random.default_rng(0)


# ------------------------------------------------------------ histogram

@pytest.mark.parametrize("b,w,k", [(1, 1, 2), (7, 13, 4), (64, 32, 16),
                                   (130, 7, 32), (256, 8, 32), (256, 32, 32),  # mesh tiles
                                   (100, 64, 256),
                                   (256, 16, 33),   # k + 1 block slots
                                   (256, 32, 129),  # k one past a KC tile
                                   (512, 256, 32),  # widest level-0 tile: 2 WC chunks
                                   (64, 300, 64),   # width padded to 3 WC chunks
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
