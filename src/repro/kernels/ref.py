"""Pure-jnp oracle for the Pallas kernel (the allclose target)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ell_histogram_ref(nbr_blk: jnp.ndarray, nbr_w: jnp.ndarray, k: int) -> jnp.ndarray:
    """counts[b, i] = sum of nbr_w[b, :] where nbr_blk[b, :] == i.

    nbr_blk: (B, W) int32, -1 = padding (weight must be 0 there too).
    nbr_w:   (B, W) float32.
    """
    onehot = jax.nn.one_hot(nbr_blk, k, dtype=nbr_w.dtype)  # -1 rows are all-0
    return jnp.einsum("bw,bwk->bk", nbr_w, onehot)
