"""Pallas TPU kernel: ELL neighbor-block histogram.

counts[b, i] = Σ_w nbr_w[b, w] · [nbr_blk[b, w] == i]

This is the inner op of every assignment decision in the system (Fennel
gains, ANR updates, LP refinement) — the compute hot spot the paper's batch
assignment spends its time in. The CPU implementation is a scatter; on TPU
it is a compare-and-accumulate over a (KC, TB) tile: node rows on the 128
lanes, label columns on the sublanes, one neighbor column per step.

Layout: the kernel takes the tiles transposed, (W, B), so neighbor column
j of a node tile is the sublane row `ref[j]` (a ref slice the TPU loads
directly — slicing a loaded value at a dynamic offset does not lower) and
the histogram comes out (K, B).  Grid: (node tiles of TB) × (label tiles
of KC) × (neighbor chunks of WC); the W axis is the last, sequential grid
axis and the output tile stays resident in VMEM across it (one HBM write
per tile).  The K axis is tiled because the host `ell` engine calls this
with k in the thousands; the accumulator is KC·TB·4 B = 64 KiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_TB = 128  # node rows per tile (lane width)
MAX_WC = 128      # neighbor columns per grid step (sublane multiple)
KC = 128          # label columns per grid tile (sublane multiple)


def pick_wc(w: int) -> int:
    """Neighbor chunk for a width already padded by ops.py (a multiple of
    8, and of MAX_WC above it)."""
    return w if w <= MAX_WC else MAX_WC


def _histogram_kernel(blk_ref, w_ref, out_ref, *, kc: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    wc, tb = blk_ref.shape
    ids = pl.program_id(1) * kc + jax.lax.broadcasted_iota(jnp.int32, (kc, tb), 0)

    def body(j, acc):
        blk = blk_ref[pl.ds(j, 1), :]  # (1, tb): neighbor column j
        wts = w_ref[pl.ds(j, 1), :]
        return acc + jnp.where(blk == ids, wts, 0.0)

    # int32 bounds: the jax engine calls this under x64, and an int64
    # loop index would reach the TPU lowering
    out_ref[...] = jax.lax.fori_loop(jnp.int32(0), jnp.int32(wc), body,
                                     out_ref[...])


def ell_histogram(
    nbr_blk_t: jnp.ndarray,
    nbr_w_t: jnp.ndarray,
    k: int,
    *,
    tb: int = DEFAULT_TB,
    interpret: bool = True,
) -> jnp.ndarray:
    """counts (k, B) float32 from transposed (W, B) tiles.  The caller pads
    B to a tb multiple, W per `pick_wc` and k to a KC multiple (ops.py)."""
    w, b = nbr_blk_t.shape
    wc = pick_wc(w)
    assert b % tb == 0 and w % wc == 0 and w % 8 == 0 and k % KC == 0, (
        b, w, k, tb, wc)
    kernel = functools.partial(_histogram_kernel, kc=KC)
    return pl.pallas_call(
        kernel,
        grid=(b // tb, k // KC, w // wc),
        in_specs=[
            pl.BlockSpec((wc, tb), lambda i, j, s: (s, i)),
            pl.BlockSpec((wc, tb), lambda i, j, s: (s, i)),
        ],
        out_specs=pl.BlockSpec((KC, tb), lambda i, j, s: (j, i)),
        out_shape=jax.ShapeDtypeStruct((k, b), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ell_histogram",
    )(nbr_blk_t, nbr_w_t)
