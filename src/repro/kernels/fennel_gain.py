"""Fennel gain + argmax — every engine of the one scoring rule.

The decision `argmax_i  w(N(v) ∩ V_i) − α·γ·load_i^(γ−1)` (feasibility-
masked, first-max tie-break, argmin(loads) fallback) appears three times
in this repo, and all three live here so they can be pinned against each
other:

* `_fennel_kernel` / `fennel_gain` — the Pallas TPU kernel: fuses the ELL
  histogram with the penalty, feasibility mask and block argmax so the
  (B, k) counts tile never round-trips to HBM.  Wavefront semantics (all
  nodes in a tile see the same loads) — the vectorized driver's engine via
  kernels/ops.py::fennel_choose_batch, which falls back to
  kernels/ref.py::fennel_gain_ref off-TPU.
* `fennel_gain_sequential` — the host CPU engine: the same scoring math as
  a scalar python loop over CSR adjacency, *sequential* semantics (each
  step sees the previous placements).  This is the initial-partition inner
  loop of core/multilevel.py, where batches are ~128 nodes and k is small:
  per-step numpy dispatch costs more than the arithmetic, so the scalar
  loop is ~5x faster on host and — unlike a wavefront engine — is exactly
  the sequential oracle.  Bit-identical to the vectorized per-step loop it
  replaced (see the float contract in the function docstring), pinned by
  tests/test_multilevel.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ell_histogram import DEFAULT_TB, pick_wc

_NEG_INF = -1e30


def _pow_scalar(g1: float):
    """Scalar twin of the `np.power(m, g1)` array loop: numpy special-cases
    exponents 2.0 (x*x), 0.5 (sqrt) and -1.0 (1/x) in its broadcast loop,
    so the scalar path must take the same fast paths to stay bit-identical;
    every other exponent matches scalar np.power exactly."""
    if g1 == 2.0:
        return lambda m: m * m
    if g1 == 0.5:
        return math.sqrt
    if g1 == -1.0:
        return lambda m: 1.0 / m
    return lambda m: float(np.power(m, g1))


def fennel_gain_sequential(
    indptr: np.ndarray,
    indices: np.ndarray,
    edge_w: np.ndarray,
    node_w: np.ndarray,
    order: np.ndarray,
    labels: np.ndarray,
    loads: np.ndarray,
    *,
    alpha: float,
    gamma: float,
    cap: float,
    k: int,
) -> None:
    """Sequential Fennel sweep over `order`, mutating labels/loads in place.

    Bit-identity contract with the vectorized per-step loop this replaced
    (ell gather + np.bincount + fennel_penalty + np.argmax per step):
    connectivity accumulates float64 left-to-right in CSR adjacency order
    (== np.bincount's input-order adds, f32 weights cast exactly); the
    penalty is (alpha*gamma) * m**(gamma-1) with numpy's pow fast paths
    (`_pow_scalar`); feasible scores compare with strict `>` (first-max ==
    np.argmax); the all-infeasible fallback is first-min of loads (==
    np.argmin).  Scores never materialize for infeasible blocks — they were
    -inf under the mask and can't win argmax anyway.
    """
    ag = float(alpha) * float(gamma)
    powf = _pow_scalar(float(gamma) - 1.0)
    cap = float(cap)
    loads_l = loads.tolist()
    labels_l = labels.tolist()
    conn = [0.0] * k
    ip = indptr.tolist()
    # f8/f4 -> python float via tolist is value-exact (f4 widens losslessly)
    idx = indices.tolist()
    ew = edge_w.tolist()
    nws = node_w.tolist()
    rng = range(k)
    for v in order.tolist():
        for i in rng:
            conn[i] = 0.0
        for j in range(ip[v], ip[v + 1]):
            b = labels_l[idx[j]]
            if b >= 0:
                conn[b] += ew[j]
        nw = nws[v]
        best_i = -1
        best_s = -math.inf
        for i in rng:
            li = loads_l[i]
            if li + nw > cap:
                continue
            m = li if li > 0.0 else 0.0
            s = conn[i] - ag * powf(m)
            if s > best_s:
                best_s = s
                best_i = i
        if best_i < 0:
            best_i = loads_l.index(min(loads_l))
        labels_l[v] = best_i
        loads_l[best_i] = loads_l[best_i] + nw
    labels[:] = labels_l
    loads[:] = loads_l


def _fennel_kernel(
    blk_ref, w_ref, loads_ref, penalty_ref, node_w_ref, best_ref, score_ref,
    acc_ref, *, cap: float,
):
    k, tb = acc_ref.shape

    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ids = jax.lax.broadcasted_iota(jnp.int32, (k, tb), 0)

    def body(j, acc):
        blk = blk_ref[pl.ds(j, 1), :]  # (1, tb): neighbor column j
        wts = w_ref[pl.ds(j, 1), :]
        return acc + jnp.where(blk == ids, wts, 0.0)

    acc_ref[...] = jax.lax.fori_loop(jnp.int32(0), jnp.int32(blk_ref.shape[0]),
                                     body, acc_ref[...])

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _decide():
        # f32 constants spelled out: under x64 a Python float would be an
        # f64 constant, and Mosaic cannot truncate one
        score = acc_ref[...] - penalty_ref[...]                   # (k, tb)
        feasible = (loads_ref[...] + node_w_ref[...]) <= jnp.float32(cap)
        masked = jnp.where(feasible, score, jnp.float32(_NEG_INF))
        best_s = jnp.max(masked, axis=0, keepdims=True)          # (1, tb)
        # first max == jnp.argmax's lowest-id tie-break
        best = jnp.min(jnp.where(masked == best_s, ids, jnp.int32(k)), axis=0,
                       keepdims=True)
        best_ref[...] = best
        score_ref[...] = best_s


# block index maps: int32 literals, because under x64 a bare 0 is an i64
# block index, which Mosaic cannot lower
def _first_block(i, s):
    return jnp.int32(0), jnp.int32(0)


def _row_block(i, s):
    return jnp.int32(0), i


def fennel_gain(
    nbr_blk_t: jnp.ndarray,
    nbr_w_t: jnp.ndarray,
    loads: jnp.ndarray,
    penalty: jnp.ndarray,
    node_w: jnp.ndarray,
    *,
    cap: float,
    tb: int = DEFAULT_TB,
    interpret: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(best block (B,), best masked score (B,)) from transposed (W, B)
    tiles; `penalty` is α·γ·load^(γ−1) per block.  Shapes pre-padded by
    ops.py.  Rows with no feasible block come back with score -1e30; the
    caller applies the least-loaded fallback."""
    w, b = nbr_blk_t.shape
    k = loads.shape[0]
    wc = pick_wc(w)
    assert b % tb == 0 and w % wc == 0 and w % 8 == 0 and k % 8 == 0
    kernel = functools.partial(_fennel_kernel, cap=float(cap))
    best, score = pl.pallas_call(
        kernel,
        grid=(b // tb, w // wc),
        in_specs=[
            pl.BlockSpec((wc, tb), lambda i, s: (s, i)),
            pl.BlockSpec((wc, tb), lambda i, s: (s, i)),
            pl.BlockSpec((k, 1), _first_block),
            pl.BlockSpec((k, 1), _first_block),
            pl.BlockSpec((1, tb), _row_block),
        ],
        out_specs=[
            pl.BlockSpec((1, tb), _row_block),
            pl.BlockSpec((1, tb), _row_block),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, b), jnp.int32),
            jax.ShapeDtypeStruct((1, b), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((k, tb), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="fennel_gain",
    )(nbr_blk_t, nbr_w_t, loads.reshape(k, 1), penalty.reshape(k, 1),
      node_w.reshape(1, b))
    return best[0], score[0]
