"""Jit'd public wrapper around the Pallas histogram kernel.

The op pads its inputs to hardware-aligned tiles and dispatches to the
Pallas kernel: compiled where the default backend is a TPU, interpreted
elsewhere.  `use_kernel=None` picks the kernel on a TPU and the pure-jnp
reference (kernels/ref.py) under XLA elsewhere; `use_kernel=False` forces
the reference.  The kernel is compiled for a described v5e by
tests/test_tpu_compile.py, and chip_smoke.py runs it on the chip against
the reference.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.ell_histogram import KC as _KC
from repro.kernels.ell_histogram import MAX_WC as _MAX_WC
from repro.kernels.ell_histogram import ell_histogram as _ell_kernel

_ON_TPU = jax.default_backend() == "tpu"
# Auto-dispatch default: the Pallas kernel on TPU, the pure-jnp ref
# elsewhere (the interpret-mode kernel stays directly invocable for tests
# via use_kernel=True).
USE_KERNELS_DEFAULT = _ON_TPU


def _pad_to(x: jnp.ndarray, axis: int, mult: int, value=0):
    size = x.shape[axis]
    target = ((size + mult - 1) // mult) * mult
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads, constant_values=value)


def _ell_tiles_t(nbr_blk, nbr_w):
    """Pad ELL tiles for the kernel (rows to 128, width to 8 or to MAX_WC
    chunks) and transpose them to the kernel's (W, B) layout."""
    w_mult = 8 if nbr_blk.shape[1] <= _MAX_WC else _MAX_WC
    blk = _pad_to(_pad_to(nbr_blk.astype(jnp.int32), 1, w_mult, -1), 0, 128, -1)
    wts = _pad_to(_pad_to(nbr_w.astype(jnp.float32), 1, w_mult, 0.0), 0, 128, 0.0)
    return blk.T, wts.T


@partial(jax.jit, static_argnames=("k", "use_kernel", "interpret"))
def block_histogram(
    nbr_blk: jnp.ndarray,
    nbr_w: jnp.ndarray,
    k: int,
    *,
    use_kernel: bool | None = None,
    interpret: bool = not _ON_TPU,
) -> jnp.ndarray:
    """counts (B, k): weighted per-block neighbor histogram (ELL layout).

    use_kernel=None auto-dispatches: Pallas on TPU, jnp reference under XLA
    elsewhere."""
    if use_kernel is None:
        use_kernel = USE_KERNELS_DEFAULT
    if not use_kernel:
        return _ref.ell_histogram_ref(nbr_blk, nbr_w, k)
    b0 = nbr_blk.shape[0]
    kp = max(-(-k // _KC) * _KC, _KC)
    blk_t, wts_t = _ell_tiles_t(nbr_blk, nbr_w)
    out = _ell_kernel(blk_t, wts_t, kp, interpret=interpret)
    return out[:k, :b0].T
