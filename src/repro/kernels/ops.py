"""Jit'd public wrappers around the Pallas kernels.

Each op pads its inputs to hardware-aligned tiles and dispatches to the
Pallas kernel: compiled where the default backend is a TPU, interpreted
elsewhere.  `use_kernel=None` picks the kernel on a TPU and the pure-jnp
reference (kernels/ref.py) under XLA elsewhere; `use_kernel=False` forces
the reference.  The histogram and Fennel kernels are compiled for a
described v5e by tests/test_tpu_compile.py, and chip_smoke.py runs them
on the chip against the references.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.ell_histogram import KC as _KC
from repro.kernels.ell_histogram import MAX_WC as _MAX_WC
from repro.kernels.ell_histogram import ell_histogram as _ell_kernel
from repro.kernels.fennel_gain import _NEG_INF
from repro.kernels.fennel_gain import fennel_gain as _fennel_kernel
from repro.kernels.embedding_bag import embedding_bag as _bag_kernel
from repro.kernels.swa_attention import swa_attention_decode as _swa_kernel

_ON_TPU = jax.default_backend() == "tpu"
# Auto-dispatch default: Pallas kernels on TPU, pure-jnp refs elsewhere
# (CPU dry-run lowers the ref formulation; interpret-mode kernels remain
# directly invocable for tests via use_kernel=True).
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
    """Pad ELL tiles for the kernels (rows to 128, width to 8 or to MAX_WC
    chunks) and transpose them to the kernels' (W, B) layout."""
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
    elsewhere (same policy as swa_attention_decode)."""
    if use_kernel is None:
        use_kernel = USE_KERNELS_DEFAULT
    if not use_kernel:
        return _ref.ell_histogram_ref(nbr_blk, nbr_w, k)
    b0 = nbr_blk.shape[0]
    kp = max(-(-k // _KC) * _KC, _KC)
    blk_t, wts_t = _ell_tiles_t(nbr_blk, nbr_w)
    out = _ell_kernel(blk_t, wts_t, kp, interpret=interpret)
    return out[:k, :b0].T


@partial(
    jax.jit,
    static_argnames=("alpha", "gamma", "cap", "use_kernel", "interpret"),
)
def fennel_choose_batch(
    nbr_blk: jnp.ndarray,
    nbr_w: jnp.ndarray,
    loads: jnp.ndarray,
    node_w: jnp.ndarray,
    *,
    alpha: float,
    gamma: float,
    cap: float,
    use_kernel: bool | None = None,
    interpret: bool = not _ON_TPU,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Wavefront Fennel assignment for a tile of nodes (fused kernel).

    use_kernel=None auto-dispatches by backend (see block_histogram)."""
    if use_kernel is None:
        use_kernel = USE_KERNELS_DEFAULT
    if not use_kernel:
        return _ref.fennel_gain_ref(
            nbr_blk, nbr_w, loads, node_w, alpha=alpha, gamma=gamma, cap=cap
        )
    b0 = nbr_blk.shape[0]
    k0 = loads.shape[0]
    kp = -(-k0 // 8) * 8
    blk_t, wts_t = _ell_tiles_t(nbr_blk, nbr_w)
    # padded blocks get load=+cap so they are never feasible/chosen
    loads_p = jnp.full((kp,), jnp.float32(cap) * 2 + 1, dtype=jnp.float32)
    loads_p = loads_p.at[:k0].set(loads.astype(jnp.float32))
    penalty = alpha * gamma * jnp.power(jnp.maximum(loads_p, 0.0), gamma - 1.0)
    node_w_p = _pad_to(node_w.astype(jnp.float32), 0, 128, 0.0)
    best, score = _fennel_kernel(
        blk_t, wts_t, loads_p, penalty, node_w_p, cap=cap, interpret=interpret,
    )
    # no feasible block: the least-loaded one (fennel_gain_ref's fallback)
    fallback = jnp.argmin(loads.astype(jnp.float32)).astype(jnp.int32)
    best = jnp.where(score > _NEG_INF, best, fallback)
    return best[:b0], score[:b0]


@partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def embedding_bag(
    table: jnp.ndarray,
    idx: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    use_kernel: bool = True,
    interpret: bool = not _ON_TPU,
) -> jnp.ndarray:
    """Pooled embedding lookup: (B, D) = Σ_l table[idx] * mask."""
    idx = jnp.clip(idx, 0, table.shape[0] - 1)
    if not use_kernel:
        return _ref.embedding_bag_ref(table, idx, mask)
    d0 = table.shape[1]
    table_p = _pad_to(table, 1, 128, 0.0)
    out = _bag_kernel(table_p, idx, mask, interpret=interpret)
    return out[:, :d0]


@partial(jax.jit, static_argnames=("window", "use_kernel", "interpret"))
def swa_attention_decode(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    pos: jnp.ndarray,
    *,
    window: int,
    use_kernel: bool | None = None,
    interpret: bool = not _ON_TPU,
) -> jnp.ndarray:
    """Decode one token with sliding-window attention over a long cache.

    q: (B, KVH, G, D); k_cache/v_cache: (B, S, KVH, D); pos: (B,) fill level.
    Slices an aligned (window + 8)-sized view of the cache (O(window) copy,
    independent of S) and runs the windowed kernel on it.
    """
    if use_kernel is None:
        use_kernel = USE_KERNELS_DEFAULT
    b, s, kvh, d = k_cache.shape
    wp = min(((window + 7) // 8) * 8 + 8, max(s, 8))
    # per-batch-element aligned window start (decode batches can be ragged)
    start = jnp.maximum(pos - window, 0)
    start = (start // 8) * 8
    start = jnp.minimum(start, jnp.int32(max(s - wp, 0))).astype(jnp.int32)
    slice_fn = jax.vmap(
        lambda cache, st: jax.lax.dynamic_slice(cache, (st, 0, 0), (wp, kvh, d))
    )
    k_win = jnp.moveaxis(slice_fn(k_cache, start), 1, 2)  # (B, KVH, Wp, D)
    v_win = jnp.moveaxis(slice_fn(v_cache, start), 1, 2)
    win_start = start
    d0 = q.shape[-1]
    if not use_kernel:
        return _ref.swa_attention_decode_ref(
            q, k_win, v_win, pos, win_start, window=window
        )
    q_p = _pad_to(q, 3, 128, 0.0)
    k_p = _pad_to(k_win, 3, 128, 0.0)
    v_p = _pad_to(v_win, 3, 128, 0.0)
    out = _swa_kernel(
        q_p, k_p, v_p, pos, win_start,
        window=window, scale=1.0 / float(d0) ** 0.5, interpret=interpret,
    )
    return out[..., :d0]
