"""Device-resident multilevel batch engine — `MultilevelConfig(engine="jax")`.

The numpy engines in core/multilevel.py run the V-cycle on host and only
dispatch the label-histogram inner op to the device; every LP round bounces
labels through `np.add.at`.  This module keeps the *whole* per-batch
V-cycle (DESIGN.md §3.5) on device:

  pack      the batch model graph is packed once into fixed-shape padded
            buffers (`CSRGraph.to_coo_padded` / `to_ell_padded`, pow2
            bucketing so jit caches a handful of compilations per stream),
  coarsen   LP clustering rounds as a `lax.fori_loop` body; contraction is
            a segment-sum over composite (coarse-src, coarse-dst) keys into
            the same padded buffers,
  initial   weighted Fennel on the coarsest level as a sequential
            `lax.fori_loop` over the (≤ coarsen_target) free nodes,
  refine    capacity-constrained LP refinement rounds per level.

The fused best-move + greedy capacity acceptance (numpy: lexsort + grouped
cumsum) takes two forms by target domain: refinement's k + 1 block slots
run a sort-free bit-serial threshold search over a one-hot of the slots;
clustering's node-id targets keep the numpy formulation, one sorted order
built from single-u32-key sorts and a segmented prefix sum.
Neighbor-label aggregation has three modes, picked per level by padded
volume:

  dense   scatter-add into a dense (n_pad, L_pad) count matrix — the
          device twin of the numpy composite-key bincount,
  ell     the padded ELL tiles through `kernels.ops.block_histogram` — the
          Pallas `ell_histogram` kernel on TPU, its jnp reference under
          XLA elsewhere (level 0 only: coarse degrees outgrow the tiles),
  sort    segmented sort + prefix sums over composite keys for shapes too
          large to densify (no volume constraint).

Labels live on device across all levels and transfer to host exactly once,
when the committed batch's assignment is read back.  All arithmetic runs
under `jax.enable_x64(True)` so results are *identical* to the
numpy `sparse` oracle at fixed seed (integer-weight graphs; pinned by
tests/test_multilevel_jax.py).  Host-side work is limited to per-level
scalar pulls (free-node count, coarse size) that drive the level loop.
"""
from __future__ import annotations

import jax  # repro: noqa RPR001 -- whole-module jax engine; imported lazily by core/multilevel.py
import jax.numpy as jnp  # repro: noqa RPR001 -- jax engine module
import numpy as np

from repro.core.fennel import FennelParams
from repro.core.spans import span
from repro.core.multilevel import _ELL_VOLUME_CAP as ELL_VOLUME_CAP
from repro.core.multilevel import _ELL_WIDTH_CAP as ELL_WIDTH_CAP
from repro.graphs.csr import CSRGraph, bucket_size

# dense (n_pad · L_pad) count-matrix entry ceiling; above it the sort mode
# takes over.  On TPU the dense compare-accumulate formulation is the fast
# one (32 MiB of f32 at the cap); on CPU the row-argmax over the padded
# label domain is pure wasted bandwidth, so the sort mode takes over much
# earlier (the refine rounds, with l_pad = k, stay dense everywhere).
# ELL tile ceilings are shared with the host engine (multilevel.py) so the
# two engines' dispatch thresholds can never drift apart.
DENSE_VOLUME_CAP = (1 << 22) if jax.default_backend() == "tpu" else (1 << 18)

# tests force a mode ("dense" | "ell" | "sort") to pin cross-mode parity
MODE_OVERRIDE: str | None = None

# buffer donation frees the device copies of loop-carried state; the CPU
# backend does not implement donation and warns, so gate on backend
_DONATE = jax.default_backend() != "cpu"

# tracing side-effect counters: each jit recompilation re-executes the
# Python body exactly once, so these count compilations per entry point
# (the shape-bucketing test asserts they stay flat across a stream)
_TRACE_COUNTS: dict[str, int] = {}


def _count_trace(name: str) -> None:
    _TRACE_COUNTS[name] = _TRACE_COUNTS.get(name, 0) + 1


def trace_counts() -> dict[str, int]:
    """Compilations per jitted engine entry point since the last reset."""
    return dict(_TRACE_COUNTS)


def reset_trace_counts() -> None:
    _TRACE_COUNTS.clear()


# dispatches per aggregation mode: each refine call (`refine.<mode>`, its
# acceptance over the k + 1 block slots) and each clustering round
# (`cluster.<mode>`); `accept.sorted` counts the clustering rounds'
# sorted acceptances
_AGG_COUNTS: dict[str, int] = {}


def _count_agg(name: str) -> None:
    _AGG_COUNTS[name] = _AGG_COUNTS.get(name, 0) + 1


def agg_counts() -> dict[str, int]:
    """Dispatched refine calls and clustering rounds per aggregation mode
    (`refine.ell`, `refine.dense`, `refine.sort`, `cluster.dense`,
    `cluster.sort`, and `cluster.ell` where a level 0 of at most 4,096
    padded nodes clusters on its tiles), and clustering rounds whose
    acceptance sorts (`accept.sorted`), since the last reset."""
    return dict(_AGG_COUNTS)


def reset_agg_counts() -> None:
    _AGG_COUNTS.clear()


def _jit(fn, *, static=(), donate=()):
    return jax.jit(fn, static_argnames=static,
                   donate_argnums=donate if _DONATE else ())


# --------------------------------------------------------------------------
# TPU-compilable building blocks
#
# The TPU has no 64-bit ALU: under x64 its compiler emulates every f64/i64
# op, and it takes minutes over a sort with 64-bit or multi-word keys, a
# float64 cumsum, or a flat scan over 2^16+ elements.  So indices are int32
# throughout, every large sort has a single u32 key (up to n_pad = 2^16),
# prefix counts are matmuls, f64 prefix sums are unrolled shift-and-add
# steps, and per-group reductions are scatters, or masked reductions over
# a one-hot where the groups are few (refine's k + 1 acceptance targets).
# Weights, loads and gains stay f64 (ROADMAP §1.4 owns that question).
# --------------------------------------------------------------------------

_I32 = jnp.int32
_U32 = jnp.uint32
_SCAN_BLOCK = 128


def _prefix_count(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum of a non-negative int32 vector whose total is
    below 2^24 (0/1 flags here), as a matmul against a triangular block.

    The TPU compiler takes minutes over a flat cumsum or associative scan
    of 2^20 elements, and seconds over this; the f32 sums are exact below
    2^24."""
    n = x.shape[0]
    if n <= _SCAN_BLOCK or n % _SCAN_BLOCK:
        return jnp.cumsum(x, dtype=_I32)
    tri = jnp.triu(jnp.ones((_SCAN_BLOCK, _SCAN_BLOCK), jnp.float32))
    rows = jnp.dot(x.reshape(-1, _SCAN_BLOCK).astype(jnp.float32), tri,
                   precision=jax.lax.Precision.HIGHEST).astype(_I32)
    totals = rows[:, -1]
    carry = _prefix_count(totals) - totals
    return (rows + carry[:, None]).reshape(n)


def _dense_rank(key_s: jnp.ndarray) -> jnp.ndarray:
    """Rank of each entry of a sorted key vector among its distinct keys."""
    new = jnp.concatenate([jnp.ones(1, bool), key_s[1:] != key_s[:-1]])
    return _prefix_count(new.astype(_I32)) - 1


def _pair_key(a: jnp.ndarray, b: jnp.ndarray, base: int,
              valid: jnp.ndarray) -> jnp.ndarray:
    """Unsigned key a·base + b for a, b < base; the dtype's max (the
    sentinel, sorting behind every valid key) where invalid.  u32 up to
    base = 2^16, u64 beyond (a slow sort to compile on the TPU).

    At base = 2^16 the valid key (base-1, base-1) equals the sentinel; every
    caller makes that harmless (contract drops self loops, aggregation
    entries behind the sentinel carry zero weight, and clustering's
    acceptance bounds its groups by the targets, not by the keys)."""
    dt = _U32 if base <= 1 << 16 else jnp.uint64
    key = a.astype(dt) * dt(base) + b.astype(dt)
    return jnp.where(valid, key, _sentinel(key))


def _sentinel(key: jnp.ndarray):
    return key.dtype.type(jnp.iinfo(key.dtype).max)


def _sort_by_key(key: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(sorted key, permutation) from one single-key sort."""
    iota = jnp.arange(key.shape[0], dtype=_I32)
    key_s, order = jax.lax.sort((key, iota), num_keys=1, is_stable=False)
    return key_s, order


def _f32_key(x: jnp.ndarray) -> jnp.ndarray:
    """u32 whose unsigned order is the float order of f32 `x` (-0.0 == 0.0)."""
    b = jax.lax.bitcast_convert_type(jnp.where(x == 0, jnp.float32(0), x),
                                     _U32)
    return jnp.where((b >> 31) == 1, ~b, b | _U32(0x80000000))


def _desc_words(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Two u32 words whose lexicographic order is the descending order of
    f64 `x`: x = hi + lo with hi, lo f32.  Rounding is monotone, so the
    order is kept exactly for values with <= 48 significant bits — every
    integer-weight graph, which is what the parity contract covers."""
    hi = x.astype(jnp.float32)
    lo = jnp.where(jnp.isfinite(x), x - hi.astype(x.dtype), 0.0)
    return ~_f32_key(hi), ~_f32_key(lo.astype(jnp.float32))


# --------------------------------------------------------------------------
# aggregation: per-node (cur_conn, best_w, best_lab) from neighbor labels
# --------------------------------------------------------------------------

def _edge_labels(edst: jnp.ndarray, labels: jnp.ndarray, n_pad: int) -> jnp.ndarray:
    """Label of each directed edge's head; sentinel edges -> -1."""
    pad = edst >= n_pad
    return jnp.where(pad, -1, labels[jnp.minimum(edst, n_pad - 1)])


def _best_from_counts(
    counts: jnp.ndarray,
    own: jnp.ndarray,
    forbidden_cols: jnp.ndarray | None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Row-wise epilogue over a dense (rows, L) count matrix.

    Mirrors the numpy ELL path bit for bit: read own-label connectivity,
    mask forbidden and own columns to -inf, argmax (first max — columns are
    raw ascending labels, so ties break toward the lower label).
    """
    rows_n, l_pad = counts.shape
    rows = jnp.arange(rows_n, dtype=_I32)
    own_c = jnp.clip(own, 0, l_pad - 1)
    cur_conn = jnp.where(own >= 0, counts[rows, own_c], 0.0)
    if forbidden_cols is not None:
        counts = jnp.where(forbidden_cols[None, :], -jnp.inf, counts)
    col_ids = jnp.arange(l_pad, dtype=_I32)
    counts = jnp.where(col_ids[None, :] == own[:, None], -jnp.inf, counts)
    best_w = jnp.max(counts, axis=1)
    # first column holding the row max (a bool argmax: no f64 comparator)
    best_col = jnp.argmax(counts == best_w[:, None], axis=1).astype(_I32)
    return cur_conn, best_w, best_col


def _agg_dense(
    esrc: jnp.ndarray,
    edst: jnp.ndarray,
    ew: jnp.ndarray,
    labels: jnp.ndarray,
    own: jnp.ndarray,
    forbidden_cols: jnp.ndarray | None,
    n_pad: int,
    l_pad: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Scatter-add dense counts — the device twin of the bincount engine."""
    lab = _edge_labels(edst, labels, n_pad)
    valid = (esrc < n_pad) & (lab >= 0)
    flat = jnp.where(valid, esrc * l_pad + jnp.clip(lab, 0, l_pad - 1),
                     n_pad * l_pad)
    counts = jnp.zeros(n_pad * l_pad + 1, dtype=ew.dtype)
    counts = counts.at[flat].add(jnp.where(valid, ew, 0.0))
    counts = counts[: n_pad * l_pad].reshape(n_pad, l_pad)
    return _best_from_counts(counts, own, forbidden_cols)


def _agg_ell(
    nbr: jnp.ndarray,
    wts: jnp.ndarray,
    labels: jnp.ndarray,
    own: jnp.ndarray,
    forbidden_cols: jnp.ndarray | None,
    n_pad: int,
    l_pad: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """ELL tiles through the histogram op (Pallas kernel on TPU)."""
    from repro.kernels import ops as _ops

    mask = nbr >= 0
    lab = jnp.where(mask, labels[jnp.clip(nbr, 0, n_pad - 1)], -1)
    # kernels on means compiled: never the interpreter on a device
    on = _ops.USE_KERNELS_DEFAULT
    counts = _ops.block_histogram(
        lab.astype(jnp.int32), wts.astype(jnp.float32), l_pad,
        use_kernel=on, interpret=not on,
    )
    # f32 kernel accumulator -> f64 epilogue, same cast the host ELL engine
    # performs (exact for the integer-weight graphs the parity suite pins)
    return _best_from_counts(counts.astype(jnp.float64), own, forbidden_cols)


def _per_node_best(src, lab, total, own, live, elig, n_pad):
    """Per-node (cur_conn, best_w, best_lab) from (src, label, total) group
    rows: `live` rows count, `elig` rows may be move targets.  Scatter
    max/min, so the result is exact in any order."""
    seg = jnp.where(live, src, n_pad)
    is_own = live & (lab == own[jnp.clip(src, 0, n_pad - 1)])
    elig = elig & ~is_own

    def seg_max(v):
        return jax.ops.segment_max(v, seg, num_segments=n_pad + 1)[:n_pad]

    cur_conn = seg_max(jnp.where(is_own, total, -jnp.inf))
    cur_conn = jnp.where(jnp.isfinite(cur_conn), cur_conn, 0.0)
    best_w = seg_max(jnp.where(elig, total, -jnp.inf))
    is_best = elig & (total == best_w[jnp.clip(src, 0, n_pad - 1)])
    best_lab = jax.ops.segment_min(jnp.where(is_best, lab, n_pad), seg,
                                   num_segments=n_pad + 1)[:n_pad]
    return cur_conn, best_w, jnp.minimum(best_lab, n_pad)


def _agg_key(esrc, edst, labels, n_pad: int) -> jnp.ndarray:
    """u32 (src, label) sort key of every directed edge (sentinel if none)."""
    lab = _edge_labels(edst, labels, n_pad)
    return _pair_key(esrc, lab, n_pad, (esrc < n_pad) & (lab >= 0))


def _agg_presorted(
    esrc, edst, ew, labels, own, forbidden, n_pad: int, key_s, order,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sort aggregation after the (src, label) sort: each group's total
    lands at its first position by a sorted segment-sum over binary-searched
    group starts (no scan), then scatter max/min per node."""
    lab = _edge_labels(edst, labels, n_pad)
    valid = (esrc < n_pad) & (lab >= 0)
    w_s = jnp.where(valid, ew, 0.0)[order]
    first = jnp.searchsorted(key_s, key_s, side="left").astype(_I32)
    total = jax.ops.segment_sum(w_s, first, num_segments=key_s.shape[0],
                                indices_are_sorted=True)
    base = key_s.dtype.type(n_pad)
    src_s = (key_s // base).astype(_I32)
    lab_s = (key_s % base).astype(_I32)
    # zero-sum groups dropped to match aggregate_by_key's dense path; the
    # sentinel group (all zero weight) goes with them
    live = (first == jnp.arange(key_s.shape[0], dtype=_I32)) & (total != 0)
    elig = live
    if forbidden is not None:
        elig &= ~forbidden[jnp.clip(lab_s, 0, n_pad - 1)]
    return _per_node_best(src_s, lab_s, total, own, live, elig, n_pad)


def _agg_sort(esrc, edst, ew, labels, own, forbidden, n_pad: int):
    """Sort aggregation: no dense scratch, any label domain."""
    key_s, order = _sort_by_key(_agg_key(esrc, edst, labels, n_pad))
    return _agg_presorted(esrc, edst, ew, labels, own, forbidden, n_pad,
                          key_s, order)


def _agg_round0(
    esrc: jnp.ndarray,
    edst: jnp.ndarray,
    ew: jnp.ndarray,
    forbidden: jnp.ndarray,
    n_pad: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Clustering round 0: labels are all-distinct (cluster = arange), so
    the CSR *is* the histogram — per-node max edge weight, tie toward the
    lower neighbor id, no sort and no dense scratch.  The device twin of
    neighbor_label_weights' L == n fast path (zero-weight edges dropped
    the same way)."""
    valid = (esrc < n_pad) & (edst < n_pad) & (ew != 0)
    elig = valid & ~forbidden[jnp.minimum(edst, n_pad - 1)]
    seg = jnp.where(elig, esrc, n_pad)
    best_w = jax.ops.segment_max(jnp.where(elig, ew, -jnp.inf), seg,
                                 num_segments=n_pad + 1)[:n_pad]
    is_best = elig & (ew == best_w[jnp.minimum(esrc, n_pad - 1)])
    best_lab = jax.ops.segment_min(jnp.where(is_best, edst, n_pad), seg,
                                   num_segments=n_pad + 1)[:n_pad]
    # no self loops -> own-label connectivity is identically zero
    return (jnp.zeros(n_pad, dtype=ew.dtype), best_w,
            jnp.minimum(best_lab, n_pad))


def _aggregate(
    mode: str,
    esrc, edst, ew, nbr, wts, labels, own, forbidden, n_pad: int, l_pad: int,
):
    """Dispatch one of the three modes; `forbidden` is a label-domain mask
    (length l_pad for dense/ell, node-domain length n_pad for sort)."""
    if mode == "dense":
        return _agg_dense(esrc, edst, ew, labels, own, forbidden, n_pad, l_pad)
    if mode == "ell":
        return _agg_ell(nbr, wts, labels, own, forbidden, n_pad, l_pad)
    if mode == "sort":
        return _agg_sort(esrc, edst, ew, labels, own, forbidden, n_pad)
    raise ValueError(f"unknown aggregation mode {mode!r}")  # pragma: no cover


# --------------------------------------------------------------------------
# fused greedy capacity acceptance (numpy: lexsort + grouped cumsum)
# --------------------------------------------------------------------------

def _accept_slots(
    movers: jnp.ndarray,
    targets: jnp.ndarray,
    gains: jnp.ndarray,
    node_w: jnp.ndarray,
    capacity: jnp.ndarray,
    n_pad: int,
) -> jnp.ndarray:
    """Refine's per-target gain-descending prefix acceptance over its k + 1
    block slots, on device, sort-free.

    The numpy engine orders movers by (target, gain desc, id asc) and takes
    each while its target's running weight, itself included, fits the
    capacity.  That running weight only grows along the order, so target t
    accepts exactly the movers whose rank key (gain desc, id asc) is at most
    θ_t, the largest key with F_t(θ_t) = w{movers of t keyed <= θ_t} <=
    cap_t.  θ_t is built bit by bit, most significant first, with one
    per-target sum per bit.  The sums are exact for integer weights, so the
    accepted set is the numpy one wherever the parity contract applies.

    Movers' targets index `capacity` (the blocks); with one discard slot
    for non-movers they form an (n_t + 1, n_pad) one-hot built once.  Each
    step reads θ_t at every row by a one-hot select and forms F_t as a
    masked reduction over it, with no scatter or gather on the n_pad rows.
    """
    n_t = capacity.shape[0]
    tgt = jnp.where(movers, targets, n_t).astype(_I32)
    w = jnp.where(movers, node_w, 0.0)
    hi, lo = _desc_words(jnp.where(movers, gains, 0.0))
    words = (hi, lo, jnp.arange(n_pad, dtype=_U32))
    cap = jnp.concatenate([capacity, jnp.zeros(1, capacity.dtype)])
    cap = cap + 1e-9
    # target slots on the short axis, rows on the long one
    hot = jnp.arange(n_t + 1, dtype=_I32)[:, None] == tgt[None, :]

    def lookup(table):
        # one nonzero per column: the sum is the selected entry, exactly
        return jnp.sum(jnp.where(hot, table[:, None], 0), axis=0,
                       dtype=table.dtype)

    def per_target(x):
        return jnp.sum(jnp.where(hot, x[None, :], 0.0), axis=1)

    def weight_upto(theta):
        t = [lookup(th) for th in theta]
        le = (words[0] < t[0]) | ((words[0] == t[0]) & (
            (words[1] < t[1]) | ((words[1] == t[1]) & (words[2] <= t[2]))))
        return le, per_target(jnp.where(le, w, 0.0))

    id_bits = max(n_pad - 1, 1).bit_length()
    sched = [(0, b) for b in range(31, -1, -1)] + \
            [(1, b) for b in range(31, -1, -1)] + \
            [(2, b) for b in range(id_bits - 1, -1, -1)]
    s_word = jnp.asarray([s[0] for s in sched], dtype=_I32)
    s_bit = jnp.asarray([1 << s[1] for s in sched], dtype=_U32)

    def step(i, theta):
        word, bit = s_word[i], s_bit[i]
        cand = tuple(jnp.where(word == j, th | bit, th)
                     for j, th in enumerate(theta))
        ok = weight_upto(cand)[1] <= cap
        return tuple(jnp.where(ok, c, th) for c, th in zip(cand, theta))

    zero = jnp.zeros(n_t + 1, dtype=_U32)
    theta = jax.lax.fori_loop(0, len(sched), step, (zero, zero, zero))
    le, f = weight_upto(theta)
    return movers & le & (lookup(f) <= lookup(cap))


# Clustering's targets are node ids, where a one-hot would be n_pad^2
# cells, so its acceptance keeps the numpy formulation: the movers in
# (target asc, gain desc, id asc) order, a per-target running weight, and
# every mover whose running weight fits.  The order comes from five sorts
# through the shared `_sort_j`, each of a single u32 key (u64 above n_pad
# 2^16, as `_pair_key`) built from dense ranks of the sort before it:
#
#   sort  key                          its dense rank
#   1     gain's lo word (desc)        rank_lo
#   2     gain's hi word (desc)        rank_hi
#   3     (rank_hi, rank_lo)           the gain's rank
#   4     (target, gain rank)          the (target, gain) group's rank
#   5     (group rank, id)             none: every key is distinct
#
# Each sort runs over the order the one before it left, so the small
# programs between them gather what they need (`elem`: the node at each
# position) and only the accepted mask is scattered back to node order.
# Non-movers take the sentinel key in sorts 1-4 and sort behind every
# mover.  A mover's pair key in sorts 3 and 4 equals the sentinel only at
# n_pad = 2^16 with both parts 2^16 - 1; its second part is a rank among
# the movers' keys, which reaches n_pad - 1 only where every row moves, and
# then no sentinel is present.  The last program bounds the groups by the
# targets themselves, not by keys.

def _accept_keys(movers, targets, gains):
    """Clustering acceptance's inputs: each row's target (n_pad where it
    does not move) and sort 1's and sort 2's keys, the lo and hi words of
    its gain (`_desc_words`), the sentinel where it does not move."""
    n_pad = movers.shape[0]
    hi, lo = _desc_words(jnp.where(movers, gains, 0.0))
    sent = _sentinel(hi)
    return (jnp.where(movers, targets, n_pad).astype(_I32),
            jnp.where(movers, lo, sent), jnp.where(movers, hi, sent))


def _accept_hi_key(key_s, order, key_hi):
    """After sort 1: rank_lo, and sort 2's key in sort 1's order."""
    _count_trace("accept_hi_key")
    return _dense_rank(key_s), key_hi[order]


def _accept_gain_key(key_s, order, elem, rank_lo, tgt):
    """After sort 2: sort 3's key, (rank_hi, rank_lo)."""
    _count_trace("accept_gain_key")
    n_pad = tgt.shape[0]
    elem = elem[order]
    return elem, _pair_key(_dense_rank(key_s), rank_lo[order], n_pad,
                           tgt[elem] < n_pad)


def _accept_target_key(key_s, order, elem, tgt):
    """After sort 3: sort 4's key, (target, gain rank)."""
    _count_trace("accept_target_key")
    n_pad = tgt.shape[0]
    elem = elem[order]
    t = tgt[elem]
    return elem, _pair_key(t, _dense_rank(key_s), n_pad, t < n_pad)


def _accept_id_key(key_s, order, elem):
    """After sort 4: sort 5's key, (group rank, id)."""
    _count_trace("accept_id_key")
    elem = elem[order]
    return elem, _pair_key(_dense_rank(key_s), elem, elem.shape[0], True)


def _segmented_cumsum(x: jnp.ndarray, head: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum of `x` restarting at every `head` row, in
    log2(n) shift-and-add steps of elementwise ops (Hillis-Steele): the
    TPU compiler takes minutes over an f64 cumsum or scan.  Exact where
    every partial sum is (integer weights)."""
    n = x.shape[0]
    d = 1
    while d < n:
        x = jnp.where(head, x,
                      x + jnp.concatenate([jnp.zeros(d, x.dtype), x[:-d]]))
        head = head | jnp.concatenate([jnp.ones(d, bool), head[:-d]])
        d *= 2
    return x


def _accept_prefix(order, elem, tgt, node_w, capacity):
    """The acceptance along the sorted order (row i is node
    elem[order[i]]): each mover whose target's running weight, itself
    included, fits the target's capacity.  Returns the mask in node
    order."""
    n_pad = tgt.shape[0]
    elem = elem[order]
    t_s = tgt[elem]
    moving = t_s < n_pad
    head = jnp.concatenate([jnp.ones(1, bool), t_s[1:] != t_s[:-1]])
    within = _segmented_cumsum(jnp.where(moving, node_w[elem], 0.0), head)
    ok = moving & (within <= capacity[jnp.minimum(t_s, n_pad - 1)] + 1e-9)
    # an int32 scatter: a bool one takes the TPU compiler seconds
    return jnp.zeros(n_pad, _I32).at[elem].set(ok.astype(_I32),
                                               unique_indices=True) > 0


# --------------------------------------------------------------------------
# jitted V-cycle stages
# --------------------------------------------------------------------------

# Every sort of more than a few thousand keys takes the TPU compiler 10-20 s
# whatever else its program holds, and the level shapes of a stream vary
# (pow2 buckets of noisy edge counts).  So the big sorts run in one shared
# program keyed by length alone, `_sort_j`, and the stages around them
# compile in a second or two per shape.

def _sort(key):
    _count_trace("sort")
    return _sort_by_key(key)


def _cluster_key(esrc, edst, cluster):
    """Sort key of a clustering round's aggregation (labels = clusters)."""
    _count_trace("cluster_key")
    return _agg_key(esrc, edst, cluster, cluster.shape[0])


def _lp_cluster_round(esrc, edst, ew, nbr, wts, node_w, pinned, n,
                      max_cluster_w, cluster, cw, key_s, order, *, mode: str):
    """One size-constrained LP clustering round up to its acceptance: each
    node's best cluster, the movers, and the acceptance's sort keys.
    `cluster` None is round 0: labels are all distinct, and the sort-free
    all-distinct fast path aggregates; later rounds use `mode` (sort: over
    the presorted keys).  Returns the cluster weights (made here in round
    0), the targets and the first two sort keys (`_accept_keys`)."""
    _count_trace("lp_cluster" if cluster is not None else "lp_cluster0")
    n_pad = node_w.shape[0]
    valid = jnp.arange(n_pad, dtype=_I32) < n
    free = (pinned == -1) & valid
    # pinned-owned clusters are never targets; cluster labels are node ids,
    # so the node-domain mask doubles as the label-column mask (l_pad = n_pad)
    forbidden = pinned >= 0
    round0 = cluster is None
    if round0:
        cluster = jnp.arange(n_pad, dtype=_I32)
        cw = jnp.where(valid, node_w, 0.0)
    with jax.named_scope("lp.aggregate"):
        if round0:
            _, best_w, best_lab = _agg_round0(esrc, edst, ew, forbidden, n_pad)
        elif mode == "sort":
            _, best_w, best_lab = _agg_presorted(
                esrc, edst, ew, cluster, cluster, forbidden, n_pad, key_s,
                order)
        else:
            _, best_w, best_lab = _aggregate(
                mode, esrc, edst, ew, nbr, wts, cluster, cluster, forbidden,
                n_pad, n_pad)
    movers = free & (best_w > 0.0)
    tgt_c = jnp.clip(best_lab, 0, n_pad - 1)
    movers &= cw[tgt_c] + node_w <= max_cluster_w
    with jax.named_scope("lp.accept"):
        return (cw, *_accept_keys(movers, best_lab, best_w))


def _acceptance_order(tgt, key_lo, key_hi):
    """Clustering acceptance's order: the five sorts and the programs
    between them, dispatched from the host.  Returns (order, elem): the
    order's row i is node elem[order[i]]."""
    key_s, order = _sort_j(key_lo)
    rank_lo, key = _accept_hi_key_j(key_s, order, key_hi)
    elem = order
    key_s, order = _sort_j(key)
    elem, key = _accept_gain_key_j(key_s, order, elem, rank_lo, tgt)
    key_s, order = _sort_j(key)
    elem, key = _accept_target_key_j(key_s, order, elem, tgt)
    key_s, order = _sort_j(key)
    elem, key = _accept_id_key_j(key_s, order, elem)
    return _sort_j(key)[1], elem


def _cluster_accept(order, elem, tgt, node_w, cluster, cw, max_cluster_w):
    """A clustering round's acceptance along its sorted order, and the
    accepted moves: new cluster labels and weights."""
    _count_trace("cluster_accept")
    n_pad = node_w.shape[0]
    with jax.named_scope("lp.accept"):
        accept = _accept_prefix(order, elem, tgt, node_w,
                                jnp.maximum(max_cluster_w - cw, 0.0))
    wmv = jnp.where(accept, node_w, 0.0)
    out = jnp.where(accept, tgt, n_pad)
    src_c = jnp.where(accept, cluster, n_pad)
    cw = (cw
          - jax.ops.segment_sum(wmv, src_c, num_segments=n_pad + 1)[:n_pad]
          + jax.ops.segment_sum(wmv, out, num_segments=n_pad + 1)[:n_pad])
    return jnp.where(accept, tgt, cluster), cw


def _contract_nodes(esrc, edst, cluster, node_w, pinned, n):
    """Cluster contraction, node side, plus the coarse-edge sort key.

    Coarse ids are the ascending ranks of the surviving cluster ids (the
    device twin of np.unique(..., return_inverse=True)): a presence bitmap
    and its prefix sum, since cluster ids are node ids.  Returns the coarse
    node weights and pins, the fine->coarse node map, the coarse node count
    and the u32 (coarse-src, coarse-dst) key of every fine edge.
    """
    _count_trace("contract")
    n_pad = node_w.shape[0]
    valid = jnp.arange(n_pad, dtype=_I32) < n
    cl = jnp.where(valid, cluster, n_pad)
    present = jnp.zeros(n_pad + 1, dtype=_I32).at[cl].set(1)[:n_pad]
    rank = _prefix_count(present)
    nc = rank[-1]
    node_map = jnp.where(valid, rank[jnp.minimum(cl, n_pad - 1)] - 1, n_pad)

    cvalid = jnp.arange(n_pad, dtype=_I32) < nc
    cw = jax.ops.segment_sum(
        jnp.where(valid, node_w, 0.0),
        jnp.where(valid, node_map, n_pad), num_segments=n_pad + 1)[:n_pad]
    pin_idx = jnp.where(valid & (pinned >= 0), node_map, n_pad)
    cpin = jnp.full(n_pad + 1, -1, dtype=_I32).at[pin_idx].max(
        jnp.where(valid, pinned, -1))[:n_pad]
    cpin = jnp.where(cvalid, cpin, -2)

    epad = (esrc >= n_pad) | (edst >= n_pad)
    s2 = jnp.where(epad, n_pad, node_map[jnp.minimum(esrc, n_pad - 1)])
    d2 = jnp.where(epad, n_pad, node_map[jnp.minimum(edst, n_pad - 1)])
    # self loops dropped, so the key (n_pad-1, n_pad-1) that shares the
    # sentinel's bits at n_pad = 2^16 never occurs
    key = _pair_key(s2, d2, n_pad, ~epad & (s2 != d2))
    return cw, cpin, node_map, nc, key


def _contract_edges(ew, key_s, order, n_pad: int):
    """Cluster contraction, edge side: one segment-sum over the sorted
    (coarse-src, coarse-dst) keys, front-compacted into the same padded
    buffers.  Returns the coarse edge arrays and their count."""
    _count_trace("contract_edges")
    e_pad = key_s.shape[0]
    w_s = ew[order]
    gid = _dense_rank(key_s)
    sums = jax.ops.segment_sum(w_s, gid, num_segments=e_pad,
                               indices_are_sorted=True)
    gkey = jax.ops.segment_max(key_s, gid, num_segments=e_pad,
                               indices_are_sorted=True)
    n_groups = gid[-1] + 1
    # zero-sum groups are kept as zero-weight edges (every consumer ignores
    # them) so the coarse arrays stay src-sorted — _initial_fennel slices
    # per-node segments out of them by searchsorted
    valid_g = ((jnp.arange(e_pad, dtype=_I32) < n_groups)
               & (gkey != _sentinel(gkey)))
    base = gkey.dtype.type(n_pad)
    esrc2 = jnp.where(valid_g, (gkey // base).astype(_I32), n_pad)
    edst2 = jnp.where(valid_g, (gkey % base).astype(_I32), n_pad)
    ew2 = jnp.where(valid_g, sums, 0.0)
    return esrc2, edst2, ew2, jnp.sum(valid_g)


def _fennel_order(node_w, pinned, n):
    """Initial-partition visit order: free nodes by weight desc, ties id
    asc.  The numpy oracle orders coarse nodes by their float32 weights
    (its contraction stores them as f32), so one u32 word of the f32
    weight is the whole key: a stable single-key sort, in a program of its
    own so that it compiles once per node bucket, not per edge shape."""
    _count_trace("fennel_order")
    n_pad = node_w.shape[0]
    free = (pinned == -1) & (jnp.arange(n_pad, dtype=_I32) < n)
    key = ~_f32_key(jnp.where(free, node_w, -jnp.inf).astype(jnp.float32))
    return jax.lax.sort((key, jnp.arange(n_pad, dtype=_I32)), num_keys=1,
                        is_stable=True)[1]


def _initial_fennel(esrc, edst, ew, node_w, pinned, n, order, loads0,
                    alpha, gamma, cap, *, w_c: int):
    """Weighted Fennel on the coarsest level, heaviest free nodes first.

    Sequential by construction (each step must see earlier placements), so
    per-step cost is everything: the edge arrays are src-sorted (CSR order
    at level 0, composite-key order after _contract), so each step slices
    the node's own edge segment at a static width `w_c` (host-bucketed max
    degree) and reduces it with a (w_c, k) one-hot contraction — no
    full-e_pad scan, no scatter, ~µs per step on CPU.
    """
    _count_trace("initial_fennel")
    n_pad = node_w.shape[0]
    k = loads0.shape[0]
    valid = jnp.arange(n_pad, dtype=_I32) < n
    free = (pinned == -1) & valid
    n_free = jnp.sum(free)
    labels0 = jnp.where(valid & (pinned >= 0), pinned, -1)
    # per-node segment starts in the sorted edge arrays
    indptr = jnp.searchsorted(esrc, jnp.arange(n_pad, dtype=_I32)).astype(_I32)
    blk_ids = jnp.arange(k, dtype=_I32)

    def step(i, carry):
        labels, loads = carry
        v = order[i]
        start = indptr[v]
        seg_src = jax.lax.dynamic_slice(esrc, (start,), (w_c,))
        seg_dst = jax.lax.dynamic_slice(edst, (start,), (w_c,))
        seg_w = jax.lax.dynamic_slice(ew, (start,), (w_c,))
        own = seg_src == v  # masks the tail of short segments (and clamping)
        lab = jnp.where(own & (seg_dst < n_pad),
                        labels[jnp.minimum(seg_dst, n_pad - 1)], -1)
        contrib = jnp.where(lab >= 0, seg_w, 0.0)
        conn = jnp.sum(contrib[:, None] * (lab[:, None] == blk_ids), axis=0)
        penalty = alpha * gamma * jnp.power(jnp.maximum(loads, 0.0),
                                            gamma - 1.0)
        score = conn - penalty
        nw = node_w[v]
        feasible = loads + nw <= cap
        masked = jnp.where(feasible, score, -jnp.inf)
        best = jnp.argmax(masked == jnp.max(masked)).astype(_I32)
        lightest = jnp.argmax(loads == jnp.min(loads)).astype(_I32)
        blk = jnp.where(feasible.any(), best, lightest)
        labels = labels.at[v].set(blk)
        loads = loads + nw * (blk_ids == blk)
        return labels, loads

    return jax.lax.fori_loop(0, n_free, step, (labels0, loads0))


def _lp_refine(esrc, edst, ew, nbr, wts, node_w, pinned, n, labels, loads,
               cap, *, rounds: int, mode: str):
    """Balanced synchronous LP refinement rounds at one level."""
    _count_trace("lp_refine")
    n_pad = node_w.shape[0]
    k = loads.shape[0]
    valid = jnp.arange(n_pad, dtype=_I32) < n
    free = (pinned == -1) & valid

    def round_(_, state):
        labels, loads = state
        with jax.named_scope("lp.aggregate"):
            cur, best_w, best_lab = _aggregate(
                mode, esrc, edst, ew, nbr, wts, labels, labels, None, n_pad, k)
        gains = best_w - cur
        movers = free & (gains > 1e-12)
        room = jnp.maximum(cap - loads, 0.0)
        with jax.named_scope("lp.accept"):
            acc = _accept_slots(movers, best_lab, gains, node_w, room,
                                n_pad)
        wmv = jnp.where(acc, node_w, 0.0)
        old = jnp.where(acc, labels, k)
        new = jnp.where(acc, best_lab, k)
        loads = (loads
                 - jax.ops.segment_sum(wmv, old, num_segments=k + 1)[:k]
                 + jax.ops.segment_sum(wmv, new, num_segments=k + 1)[:k])
        labels = jnp.where(acc, best_lab, labels)
        return labels, loads

    return jax.lax.fori_loop(0, rounds, round_, (labels, loads))


def _project(labels, node_map, pinned):
    """Uncoarsen one level: inherit the coarse label, pinned override."""
    _count_trace("project")
    n_pad = labels.shape[0]
    fine = labels[jnp.clip(node_map, 0, n_pad - 1)]
    return jnp.where(pinned >= 0, pinned, jnp.where(node_map < n_pad, fine, -1))


_sort_j = _jit(_sort)
_cluster_key_j = _jit(_cluster_key)
_lp_cluster_round_j = _jit(_lp_cluster_round, static=("mode",))
_accept_hi_key_j = _jit(_accept_hi_key)
_accept_gain_key_j = _jit(_accept_gain_key)
_accept_target_key_j = _jit(_accept_target_key)
_accept_id_key_j = _jit(_accept_id_key)
_cluster_accept_j = _jit(_cluster_accept)
_contract_nodes_j = _jit(_contract_nodes)
_contract_edges_j = _jit(_contract_edges, static=("n_pad",))
_fennel_order_j = _jit(_fennel_order)
_initial_fennel_j = _jit(_initial_fennel, static=("w_c",), donate=(7,))
_lp_refine_j = _jit(_lp_refine, static=("rounds", "mode"), donate=(8, 9))
_project_j = _jit(_project, donate=(0,))


# --------------------------------------------------------------------------
# host driver: level loop + packing
# --------------------------------------------------------------------------

def _pick_mode(n_pad: int, l_pad: int, w_pad: int | None) -> str:
    """Aggregation mode for one level (host-side, shape-only).

    `w_pad` is the level-0 ELL tile width, or None on coarse levels where
    the tiles no longer describe the graph (coarse degrees outgrow them).
    """
    if MODE_OVERRIDE is not None:
        if MODE_OVERRIDE != "ell":
            return MODE_OVERRIDE
        if w_pad is not None:
            return "ell"  # coarse levels fall through to the shape rules
    elif w_pad is not None:
        # level 0 with usable ELL tiles: the Pallas kernel path on TPU
        from repro.kernels import ops as _ops

        if (_ops.USE_KERNELS_DEFAULT and w_pad <= ELL_WIDTH_CAP
                and n_pad * max(w_pad, l_pad) <= ELL_VOLUME_CAP):
            return "ell"
    if n_pad * l_pad <= DENSE_VOLUME_CAP:
        return "dense"
    return "sort"


def multilevel_partition_jax(
    g: CSRGraph,
    pinned: np.ndarray,
    p: FennelParams,
    loads_base: np.ndarray,
    cfg,
) -> np.ndarray:
    """Drop-in `multilevel_partition` with the V-cycle resident on device.

    Semantics (and, at fixed seed on integer-weight graphs, exact labels)
    match the numpy `sparse` engine; see module docstring for what stays
    host-side.  `cfg` is a MultilevelConfig (imported lazily to avoid a
    module cycle with multilevel.py).
    """
    with jax.enable_x64(True):
        with span("vcycle.pack"):
            n = g.n
            # floored at the block count, so n_pad covers k even when the
            # graph is smaller than the partition (k > n)
            n_pad = bucket_size(max(n, p.k))
            # edge bucket floored at 8·n_pad for stream-scale graphs: batch
            # models in one stream have near-constant node counts but noisy
            # edge counts, and the floor absorbs that noise into a single
            # compilation.  The cap keeps the floor from inflating large or
            # coarse graphs whose true edge count is what matters.
            e_pad = bucket_size(int(g.indices.size),
                                minimum=min(8 * n_pad, 2048))
            src_h, dst_h, w_h = g.to_coo_padded(n_pad, e_pad)
            esrc = jnp.asarray(src_h.astype(np.int32))
            edst = jnp.asarray(dst_h.astype(np.int32))
            ew = jnp.asarray(w_h)
            node_w_h = np.zeros(n_pad, dtype=np.float64)
            node_w_h[:n] = g.node_w
            node_w = jnp.asarray(node_w_h)
            pin_h = np.full(n_pad, -2, dtype=np.int32)
            pin_h[:n] = pinned
            pin = jnp.asarray(pin_h)

            free_total = pinned < 0
            n_free = int(free_total.sum())
            total_free_w = float(g.node_w[free_total].astype(np.float64).sum())
            max_cluster_w = max(total_free_w / max(2 * p.k, 16),
                                float(g.node_w.max(initial=1.0)))

            # level 0 may use the ELL tiles packed once per batch; free-node
            # degrees bound the width (pinned aux rows are never movers, so
            # their truncation is harmless)
            free_deg = int(np.max(np.diff(g.indptr)[free_total], initial=1))
            w_pad = bucket_size(free_deg, minimum=8)

            def cluster_mode(level: int, np_l: int) -> str:
                return _pick_mode(np_l, np_l, w_pad if level == 0 else None)

            def refine_mode(level: int, np_l: int) -> str:
                return _pick_mode(np_l, p.k, w_pad if level == 0 else None)

            dummy_nbr = jnp.zeros((1, 8), dtype=jnp.int32)
            dummy_wts = jnp.zeros((1, 8), dtype=jnp.float64)
            if "ell" in (cluster_mode(0, n_pad), refine_mode(0, n_pad)):
                with span("vcycle.pack_ell"):
                    nbr_h, wts_h, _ = g.to_ell_padded(
                        np.arange(n, dtype=np.int64),
                        row_bucket=n_pad, width_bucket=w_pad)
                    nbr = jnp.asarray(nbr_h.astype(np.int32))
                    wts = jnp.asarray(wts_h)
            else:
                nbr, wts = dummy_nbr, dummy_wts

        # ---- coarsen (level loop on host; arrays stay on device)
        levels: list[tuple] = []
        cur = (esrc, edst, ew, node_w, pin)
        cur_n = n
        cur_free = n_free
        cur_np, cur_ep = n_pad, e_pad
        level = 0
        for _ in range(cfg.max_levels):
            if cur_free <= cfg.coarsen_target:
                break
            lvl_nbr = nbr if level == 0 else dummy_nbr
            lvl_wts = wts if level == 0 else dummy_wts
            c_mode = cluster_mode(level, cur_np)
            cluster = jnp.arange(cur_np, dtype=jnp.int32)
            cw = key_s = order = None
            for r in range(cfg.lp_iters):
                if r and c_mode == "sort":
                    key_s, order = _sort_j(
                        _cluster_key_j(cur[0], cur[1], cluster))
                cw, tgt, key_lo, key_hi = _lp_cluster_round_j(
                    cur[0], cur[1], cur[2], lvl_nbr, lvl_wts, cur[3], cur[4],
                    cur_n, max_cluster_w, cluster if r else None, cw,
                    key_s, order, mode=c_mode)
                cluster, cw = _cluster_accept_j(
                    *_acceptance_order(tgt, key_lo, key_hi), tgt, cur[3],
                    cluster, cw, max_cluster_w)
                _count_agg(f"cluster.{c_mode}")
                _count_agg("accept.sorted")
            cw2, cpin2, node_map, nc_dev, ekey = _contract_nodes_j(
                cur[0], cur[1], cluster, cur[3], cur[4], cur_n)
            es2, ed2, ew2, ne_dev = _contract_edges_j(
                cur[2], *_sort_j(ekey), n_pad=cur_np)
            with span("vcycle.sync"):
                nc = int(nc_dev)
            if nc >= cfg.min_shrink * cur_n:
                break
            levels.append((cur, cur_n, node_map, level))
            # re-bucket: coarse levels shrink geometrically, and slicing the
            # (front-compacted) buffers down keeps per-level cost shrinking
            # with them instead of paying the level-0 padding everywhere.
            # Old sentinels (= old n_pad) stay recognizable: >= the new pad.
            new_np = max(bucket_size(max(nc, p.k)), 64)
            with span("vcycle.sync"):
                ne = int(ne_dev)
            new_ep = bucket_size(ne, minimum=min(8 * new_np, 2048))
            new_ep = min(new_ep, cur_ep)
            if new_np < cur_np or new_ep < cur_ep:
                es2, ed2, ew2 = es2[:new_ep], ed2[:new_ep], ew2[:new_ep]
                cw2, cpin2 = cw2[:new_np], cpin2[:new_np]
            cur = (es2, ed2, ew2, cw2, cpin2)
            cur_n = nc
            cur_np, cur_ep = new_np, new_ep
            with span("vcycle.sync"):
                cur_free = int(jnp.sum((cpin2 == -1)
                                       & (jnp.arange(cur_np) < nc)))
            level += 1

        # ---- initial partition on the coarsest level
        alpha = jnp.float64(p.alpha)
        gamma = jnp.float64(p.gamma)
        cap = jnp.float64(p.cap)
        # w_c need only cover FREE nodes (fennel never slices a pinned
        # row), which keeps it off the aux-node degrees that grow over a
        # stream and would churn the jit cache
        if level == 0:
            max_deg = free_deg
        else:  # one scalar pull: the coarsest free max degree sizes slices
            cnt = jnp.bincount(jnp.minimum(cur[0], cur_np), length=cur_np + 1)
            free_c = (cur[4] == -1) & (jnp.arange(cur_np) < cur_n)
            with span("vcycle.sync"):
                max_deg = max(int(jnp.max(jnp.where(free_c, cnt[:cur_np], 0))), 1)
        # floored at 64: per-step slices stay trivially cheap and batch-to-
        # batch degree noise maps onto one compilation instead of four
        w_c = min(bucket_size(max_deg, minimum=64), cur_ep)
        labels, loads = _initial_fennel_j(
            cur[0], cur[1], cur[2], cur[3], cur[4], cur_n,
            _fennel_order_j(cur[3], cur[4], cur_n),
            jnp.asarray(np.asarray(loads_base, dtype=np.float64)),
            alpha, gamma, cap, w_c=w_c)
        r_mode = refine_mode(level, cur_np)
        labels, loads = _lp_refine_j(
            cur[0], cur[1], cur[2],
            nbr if level == 0 else dummy_nbr,
            wts if level == 0 else dummy_wts,
            cur[3], cur[4], cur_n, labels, loads, cap,
            rounds=cfg.refine_rounds, mode=r_mode)
        _count_agg(f"refine.{r_mode}")

        # ---- uncoarsen + refine
        for fine, fine_n, node_map, lvl in reversed(levels):
            labels = _project_j(labels, node_map, fine[4])
            r_mode = refine_mode(lvl, fine[3].shape[0])
            labels, loads = _lp_refine_j(
                fine[0], fine[1], fine[2],
                nbr if lvl == 0 else dummy_nbr,
                wts if lvl == 0 else dummy_wts,
                fine[3], fine[4], fine_n, labels, loads, cap,
                rounds=cfg.refine_rounds, mode=r_mode)
            _count_agg(f"refine.{r_mode}")

        # the single device->host transfer of the batch assignment
        with span("vcycle.sync"):
            out = np.asarray(labels[:n])
        return out.astype(np.int64)
