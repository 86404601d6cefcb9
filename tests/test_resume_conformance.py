"""Crash/resume conformance: every snapshot a checkpointed run writes must
resume to the exact labels of an uninterrupted run — per driver, per on-disk
format, per restream replay order — plus the shutdown-hardening guarantees
(no orphaned pipeline threads, loud truncated-replay diagnoses)."""
import os
import shutil
import threading
import time

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro.core.checkpoint as ckmod
from repro.api import CheckpointError, partition, resume
from repro.core.buffcut import BuffCutConfig, _buffcut_partition
from repro.core.restream import restream_refine
from repro.graphs.generators import rmat_graph
from repro.graphs.io import write_metis
from repro.graphs.stream import NodeStream
from repro.graphs.stream_io import write_packed

_KW = dict(k=8, buffer_size=64, batch_size=16, eps=0.1)


@pytest.fixture(scope="module")
def graph():
    return rmat_graph(300, 6, seed=3)  # rounds up to n=512


@pytest.fixture(scope="module")
def sources(graph, tmp_path_factory):
    d = tmp_path_factory.mktemp("resume-src")
    packed = str(d / "g.bcsr")
    metis = str(d / "g.metis")
    write_packed(graph, packed)
    write_metis(graph, metis)
    return {"packed": packed, "metis": metis}


def _capture_snapshots(monkeypatch, snaps_dir: str):
    """Tee every checkpoint write into `snaps_dir` so the test can resume
    from each intermediate snapshot after the run completes.  Returns the
    copy list and a `stop()` that turns the tee off (so the resumes
    themselves aren't captured)."""
    real = ckmod.save_checkpoint
    copies = []
    active = {"on": True}

    def tee(path, state):
        real(path, state)
        if active["on"]:
            dst = os.path.join(snaps_dir, f"{len(copies):03d}.ckpt")
            shutil.copy(path, dst)
            copies.append((dst, state["kind"]))

    monkeypatch.setattr(ckmod, "save_checkpoint", tee)
    return copies, lambda: active.update(on=False)


def _sample(seq, limit=4):
    if len(seq) <= limit:
        return list(seq)
    idx = np.linspace(0, len(seq) - 1, limit).astype(int)
    return [seq[i] for i in idx]


@pytest.mark.parametrize("fmt", ["packed", "metis"])
@pytest.mark.parametrize("driver,order", [
    ("buffcut", "priority"),
    ("buffcut-vec", "stream"),
    ("buffcut-pipe", "priority"),
])
def test_every_snapshot_resumes_bit_identically(
    driver, order, fmt, sources, tmp_path, monkeypatch
):
    src = sources[fmt]
    base = partition(src, driver=driver, restream_passes=2,
                     restream_order=order, **_KW)
    snaps = str(tmp_path / "snaps")
    os.makedirs(snaps)
    copies, stop = _capture_snapshots(monkeypatch, snaps)
    cp = str(tmp_path / "run.ckpt")
    chk = partition(src, driver=driver, restream_passes=2,
                    restream_order=order, checkpoint_path=cp,
                    checkpoint_every=2, **_KW)
    np.testing.assert_array_equal(chk.labels, base.labels)
    assert len(copies) >= 3, "expected several snapshots at every=2"
    kinds = {kind for _, kind in copies}
    assert "restream" in kinds, "no snapshot landed inside the restream phase"
    stop()
    # resume from a spread of snapshots incl. the first and last
    for snap, kind in _sample(copies):
        res = resume(snap)
        np.testing.assert_array_equal(res.labels, base.labels, err_msg=(
            f"resume from {os.path.basename(snap)} (kind={kind}) diverged"
        ))
        assert res.stats.cut_weight == pytest.approx(base.stats.cut_weight)


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 10**6), st.sampled_from(["buffcut", "buffcut-vec", "buffcut-pipe"]))
def test_property_random_kill_point_resumes(sources, tmp_path_factory,
                                            seed, driver):
    """Randomized crash point: kill the run at an arbitrary snapshot index
    and the resumed labels must still match an uninterrupted run."""
    src = sources["packed"]
    base = partition(src, driver=driver, restream_passes=1,
                     restream_order="stream", **_KW)
    d = tmp_path_factory.mktemp("kill")
    # a patch scoped to this example: hypothesis reruns the body, so a
    # function-scoped fixture would leak across examples
    with pytest.MonkeyPatch.context() as mp:
        copies, stop = _capture_snapshots(mp, str(d))
        cp = str(d / "run.ckpt")
        partition(src, driver=driver, restream_passes=1,
                  restream_order="stream", checkpoint_path=cp,
                  checkpoint_every=1, **_KW)
        stop()
    snap, kind = copies[seed % len(copies)]
    res = resume(snap)
    np.testing.assert_array_equal(res.labels, base.labels)


def test_resume_rejects_wrong_config(sources, tmp_path, monkeypatch):
    copies, stop = _capture_snapshots(monkeypatch, str(tmp_path))
    cp = str(tmp_path / "run.ckpt")
    partition(sources["packed"], driver="buffcut", checkpoint_path=cp,
              checkpoint_every=2, **_KW)
    stop()
    snap, _ = copies[0]
    with pytest.raises(CheckpointError, match="config does not match"):
        resume(snap, k=9, buffer_size=64, batch_size=16, eps=0.1)
    with pytest.raises(CheckpointError, match="written by a"):
        resume(snap, driver="buffcut-vec")


def test_resume_rejects_corrupted_snapshot(sources, tmp_path, monkeypatch):
    copies, stop = _capture_snapshots(monkeypatch, str(tmp_path))
    cp = str(tmp_path / "run.ckpt")
    partition(sources["packed"], driver="buffcut", checkpoint_path=cp,
              checkpoint_every=2, **_KW)
    stop()
    snap, _ = copies[-1]
    raw = bytearray(open(snap, "rb").read())
    raw[len(raw) // 2] ^= 0x55
    open(snap, "wb").write(bytes(raw))
    with pytest.raises(CheckpointError, match="CRC"):
        resume(snap)


def test_checkpoint_counts_surface_in_stats(sources, tmp_path):
    cp = str(tmp_path / "run.ckpt")
    res = partition(sources["packed"], driver="buffcut", checkpoint_path=cp,
                    checkpoint_every=2, **_KW)
    assert res.stats.checkpoints_written >= 3
    assert os.path.exists(cp)


# --------------------------------------------------- shutdown hardening


def _assert_threads_settle(baseline: int, timeout: float = 6.0) -> None:
    deadline = time.monotonic() + timeout
    while threading.active_count() > baseline:
        if time.monotonic() > deadline:
            extra = [t.name for t in threading.enumerate()]
            pytest.fail(f"orphaned threads after failure: {extra}")
        time.sleep(0.02)


def test_pipelined_parse_error_leaves_no_threads(graph, tmp_path):
    bad = str(tmp_path / "bad.metis")
    write_metis(graph, bad)
    lines = open(bad, "rb").read().splitlines(keepends=True)
    lines[len(lines) // 2] = b"this is not adjacency\n"
    open(bad, "wb").write(b"".join(lines))
    baseline = threading.active_count()
    with pytest.raises(ValueError):
        partition(bad, driver="buffcut-pipe", **_KW)
    _assert_threads_settle(baseline)


def test_pipelined_truncated_stream_leaves_no_threads(graph, tmp_path):
    p = str(tmp_path / "trunc.bcsr")
    write_packed(graph, p)
    raw = open(p, "rb").read()
    open(p, "wb").write(raw[: int(len(raw) * 0.6)])
    baseline = threading.active_count()
    with pytest.raises(ValueError):
        partition(p, driver="buffcut-pipe", **_KW)
    _assert_threads_settle(baseline)


def test_pipelined_checkpoint_failure_leaves_no_threads(graph, tmp_path,
                                                        monkeypatch):
    """A crash raised from the checkpoint write path itself (mid-run, main
    thread) must still tear the reader/worker threads down."""

    def boom(path, state):
        raise RuntimeError("disk full")

    monkeypatch.setattr(ckmod, "save_checkpoint", boom)
    p = str(tmp_path / "g.bcsr")
    write_packed(graph, p)
    baseline = threading.active_count()
    with pytest.raises(RuntimeError, match="disk full"):
        partition(p, driver="buffcut-pipe", checkpoint_path=str(tmp_path / "c"),
                  checkpoint_every=2, **_KW)
    _assert_threads_settle(baseline)


# ------------------------------------------------- engine degradation


def _flaky_jax_multilevel(monkeypatch):
    """Patch multilevel_partition so every jax-engine call dies the way a
    lost accelerator does; sparse calls run for real."""
    import jax

    import repro.core.multilevel as ml

    real = ml.multilevel_partition

    def flaky(g, pinned, p, loads_base, cfg=None):
        if cfg is not None and cfg.engine == "jax":
            raise jax.errors.JaxRuntimeError("injected: XLA backend lost")
        return real(g, pinned, p, loads_base, cfg)

    monkeypatch.setattr(ml, "multilevel_partition", flaky)


def test_jax_engine_failure_falls_back_to_sparse(graph, monkeypatch):
    import dataclasses

    cfg_sparse = BuffCutConfig(**_KW)
    cfg_jax = dataclasses.replace(
        cfg_sparse, ml=dataclasses.replace(cfg_sparse.ml, engine="jax")
    )
    base, base_stats = _buffcut_partition(NodeStream(graph), cfg_sparse)
    _flaky_jax_multilevel(monkeypatch)
    labels, stats = _buffcut_partition(NodeStream(graph), cfg_jax)
    # engine parity is pinned, so the degraded run is bit-identical
    np.testing.assert_array_equal(labels, base)
    assert stats.engine_fallbacks == base_stats.n_batches + base_stats.n_hubs \
        or stats.engine_fallbacks >= 1
    assert base_stats.engine_fallbacks == 0


@pytest.mark.parametrize("exc", [ImportError, NotImplementedError])
def test_jax_engine_path_errors_propagate(graph, monkeypatch, exc):
    """A broken device path (an import or lowering failure) is not a lost
    device: no fallback hides it behind a host run."""
    import dataclasses

    import repro.core.multilevel as ml

    real = ml.multilevel_partition

    def broken(g, pinned, p, loads_base, cfg=None):
        if cfg is not None and cfg.engine == "jax":
            raise exc("injected: device path broken")
        return real(g, pinned, p, loads_base, cfg)

    monkeypatch.setattr(ml, "multilevel_partition", broken)
    cfg = BuffCutConfig(**_KW)
    cfg = dataclasses.replace(cfg, ml=dataclasses.replace(cfg.ml, engine="jax"))
    with pytest.raises(exc, match="device path broken"):
        _buffcut_partition(NodeStream(graph), cfg)


def test_sparse_engine_failure_still_propagates(graph, monkeypatch):
    import repro.core.multilevel as ml

    def broken(g, pinned, p, loads_base, cfg=None):
        raise RuntimeError("host engine bug")

    monkeypatch.setattr(ml, "multilevel_partition", broken)
    with pytest.raises(RuntimeError, match="host engine bug"):
        _buffcut_partition(NodeStream(graph), BuffCutConfig(**_KW))


# --------------------------------------------------- replay-count guard


class _ShrinkingStream(NodeStream):
    """Replays fully the first `full_iters` times, then loses its tail —
    the disk-file-shrank-under-us failure mode."""

    def __init__(self, g, full_iters: int, keep: int):
        super().__init__(g)
        self._iters = 0
        self._keep = keep
        self._full = full_iters

    def __iter__(self):
        self._iters += 1
        it = super().__iter__()
        if self._iters <= self._full:
            yield from it
            return
        for i, rec in enumerate(it):
            if i >= self._keep:
                return
            yield rec


def test_replay_guard_distinguishes_truncation_from_one_shot(graph):
    cfg = BuffCutConfig(**_KW)
    b0, s0 = _buffcut_partition(NodeStream(graph), cfg)
    # truncated mid-pass: prelude replay comes up short with a byte offset /
    # record-index diagnosis naming the pass
    stream = _ShrinkingStream(graph, full_iters=0, keep=graph.n // 2)
    with pytest.raises(ValueError, match="truncated mid-pass"):
        restream_refine(stream, b0, cfg, 1)
    # pass-1 truncation (prelude skipped via seeds) names the pass
    stream = _ShrinkingStream(graph, full_iters=0, keep=graph.n // 2)
    with pytest.raises(ValueError, match="during restream pass 1"):
        restream_refine(stream, b0, cfg, 1, initial_cut=s0.cut_weight,
                        initial_loads=np.asarray(s0.block_loads))
    # a source that cannot replay at all keeps the one-shot diagnosis
    stream = _ShrinkingStream(graph, full_iters=0, keep=0)
    with pytest.raises(ValueError, match="one-shot stream"):
        restream_refine(stream, b0, cfg, 1, initial_cut=s0.cut_weight,
                        initial_loads=np.asarray(s0.block_loads))
