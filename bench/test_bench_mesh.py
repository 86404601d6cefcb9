"""The mesh cell's path on the CPU at a tiny size: its Delaunay generator,
a Delaunay mesh in natural order through the whole run with its level-0
refine on the ELL tiles, held to the reference; its control; and the
readers of the kernel's time and roofline share."""
import json
from pathlib import Path

import numpy as np
import pytest

import repro.core.multilevel_jax as mlj
from bench import control, control_mesh, run  # noqa: F401  (control_mesh adds `rounds`)
from bench.graphs import delaunay
from bench.metrics import ell_histogram_ms, ell_histogram_roofline

ROOT = Path(__file__).resolve().parents[1]
SEED = 2**31 + 99


@pytest.fixture
def mesh_spec():
    """BENCHMARK.json with one extra cell on a Delaunay mesh of 16,384 points."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-mesh", "file": "bench/testdata/tiny-mesh.json"})
    spec["workloads"].append({"name": "tiny-mesh.natural", "config": "tiny-mesh",
                              "traffic": "natural", "chips": 1})
    return spec


TINY = {"points": 1 << 14, "cells": 128}


def test_delaunay_is_a_seeded_triangulation():
    """A planar triangulation of the seed's points: m = 3n - 3 - hull
    (every vertex in a triangle), degrees spread as a random point set's
    do, row-major numbering (most edges join ids at most three rows
    apart), the same graph for a seed and another graph for another seed."""
    from scipy.spatial import ConvexHull

    n, cells = TINY["points"], TINY["cells"]
    seeds = (2**31 + 7, 2**31 + 8)
    (ip, ix), again, (ip2, ix2) = (delaunay.generate(TINY, s)
                                   for s in (seeds[0], seeds[0], seeds[1]))
    assert np.array_equal(ip, again[0]) and np.array_equal(ix, again[1])
    assert ip2.shape == ip.shape and not np.array_equal(ix2, ix)

    deg = np.diff(ip)
    src = np.repeat(np.arange(n), deg)
    assert np.all(src != ix)
    pairs = set(zip(src.tolist(), ix.tolist()))
    assert all((v, u) in pairs for u, v in pairs)
    xy = np.random.default_rng(seeds[0]).random((n, 2))
    hull = len(ConvexHull(xy).vertices)
    assert len(ix) // 2 == 3 * n - 3 - hull
    assert deg.min() >= 2 and deg.max() >= 10 and abs(deg.mean() - 6) < 0.01
    assert np.mean(np.abs(src - ix) <= 3 * cells) > 0.98


def _one_altered(real):
    def vcycle(g, pinned, p, loads, cfg=None):
        out = real(g, pinned, p, loads, cfg)
        out[0] = (out[0] + 1) % p.k
        return out
    return vcycle


@pytest.mark.parametrize("fault", [None, _one_altered])
def test_mesh_through_ell_tiles(fault, mesh_spec, tmp_path, monkeypatch, jax_state):
    """Level-0 refine on the ELL tiles (the chip's kernel path; its jnp twin
    here): correct against the reference, and not correct with one label
    altered in every V-cycle."""
    import repro.core.multilevel as ml

    monkeypatch.setattr(mlj, "MODE_OVERRIDE", "ell")
    if fault is not None:
        monkeypatch.setattr(ml, "multilevel_partition", fault(ml.multilevel_partition))
    mlj.reset_agg_counts()
    res = run.run("tiny-mesh.natural", SEED, 1.0, False, spec=mesh_spec,
                  require_tpu=False, data_dir=tmp_path)
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["correct"] is (fault is None)
    assert mlj.agg_counts()["refine.ell"] > 0


@pytest.mark.parametrize("broken", ["none", "rounds"])
def test_mesh_control_in_the_programs_place(broken, mesh_spec, tmp_path, jax_state):
    """The reference in the V-cycle's place through a whole mesh run: correct
    with the stated settings, not correct with one refinement round fewer."""
    res = control.run_control("tiny-mesh.natural", broken, SEED, 1.0, spec=mesh_spec,
                              require_tpu=False, data_dir=tmp_path)
    assert res["attempted"] > 0
    assert res["correct"] is (broken == "none")


# one kernel call on (8, 65,536) tiles as the chip's trace holds it: the
# kernel's own instruction, then two fusions that read its result
OWN = {"seconds": 77.853e-6,
       "shapes": [("f32", [128, 65536]), ("s32", [8, 65536]), ("f32", [8, 65536])]}
READERS = [
    {"seconds": 7.597e-6, "shapes": [("f32", [65536]), ("f32", [65536]), ("s32", [65536]),
                                     ("f32", [128, 65536])]},
    {"seconds": 11.911e-6, "shapes": [("pred", [65536]), ("u32", [65536]),
                                      ("u32", [65536]), ("f32", [65536]), ("s32", [65536]),
                                      ("pred", [65536]), ("f32", [128, 65536])]},
]


@pytest.mark.parametrize("trace,ms,share", [
    (None, None, None),
    ({"kernels": {}}, None, None),
    ({"kernels": {"ell_histogram": READERS}}, None, None),
    # the bytes bound: 37,748,736 B at 819 GB/s is 46.09 us of 77.853 us
    ({"kernels": {"ell_histogram": [OWN] + READERS}}, 0.077853, 59.203),
    ({"kernels": {"ell_histogram": [OWN, dict(OWN, seconds=2 * OWN["seconds"])]}},
     1.5 * 0.077853, 59.203 * 2 / 3),
])
def test_ell_histogram_readers(trace, ms, share):
    """Mean time and roofline share of the kernel's own calls: the fusions
    that read its result are left out, and nothing found reads None."""
    m = {"trace": trace, "batches": 3, "device_kind": "TPU v5 lite"}
    for reader, want in ((ell_histogram_ms, ms), (ell_histogram_roofline, share)):
        got = reader.read(m)
        if want is None:
            assert got is None
        else:
            assert got == pytest.approx(want, rel=1e-4)
