"""Delaunay triangulation of uniform random points in the unit square, the
construction of the DIMACS10 ``delaunay_nX`` graphs.  ``points`` points are
drawn from the seed and numbered row-major over a ``cells x cells`` grid
of the square (by row of cells, then by x), as a mesh generator numbers
its vertices; two points are joined where they share a triangle's side.
The triangulation is Qhull's, through `scipy.spatial.Delaunay`."""
from __future__ import annotations

import numpy as np
from scipy.spatial import Delaunay

from bench.canonical import from_edges


def generate(params: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    n = int(params["points"])
    cells = int(params["cells"])
    xy = np.random.default_rng(seed).random((n, 2))
    row = np.minimum((xy[:, 1] * cells).astype(np.int64), cells - 1)
    xy = xy[np.lexsort((xy[:, 0], row))]
    tri = Delaunay(xy).simplices
    src = tri.ravel()
    dst = tri[:, [1, 2, 0]].ravel()
    return from_edges(n, src, dst)
