"""Graph generators of the benchmark, kept apart from the program's own.

``delaunay_mesh`` follows the DIMACS10 class ``delaunay_nS``: the Delaunay
triangulation of 2^S points drawn uniformly from the unit square.  The mesh
is built on the host with ``scipy.spatial.Delaunay`` and handed to the
partitioner as a padded ``Graph`` of a capacity fixed by the configuration,
so that the finest level's programs are the same for every seed.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Mesh:
    """A mesh on the host: its points, its undirected edges (u < v), and
    the padded capacity it is handed to the partitioner at."""

    points: np.ndarray   # (n, 2) float64
    edges: np.ndarray    # (e, 2) int64, u < v, unique
    n_max: int
    m_max: int

    @property
    def n(self) -> int:
        return self.points.shape[0]

    @property
    def m(self) -> int:
        """Directed edge count, as the CSR stores each edge twice."""
        return 2 * self.edges.shape[0]


def delaunay_mesh(rng: np.random.Generator, scale: int) -> Mesh:
    """Delaunay triangulation of 2^scale uniform points in the unit square,
    at capacity (2^scale, 6 * 2^scale): a planar triangulation has at most
    3n - 6 undirected edges, so every seed fits."""
    from scipy.spatial import Delaunay

    n = 1 << scale
    pts = rng.random((n, 2))
    tri = Delaunay(pts).simplices.astype(np.int64)
    e = np.concatenate([tri[:, [0, 1]], tri[:, [1, 2]], tri[:, [0, 2]]])
    e.sort(axis=1)
    edges = np.unique(e, axis=0)
    mesh = Mesh(points=pts, edges=edges, n_max=n, m_max=6 * n)
    if mesh.m > mesh.m_max:
        raise ValueError(f"{mesh.m} directed edges exceed capacity "
                         f"{mesh.m_max}")
    return mesh


def csr_arrays(mesh: Mesh) -> dict[str, np.ndarray]:
    """Padded CSR arrays of the mesh (unit weights), in the partitioner's
    layout: ``xadj`` (N+1,), ``adjncy``/``adjwgt``/``esrc`` (M,), ``vwgt``
    (N,), every padding entry 0 and the ``xadj`` tail repeating m."""
    n, m = mesh.n, mesh.m
    src = np.concatenate([mesh.edges[:, 0], mesh.edges[:, 1]])
    dst = np.concatenate([mesh.edges[:, 1], mesh.edges[:, 0]])
    order = np.lexsort((dst, src))
    src, dst = src[order], dst[order]
    xadj = np.full(mesh.n_max + 1, m, np.int32)
    xadj[: n + 1] = np.concatenate(
        [[0], np.cumsum(np.bincount(src, minlength=n))])

    def pad(a, size):
        out = np.zeros(size, np.int32)
        out[: a.shape[0]] = a
        return out

    return {
        "xadj": xadj,
        "adjncy": pad(dst, mesh.m_max),
        "adjwgt": pad(np.ones(m, np.int32), mesh.m_max),
        "vwgt": pad(np.ones(n, np.int32), mesh.n_max),
        "esrc": pad(src, mesh.m_max),
        "n": np.int32(n),
        "m": np.int32(m),
    }


def to_graph(mesh: Mesh):
    """The mesh as the program's padded ``Graph``, on the default device."""
    import jax

    from repro.core.graph import Graph

    a = csr_arrays(mesh)
    return Graph(*jax.device_put([a[f] for f in Graph._fields]))
