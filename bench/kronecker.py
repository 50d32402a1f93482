"""Graph500 Kronecker graphs, kept apart from the program's own generators.

``kronecker_graph`` follows the Graph500 specification's generator: 16 · 2^S
edges, each of whose S bit pairs falls into one quadrant of the initiator
A=0.57, B=0.19, C=0.19, D=0.05, and vertex ids randomly permuted.  The same
class is ``kron_g500-lognS`` in the 10th DIMACS Implementation Challenge.
Self loops and duplicate edges are dropped (unit weights), and so are the
isolated vertices, with the ids compacted in the permuted order, so the hub
lands at a random id.  The result has the fields ``bench/graphs.py``'s
``csr_arrays`` and ``to_graph`` read: ``edges`` (u < v, unique), ``n``,
``m``, ``n_max``, ``m_max``.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

INITIATOR = (0.57, 0.19, 0.19, 0.05)
EDGE_FACTOR = 16


@dataclass(frozen=True)
class KronGraph:
    """A Kronecker graph on the host: its undirected edges (u < v, unique)
    over ``n`` non-isolated vertices, and the padded capacity it is handed
    to the partitioner at."""

    n: int
    edges: np.ndarray    # (e, 2) int64, u < v, unique
    n_max: int
    m_max: int

    @property
    def m(self) -> int:
        """Directed edge count, as the CSR stores each edge twice."""
        return 2 * self.edges.shape[0]

    def degrees(self) -> np.ndarray:
        return np.bincount(self.edges.ravel(), minlength=self.n)


def kronecker_edges(rng: np.random.Generator, scale: int,
                    edge_factor: int = EDGE_FACTOR,
                    initiator=INITIATOR) -> np.ndarray:
    """The specification's raw edge list, (edge_factor · 2^scale, 2) int64
    over 2^scale ids: per bit level, the source bit is 1 with probability
    C + D, the target bit 1 with D / (C + D) after a source bit of 1 and
    B / (A + B) after a 0; then the ids are permuted at random."""
    a, b, c, _ = initiator
    ne = edge_factor << scale
    src = np.zeros(ne, np.int64)
    dst = np.zeros(ne, np.int64)
    a_norm, c_norm = a / (a + b), c / (1.0 - a - b)
    for bit in range(scale):
        ii = rng.random(ne) > a + b
        jj = rng.random(ne) > np.where(ii, c_norm, a_norm)
        src |= ii.astype(np.int64) << bit
        dst |= jj.astype(np.int64) << bit
    perm = rng.permutation(1 << scale)
    return np.stack([perm[src], perm[dst]], axis=1)


def kronecker_graph(rng: np.random.Generator, scale: int,
                    n_max: int | None = None, m_max: int | None = None,
                    edge_factor: int = EDGE_FACTOR,
                    initiator=INITIATOR) -> KronGraph:
    """The simple graph of :func:`kronecker_edges` without its isolated
    vertices, at capacity (``n_max``, ``m_max``); each defaults to its
    bound for any seed, 2^scale and 2 · edge_factor · 2^scale directed
    edges."""
    raw = kronecker_edges(rng, scale, edge_factor, initiator)
    raw = raw[raw[:, 0] != raw[:, 1]]
    raw.sort(axis=1)
    edges = np.unique(raw, axis=0)
    used, edges = np.unique(edges, return_inverse=True)
    edges = edges.reshape(-1, 2)
    g = KronGraph(n=used.shape[0], edges=edges,
                  n_max=n_max or 1 << scale,
                  m_max=m_max or 2 * edge_factor << scale)
    if g.n > g.n_max or g.m > g.m_max:
        raise ValueError(f"({g.n}, {g.m}) exceed capacity "
                         f"({g.n_max}, {g.m_max})")
    return g
