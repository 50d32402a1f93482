"""Coarsening: matching validity, contraction invariants, multilevel hierarchy."""
import jax.numpy as jnp
import numpy as np
import pytest

from host_coarsen import coarsen_once
from repro.core import coarsen
from repro.core import graph as gr
from repro.core.graph import build_csr_host, validate_host
from repro.data import graphs as gen


def _check_matching(g, match):
    n = int(g.n)
    m = np.asarray(match)
    for v in range(n):
        if m[v] >= 0:
            assert m[v] != v or True  # self allowed only for pads
            assert m[m[v]] == v, f"not involution at {v}"


@pytest.mark.parametrize("name", ["grid_64x32", "rmat_12", "cube_12"])
def test_hem_valid_involution(name):
    g = gen.suite_graph(name)
    match = coarsen.heavy_edge_matching(g)
    _check_matching(g, match)
    frac = float(np.mean(np.asarray(match)[: int(g.n)] >= 0))
    # Meshes match well with pure HEM; power-law graphs do not (which is
    # exactly why the paper adds two-hop matching at >25% unmatched).
    assert frac > (0.25 if name == "rmat_12" else 0.5), f"HEM matched {frac:.0%}"
    if frac < 0.75:
        match2 = coarsen.twohop_matching(g, match)
        _check_matching(g, match2)
        frac2 = float(np.mean(np.asarray(match2)[: int(g.n)] >= 0))
        assert frac2 > frac + 0.1, f"two-hop didn't help: {frac:.0%}->{frac2:.0%}"


def test_twohop_star():
    # star graph: HEM matches center with one leaf; remaining leaves
    # are two-hop "leaves" and should pair up.
    g = gen.star(10)
    match = coarsen.heavy_edge_matching(g)
    match = coarsen.twohop_matching(g, match)
    _check_matching(g, match)
    matched = np.asarray(match)[:10] >= 0
    assert matched.sum() >= 8  # at most one leftover leaf + maybe none


def test_contraction_preserves_weight():
    g = gen.suite_graph("rmat_12")
    gc, cmap = coarsen_once(g)
    validate_host(gc)
    # vertex weight conserved
    assert int(gc.total_vweight()) == int(g.total_vweight())
    # edge weight: coarse total + internal = fine total
    cu = np.asarray(cmap)[np.asarray(g.esrc)[: int(g.m)]]
    cv = np.asarray(cmap)[np.asarray(g.adjncy)[: int(g.m)]]
    w = np.asarray(g.adjwgt)[: int(g.m)]
    internal = w[cu == cv].sum() // 2
    assert int(gc.total_eweight()) + internal == int(g.total_eweight())


def test_contraction_no_self_loops_no_dups():
    g = gen.suite_graph("smallworld_4k")
    gc, cmap = coarsen_once(g)
    m = int(gc.m)
    src = np.asarray(gc.esrc)[:m]
    dst = np.asarray(gc.adjncy)[:m]
    assert np.all(src != dst)
    keys = src.astype(np.int64) * int(gc.n) + dst
    assert np.unique(keys).shape[0] == m


def test_multilevel_hierarchy():
    g = gen.suite_graph("rmat_12")
    levels = coarsen.multilevel_coarsen(gr.stack_graphs([g]),
                                        coarse_target=256)
    assert len(levels) >= 2
    sizes = [int(lv.stats["n"][0]) for lv in levels]
    assert all(a > b for a, b in zip(sizes, sizes[1:]))
    assert sizes[-1] <= max(256, int(0.95 * sizes[-2]) + 1)
    # every level conserves vertex weight
    for lv in levels:
        assert int(lv.graph.total_vweight()) == int(g.total_vweight())
    # cmaps project: fine vertex -> valid coarse vertex
    for i, lv in enumerate(levels[:-1]):
        nc = sizes[i + 1]
        cm = np.asarray(lv.cmap[0])[: sizes[i]]
        assert cm.min() >= 0 and cm.max() < nc
        # surjective: every coarse vertex has a fine preimage
        assert np.unique(cm).shape[0] == nc


def test_project_partition():
    g = gen.grid2d(8, 8)
    gc, cmap = coarsen_once(g)
    nc = int(gc.n)
    rng = np.random.default_rng(0)
    pc = jnp.asarray(rng.integers(0, 4, gc.n_max).astype(np.int32))
    pf = coarsen.project_partition(cmap, pc)
    pf = np.asarray(pf)
    cm = np.asarray(cmap)
    for v in range(int(g.n)):
        assert pf[v] == np.asarray(pc)[cm[v]]
