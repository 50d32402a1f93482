"""Hypothesis property tests for the partitioner's invariants.

Includes the paper's Theorem 4.1: the slot-bucketed (approximate) eviction
prefix loses at most 2x the loss of the exact loss-ordered prefix, for
uniform vertex weights and non-negative losses.
"""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dep: skip, don't die at collect
from hypothesis import given, settings, strategies as st  # noqa: E402

from host_coarsen import coarsen_once
from repro.core import coarsen, connectivity as cn, metrics, rebalance, refine
from repro.core.graph import build_csr_host
from repro.data import graphs as gen

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")


@st.composite
def random_graph(draw, max_n=32):
    n = draw(st.integers(4, max_n))
    n_edges = draw(st.integers(n - 1, min(3 * n, n * (n - 1) // 2)))
    rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))
    # spanning path guarantees connectivity
    path = np.stack([np.arange(n - 1), np.arange(1, n)], 1)
    extra = rng.integers(0, n, (n_edges, 2))
    edges = np.concatenate([path, extra])
    edges = edges[edges[:, 0] != edges[:, 1]]
    w = rng.integers(1, 8, edges.shape[0])
    vw = rng.integers(1, 4, n)
    return build_csr_host(n, edges, w, vw)


# ---------------------------------------------------------------------------
# Theorem 4.1
# ---------------------------------------------------------------------------

@given(
    losses=st.lists(st.integers(0, 10_000), min_size=1, max_size=200),
    frac=st.floats(0.05, 0.95),
)
def test_theorem_4_1_bucketed_prefix_2x_bound(losses, frac):
    """loss(L'_x) <= 2 * loss(L_x): slot-ordered prefix vs exact prefix.

    Uniform weights, non-negative losses (the theorem's assumptions).
    """
    losses = np.asarray(losses, dtype=np.int64)
    x = max(1, int(frac * len(losses)))  # prefix size (uniform weights)
    exact = np.sort(losses)[:x]
    slots = np.asarray(rebalance.slot(jnp.asarray(losses)))
    order = np.argsort(slots, kind="stable")
    approx = losses[order][:x]
    assert approx.sum() <= 2 * exact.sum() + 0  # Thm 4.1


@given(g=random_graph(), k=st.integers(2, 6), data=st.data())
def test_refine_output_invariants(g, k, data):
    n = int(g.n)
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    parts0 = jnp.asarray(rng.integers(0, k, g.n_max).astype(np.int32))
    parts0 = jnp.where(g.vertex_mask(), parts0, k)
    lam = 0.20  # loose enough to be satisfiable with integral weights
    parts, stats = refine.jet_refine(g, parts0, k, lam=lam, max_iter=60)
    p = np.asarray(parts)
    # every real vertex assigned a real part; pads ghost
    assert p[:n].min() >= 0 and p[:n].max() < k
    assert np.all(p[n:] == k)
    # cutsize never worse than a balanced input
    W = g.total_vweight()
    sizes0 = metrics.part_sizes(g, parts0, k)
    if bool(metrics.is_balanced(sizes0, W, k, lam)):
        assert int(metrics.cutsize(g, parts)) <= int(metrics.cutsize(g, parts0))


@given(g=random_graph(), data=st.data())
def test_coarsen_conservation(g, data):
    gc, cmap = coarsen_once(g, seed=data.draw(st.integers(0, 1000)))
    assert int(gc.total_vweight()) == int(g.total_vweight())
    m = int(g.m)
    cm = np.asarray(cmap)
    cu = cm[np.asarray(g.esrc)[:m]]
    cv = cm[np.asarray(g.adjncy)[:m]]
    w = np.asarray(g.adjwgt)[:m]
    internal = w[cu == cv].sum() // 2
    assert int(gc.total_eweight()) + internal == int(g.total_eweight())
    assert int(gc.n) <= int(g.n)


@given(g=random_graph(), k=st.integers(2, 5), data=st.data())
def test_connectivity_backends_equivalent(g, k, data):
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31 - 1)))
    parts = jnp.asarray(rng.integers(0, k, g.n_max).astype(np.int32))
    parts = jnp.where(g.vertex_mask(), parts, k)
    qd = cn.dense_queries(g, parts, k)
    qs = cn.sorted_queries(g, parts, k)
    n = int(g.n)
    for a, b in zip(qd, qs):
        assert np.array_equal(np.asarray(a)[:n], np.asarray(b)[:n])


@given(g=random_graph(), k=st.integers(2, 5))
def test_rebalance_never_increases_max_part(g, k):
    # all vertices in part 0 -> any rebalance iteration must shrink the max
    parts = jnp.where(g.vertex_mask(), 0, k).astype(jnp.int32)
    for fn in (rebalance.jetrw_moves, rebalance.jetrs_moves):
        move, dest = fn(g, parts, k, 0.10)
        parts2 = jnp.where(move, dest, parts)
        s0 = np.asarray(metrics.part_sizes(g, parts, k))
        s2 = np.asarray(metrics.part_sizes(g, parts2, k))
        assert s2.max() <= s0.max()
        d = np.asarray(dest)[np.asarray(move)]
        if d.size:
            assert d.min() >= 0 and d.max() < k


@given(g=random_graph(max_n=24))
def test_matching_involution_property(g):
    match = coarsen.heavy_edge_matching(g)
    match = coarsen.twohop_matching(g, match)
    m = np.asarray(match)
    n = int(g.n)
    for v in range(n):
        if m[v] >= 0:
            assert m[m[v]] == v
