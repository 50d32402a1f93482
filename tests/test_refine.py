"""Jetlp / Jetr / full Jet refinement behaviour tests."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import connectivity as cn
from repro.core import metrics, rebalance, refine
from repro.core.graph import build_csr_host
from repro.core.partition import PartitionConfig, partition, refine_only
from repro.data import graphs as gen


def _rand_parts(g, k, seed=0):
    rng = np.random.default_rng(seed)
    p = np.full(g.n_max, k, dtype=np.int32)
    p[: int(g.n)] = rng.integers(0, k, int(g.n))
    return jnp.asarray(p)


def test_slot_values():
    loss = jnp.asarray([-5, -1, 0, 1, 2, 3, 4, 7, 8, 1024])
    s = np.asarray(rebalance.slot(loss))
    assert list(s) == [0, 0, 1, 2, 3, 3, 4, 4, 5, 12]


def test_jetlp_improves_cut():
    g = gen.grid2d(16, 16)
    k = 4
    parts = _rand_parts(g, k)
    lock = jnp.zeros((g.n_max,), bool)
    cut0 = int(metrics.cutsize(g, parts))
    move, dest = refine.jetlp_moves(g, parts, k, lock, c=0.25)
    parts2 = jnp.where(move, dest, parts)
    cut1 = int(metrics.cutsize(g, parts2))
    assert cut1 < cut0


def test_jetlp_respects_locks():
    g = gen.grid2d(16, 16)
    k = 4
    parts = _rand_parts(g, k)
    lock = jnp.ones((g.n_max,), bool)
    move, _ = refine.jetlp_moves(g, parts, k, lock, c=0.25)
    assert int(jnp.sum(move.astype(jnp.int32))) == 0


def _jetlp_per_field(g, parts, k, lock, c, variant, q):
    """Alg 4.2 with the afterburner reading one field per gather: ``F``,
    ``X``, ``Pd`` and ``parts`` at each endpoint, eight M-length gathers.
    Returns (move, dest, X)."""
    use_ratio, use_ab, use_locks = refine.variant_flags(variant)
    F = q.best_conn - q.conn_self
    if use_ratio:
        thr = jnp.floor(c * q.conn_self.astype(jnp.float32)).astype(jnp.int32)
        filter1 = (F >= 0) | (-F < thr)
    else:
        filter1 = F >= 0
    X = g.vertex_mask() & (q.best_conn > 0) & filter1
    if use_locks:
        X = X & ~lock
    Pd = jnp.where(X, q.best_part, parts)
    if not use_ab:
        return X, Pd, X
    u, v, w = g.adjncy, g.esrc, g.adjwgt
    Fu, Fv = F[u], F[v]
    u_first = X[u] & ((Fu > Fv) | ((Fu == Fv) & (u < v)))
    pu = jnp.where(u_first, Pd[u], parts[u])
    contrib = w * (
        (pu == Pd[v]).astype(jnp.int32) - (pu == parts[v]).astype(jnp.int32)
    )
    F2 = jax.ops.segment_sum(
        jnp.where(g.edge_mask() & X[v], contrib, 0), v, num_segments=g.n_max
    )
    return X & (F2 >= 0), Pd, X


def _padded_weighted_graph(seed, n=90, n_max=128, m_pad=37):
    """A connected weighted graph padded past both its vertex and edge
    counts (padding edges carry ``esrc = 0``, so ``esrc`` is unsorted)."""
    rng = np.random.default_rng(seed)
    ring = np.stack([np.arange(n), (np.arange(n) + 1) % n], axis=1)
    extra = rng.integers(0, n, size=(3 * n, 2))
    edges = np.concatenate([ring, extra])
    wts = rng.integers(1, 5, size=len(edges))
    m = build_csr_host(n, edges, wts).adjncy.shape[0]
    return build_csr_host(n, edges, wts, n_max=n_max, m_max=m + m_pad)


def _tied_queries(g, parts, k, rng):
    """ConnQueries with gains in [-7, 3]: ties in ``F`` on most edges, and
    negative gains that filter 1 admits (``-F < floor(c * conn_self)``)."""
    vm = np.asarray(g.vertex_mask())
    p = np.asarray(parts)
    conn_self = np.where(vm, rng.integers(0, 8, g.n_max), 0)
    best_conn = np.where(vm, rng.integers(0, 4, g.n_max), 0)
    other = (p + 1 + rng.integers(0, max(k - 1, 1), g.n_max)) % k
    best_part = np.where(vm & (best_conn > 0), other, k)
    return cn.ConnQueries(*(jnp.asarray(a.astype(np.int32))
                            for a in (conn_self, best_part, best_conn)))


@pytest.mark.parametrize("k", [2, 8, 64])
@pytest.mark.parametrize("variant", list(refine.VARIANTS))
def test_jetlp_moves_equal_per_field_afterburner(variant, k):
    """``jetlp_moves`` returns the per-field afterburner's ``move`` and
    ``dest`` bit for bit: on a padded weighted graph with random locks,
    under the graph's own queries and under queries with forced ties in
    ``F`` and admitted negative gains, and vmapped over two trials."""
    g = _padded_weighted_graph(seed=k)
    rng = np.random.default_rng(100 + k)
    c = 0.75
    cases = []
    for _ in range(2):
        parts = _rand_parts(g, k, seed=int(rng.integers(1 << 30)))
        lock = jnp.asarray(rng.random(g.n_max) < 0.3)
        cases.append((parts, lock, cn.queries(g, parts, k)))
        cases.append((parts, lock, _tied_queries(g, parts, k, rng)))
    use_ratio, use_ab, _ = refine.variant_flags(variant)
    tied_edges = rejected = admitted_negative = 0
    for parts, lock, q in cases:
        move, dest = refine.jetlp_moves(g, parts, k, lock, c,
                                        variant=variant, queries=q)
        ref_move, ref_dest, X = _jetlp_per_field(g, parts, k, lock, c,
                                                 variant, q)
        np.testing.assert_array_equal(np.asarray(move), np.asarray(ref_move))
        np.testing.assert_array_equal(np.asarray(dest), np.asarray(ref_dest))
        F = np.asarray(q.best_conn - q.conn_self)
        Xn, em = np.asarray(X), np.asarray(g.edge_mask())
        u, v = np.asarray(g.adjncy), np.asarray(g.esrc)
        tied_edges += int(np.sum(em & Xn[u] & Xn[v] & (F[u] == F[v])))
        rejected += int(np.sum(Xn & ~np.asarray(move)))
        admitted_negative += int(np.sum(Xn & (F < 0)))
    assert tied_edges > 0
    assert (rejected > 0) == use_ab
    assert (admitted_negative > 0) == use_ratio

    # two trials in one vmapped call, as the trial axis runs them
    parts, lock, q = (jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), a, b)
                      for a, b in zip(cases[1], cases[3]))
    move, dest = jax.vmap(
        lambda p, lk, qq: refine.jetlp_moves(g, p, k, lk, c, variant=variant,
                                             queries=qq))(parts, lock, q)
    for t, (p1, lk1, q1) in enumerate((cases[1], cases[3])):
        ref_move, ref_dest, _ = _jetlp_per_field(g, p1, k, lk1, c, variant, q1)
        np.testing.assert_array_equal(np.asarray(move[t]), np.asarray(ref_move))
        np.testing.assert_array_equal(np.asarray(dest[t]), np.asarray(ref_dest))


def _m_row_gathers(fn, g, *args):
    """Gathers with an M-long output in ``fn``'s compiled CPU HLO."""
    hlo = jax.jit(fn).lower(g, *args).compile().as_text()
    shapes = re.findall(r"= \w+\[([\d,]+)\][^\n]*? gather\(", hlo)
    return sum(str(g.m_max) in dims.split(",") for dims in shapes)


def test_afterburner_gathers_each_endpoint_once():
    """The afterburner reads each edge's head and tail state with one
    gather each: 2 M-length gathers, where one gather per field took 8."""
    g = _padded_weighted_graph(seed=1)
    k = 8
    parts = _rand_parts(g, k, seed=2)
    lock = jnp.zeros((g.n_max,), bool)
    q = cn.queries(g, parts, k)
    packed = _m_row_gathers(
        lambda g, p, lk, q: refine.jetlp_moves(g, p, k, lk, 0.75, queries=q),
        g, parts, lock, q)
    per_field = _m_row_gathers(
        lambda g, p, lk, q: _jetlp_per_field(g, p, k, lk, 0.75, "full", q)[:2],
        g, parts, lock, q)
    assert (packed, per_field) == (2, 8)


@pytest.mark.parametrize("mode", ["weak", "strong"])
def test_rebalance_reduces_oversize(mode):
    g = gen.grid2d(20, 20)  # 400 vertices
    k = 4
    lam = 0.03
    # pathological: everything in part 0
    parts = jnp.where(g.vertex_mask(), 0, k).astype(jnp.int32)
    fn = rebalance.jetrw_moves if mode == "weak" else rebalance.jetrs_moves
    move, dest = fn(g, parts, k, lam)
    parts2 = jnp.where(move, dest, parts)
    sizes0 = np.asarray(metrics.part_sizes(g, parts, k))
    sizes2 = np.asarray(metrics.part_sizes(g, parts2, k))
    assert sizes2.max() < sizes0.max()
    # destinations are real parts
    d = np.asarray(dest)[np.asarray(move)]
    assert d.min() >= 0 and d.max() < k


def test_strong_rebalance_balances_in_one_shot():
    g = gen.grid2d(20, 20)
    k = 4
    lam = 0.10
    parts = jnp.where(g.vertex_mask(), 0, k).astype(jnp.int32)
    move, dest = rebalance.jetrs_moves(g, parts, k, lam)
    parts2 = jnp.where(move, dest, parts)
    W = g.total_vweight()
    sizes2 = metrics.part_sizes(g, parts2, k)
    assert bool(metrics.is_balanced(sizes2, W, k, lam))


@pytest.mark.parametrize("backend", ["dense", "sorted"])
def test_jet_refine_balances_and_improves(backend):
    g = gen.suite_graph("geo_4k")
    k = 8
    lam = 0.03
    parts0 = _rand_parts(g, k, seed=3)
    cut0 = int(metrics.cutsize(g, parts0))
    parts, stats = refine.jet_refine(g, parts0, k, lam=lam, backend=backend)
    W = g.total_vweight()
    sizes = metrics.part_sizes(g, parts, k)
    assert bool(metrics.is_balanced(sizes, W, k, lam)), "output unbalanced"
    cut1 = int(metrics.cutsize(g, parts))
    assert cut1 < cut0 * 0.9, f"barely improved: {cut0} -> {cut1}"
    # all real vertices have real parts; pads ghost
    p = np.asarray(parts)
    assert p[: int(g.n)].max() < k
    assert np.all(p[int(g.n):] == k)


def test_jet_refine_from_unbalanced_start():
    g = gen.grid2d(24, 24)
    k = 6
    lam = 0.05
    parts0 = jnp.where(g.vertex_mask(), 0, k).astype(jnp.int32)
    parts, stats = refine.jet_refine(g, parts0, k, lam=lam)
    W = g.total_vweight()
    sizes = metrics.part_sizes(g, parts, k)
    assert bool(metrics.is_balanced(sizes, W, k, lam))
    assert int(stats["rb_iters"]) >= 1


@pytest.mark.parametrize("b_max", [0, 1, 1000])
def test_rs_iters_counts_strong_rebalances(b_max):
    """``rs_iters`` counts the strong rebalances among ``rb_iters``: all of
    them at ``b_max=0``, some at ``b_max=1`` and none when the weak budget
    is never spent."""
    g = gen.grid2d(24, 24)
    k = 6
    parts0 = jnp.where(g.vertex_mask(), 0, k).astype(jnp.int32)
    _, stats = refine.jet_refine(g, parts0, k, lam=0.05, b_max=b_max,
                                 max_iter=60)
    rb_iters, rs_iters = int(stats["rb_iters"]), int(stats["rs_iters"])
    assert rb_iters >= 1
    assert int(stats["iterations"]) == int(stats["lp_iters"]) + rb_iters
    if b_max == 0:
        assert rs_iters == rb_iters
    elif b_max == 1:
        assert 0 < rs_iters < rb_iters
    else:
        assert rs_iters == 0


@pytest.mark.parametrize("variant", list(refine.VARIANTS))
def test_refine_variants_run(variant):
    g = gen.grid2d(12, 12)
    k = 4
    parts0 = _rand_parts(g, k, seed=1)
    parts, _ = refine.jet_refine(g, parts0, k, lam=0.05, variant=variant)
    W = g.total_vweight()
    sizes = metrics.part_sizes(g, parts, k)
    assert bool(metrics.is_balanced(sizes, W, k, 0.05))


def test_full_partition_pipeline():
    g = gen.suite_graph("rmat_12")
    cfg = PartitionConfig(k=8, lam=0.03, coarse_target=256)
    res = partition(g, cfg)
    assert res.balanced, f"imbalance {res.imbalance}"
    assert res.cut > 0
    assert res.levels >= 2
    # compare against a random partition: multilevel must be far better
    rng = np.random.default_rng(0)
    rand = jnp.asarray(
        np.where(np.arange(g.n_max) < int(g.n), rng.integers(0, 8, g.n_max), 8)
        .astype(np.int32)
    )
    rand_cut = int(metrics.cutsize(g, rand))
    # RMAT is an expander: min cuts are genuinely large; still must beat random
    assert res.cut < 0.6 * rand_cut, f"cut {res.cut} vs random {rand_cut}"


def test_full_partition_quality_grid():
    # structured grid: quality is checkable against the geometric optimum
    g = gen.grid2d(64, 64)
    res = partition(g, PartitionConfig(k=8, lam=0.03, coarse_target=256))
    assert res.balanced
    # 4x2 blocks of 16x32 cost 256; accept anything within 1.5x of optimal
    assert res.cut <= 384, f"grid cut {res.cut} far from optimal 256"


def test_refine_only_mode():
    g = gen.grid2d(32, 32)
    k = 4
    parts0 = _rand_parts(g, k, seed=7)
    cfg = PartitionConfig(k=k, lam=0.03)
    res = refine_only(g, parts0, cfg)
    assert res.balanced
    assert res.cut < int(metrics.cutsize(g, parts0))


def test_weighted_vertices_balance():
    # non-uniform vertex weights
    g0 = gen.grid2d(16, 16)
    from repro.core.graph import graph_to_host

    n, edges, ew, _ = graph_to_host(g0)
    rng = np.random.default_rng(5)
    vw = rng.integers(1, 5, n)
    g = build_csr_host(n, edges, ew, vw)
    k = 4
    lam = 0.10
    parts0 = _rand_parts(g, k, seed=2)
    parts, _ = refine.jet_refine(g, parts0, k, lam=lam)
    W = g.total_vweight()
    sizes = metrics.part_sizes(g, parts, k)
    assert bool(metrics.is_balanced(sizes, W, k, lam))
