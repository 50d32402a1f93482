"""Jetlp / Jetr / full Jet refinement behaviour tests."""
import jax.numpy as jnp
import numpy as np
import pytest

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
