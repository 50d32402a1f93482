"""Device-resident coarsening (DESIGN.md §8): equivalence with the host
repack reference (``host_coarsen``), shape-schedule mechanics, capacity
re-bucketing, and the counters a fleet lane reports."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from host_coarsen import coarsen_once, multilevel
from repro.core import coarsen
from repro.core import graph as gr
from repro.core.graph import csr_from_edge_runs, validate_host
from repro.core.partition import (
    PartitionConfig, partition, partition_fleet_stacked,
)
from repro.data import graphs as gen

FAMILIES = ["grid_64x32", "rmat_12", "smallworld_4k"]


def _lane(g):
    return gr.stack_graphs([g])


def test_coarsen_level_traces_with_no_host_transfers():
    """The whole level — matching, two-hop cond, contraction, CSR build —
    must stage to one pure jaxpr: any host sync would fail tracing."""
    g = gen.suite_graph("grid_64x32")
    jaxpr = jax.make_jaxpr(
        lambda gg, s: coarsen.coarsen_level(gg, seed=s)
    )(_lane(g), jnp.int32(0))
    assert "callback" not in str(jaxpr)


@pytest.mark.parametrize("name", FAMILIES)
def test_device_hierarchy_matches_host(name):
    g = gen.suite_graph(name)
    dev = coarsen.multilevel_coarsen(_lane(g), coarse_target=256)
    host = multilevel(g, coarse_target=256)
    assert len(dev) == len(host) and len(dev) >= 2
    for a, (hg, hcmap) in zip(dev, host):
        ag = gr.unstack_graph(a.graph, 0)
        # same true sizes, same tight content — padding may differ
        n, m = int(a.stats["n"][0]), int(a.stats["m"][0])
        assert (n, m) == (int(hg.n), int(hg.m))
        for f in ("esrc", "adjncy", "adjwgt"):
            assert np.array_equal(np.asarray(getattr(ag, f))[:m],
                                  np.asarray(getattr(hg, f))[:m]), f
        assert np.array_equal(np.asarray(ag.vwgt)[:n],
                              np.asarray(hg.vwgt)[:n])
        assert np.array_equal(np.asarray(ag.xadj)[: n + 1],
                              np.asarray(hg.xadj)[: n + 1])
        validate_host(ag)
        assert (a.cmap is None) == (hcmap is None)
        if a.cmap is not None:
            assert np.array_equal(np.asarray(a.cmap[0])[:n],
                                  np.asarray(hcmap)[:n])


def test_device_levels_shrink_capacity():
    g = gen.suite_graph("grid_64x32")
    dev = coarsen.multilevel_coarsen(_lane(g), coarse_target=128)
    caps = [(lv.stats["n_max"], lv.stats["m_max"]) for lv in dev]
    assert caps[-1][0] < caps[0][0] and caps[-1][1] < caps[0][1], caps
    for lv in dev:
        assert lv.stats["n"][0] <= lv.stats["n_max"]
        assert lv.stats["m"][0] <= lv.stats["m_max"]
        assert lv.graph.vwgt.shape == (1, lv.stats["n_max"])


def test_shape_schedule_rungs():
    sched = coarsen.shape_schedule(10000, 80000)
    assert sched[0] == (10000, 80000)
    # descending in both coordinates, aligned past rung 0
    for (n0, m0), (n1, m1) in zip(sched, sched[1:]):
        assert n1 <= n0 and m1 <= m0
        assert n1 % 64 == 0 and m1 % 64 == 0
    # selection: per-axis smallest fitting rung, top rung always fits
    assert coarsen.select_capacity(sched, 10000, 80000) == sched[0]
    cap = coarsen.select_capacity(sched, 100, 700)
    assert cap[0] >= 100 and cap[1] >= 700
    assert cap[0] == min(n for n, _ in sched if n >= 100)
    assert cap[1] == min(m for _, m in sched if m >= 700)


def test_undersized_schedule_rejected():
    g = gen.suite_graph("grid_64x32")  # n=2048
    bad = coarsen.shape_schedule(256, 1024)
    with pytest.raises(ValueError, match="rung 0"):
        coarsen.multilevel_coarsen(_lane(g), schedule=bad)


def test_with_capacity_roundtrip():
    g = gen.suite_graph("grid_64x32")
    big = g.with_capacity(g.n_max + 100, g.m_max + 256)
    assert big.n_max == g.n_max + 100 and big.m_max == g.m_max + 256
    validate_host(big)
    back = big.with_capacity(g.n_max, g.m_max)
    for f in g._fields:
        assert np.array_equal(np.asarray(getattr(back, f)),
                              np.asarray(getattr(g, f))), f


def test_csr_from_edge_runs_matches_contract():
    """Device CSR constructor reproduces what the host repack builds."""
    g = gen.suite_graph("cube_12")
    gc_host, cmap = coarsen_once(g, seed=3)
    cu, cv, w, valid, n_runs, vwgt_c = coarsen.contract_edges(g, cmap)
    gc_dev = csr_from_edge_runs(cu, cv, w, valid, n_runs, vwgt_c,
                                jnp.asarray(int(gc_host.n), jnp.int32),
                                n_max=g.n_max, m_max=g.m_max)
    validate_host(gc_dev)
    n, m = int(gc_host.n), int(gc_host.m)
    assert int(gc_dev.n) == n and int(gc_dev.m) == m
    assert np.array_equal(np.asarray(gc_dev.xadj)[: n + 1],
                          np.asarray(gc_host.xadj)[: n + 1])
    for f in ("esrc", "adjncy", "adjwgt"):
        assert np.array_equal(np.asarray(getattr(gc_dev, f))[:m],
                              np.asarray(getattr(gc_host, f))[:m]), f


@pytest.mark.parametrize("name, ran", [("rmat_12", 1), ("grid_64x32", 0)])
def test_level_counters_match_a_recount(name, ran):
    """``coarsen_level``'s counters against a numpy recount from HEM's own
    ``match`` and the level's ``cmap``: a power-law level takes the
    two-hop branch, a mesh level does not."""
    g = gen.suite_graph(name)
    gcb, cmapb, stats = coarsen.coarsen_level(_lane(g), seed=3)
    gc, cmap = gr.unstack_graph(gcb, 0), cmapb[0]
    hem_unmatched, twohop, pairs = (int(x) for x in np.asarray(stats[0, 3:]))
    n = int(g.n)
    match = np.asarray(coarsen.heavy_edge_matching(g, seed=3))[:n]
    left = int(np.count_nonzero(match < 0))
    assert hem_unmatched == left
    assert twohop == int(np.float32(left) / np.float32(n)
                         > np.float32(0.25)) == ran
    members = np.bincount(np.asarray(cmap)[:n])
    assert members.shape[0] == int(gc.n) and set(members) <= {1, 2}
    hem_pairs = (n - left) // 2
    assert pairs == int(np.count_nonzero(members == 2)) - hem_pairs
    assert (pairs > 0) == bool(ran)
    # the counted level is the host path's level, bit for bit
    gc_host, cmap_host = coarsen_once(g, seed=3)
    assert np.array_equal(np.asarray(cmap)[:n], np.asarray(cmap_host)[:n])
    assert int(gc.m) == int(gc_host.m)


@pytest.mark.parametrize("name, twohop_everywhere", [
    ("grid_64x32", False), ("rmat_12", False), ("smallworld_4k", False),
    ("rmat_12", True),
])
def test_fleet_lane_equals_standalone(name, twohop_everywhere):
    """A graph run as lane 0 of a two-lane bucket, beside a small graph
    that freezes at once, equals its standalone ``partition()``: labels,
    cut, and every ``level_stats`` key, the coarsening counters included.
    On the power-law graph every counted level took the two-hop branch."""
    g = gen.suite_graph(name)
    cfg = PartitionConfig(k=8, coarse_target=256, max_iter=60, patience=6)
    solo = partition(g, cfg)
    small = gen.grid2d(8, 8).with_capacity(g.n_max, g.m_max)
    bucket = gr.StackedBucket(capacity=(g.n_max, g.m_max),
                              graph=gr.stack_graphs([g, small]),
                              tags=(0, 1), orig_n_max=(g.n_max, g.n_max))
    schedule = coarsen.shape_schedule(g.n_max, g.m_max)
    lane = partition_fleet_stacked([bucket], cfg, schedule).results[0]
    assert lane.cut == solo.cut
    np.testing.assert_array_equal(np.asarray(lane.parts),
                                  np.asarray(solo.parts))
    assert lane.level_stats == solo.level_stats
    counted = [ls for ls in lane.level_stats if "twohop" in ls]
    assert len(counted) >= len(lane.level_stats) - 1 >= 1
    assert all(set(coarsen.COUNTERS) <= set(ls) for ls in counted)
    if twohop_everywhere:
        assert all(ls["twohop"] == 1 for ls in counted)
