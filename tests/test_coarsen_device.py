"""Device-resident coarsening (DESIGN.md §8): equivalence with the legacy
host-repack path, shape-schedule mechanics, and capacity re-bucketing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import coarsen
from repro.core.graph import csr_from_edge_runs, validate_host
from repro.core.partition import PartitionConfig, partition
from repro.data import graphs as gen

FAMILIES = ["grid_64x32", "rmat_12", "smallworld_4k"]


def test_coarsen_level_traces_with_no_host_transfers():
    """The whole level — matching, two-hop cond, contraction, CSR build —
    must stage to one pure jaxpr: any host sync would fail tracing."""
    g = gen.suite_graph("grid_64x32")
    jaxpr = jax.make_jaxpr(
        lambda gg, s: coarsen.coarsen_level(gg, seed=s)
    )(g, jnp.int32(0))
    assert "callback" not in str(jaxpr)


@pytest.mark.parametrize("name", FAMILIES)
def test_device_hierarchy_matches_host(name):
    g = gen.suite_graph(name)
    dev = coarsen.multilevel_coarsen(g, coarse_target=256, mode="device")
    host = coarsen.multilevel_coarsen(g, coarse_target=256, mode="host")
    assert len(dev) == len(host) and len(dev) >= 2
    for a, b in zip(dev, host):
        # same true sizes, same tight content — padding may differ
        assert (a.stats["n"], a.stats["m"]) == (b.stats["n"], b.stats["m"])
        n, m = a.stats["n"], a.stats["m"]
        for f in ("esrc", "adjncy", "adjwgt"):
            assert np.array_equal(np.asarray(getattr(a.graph, f))[:m],
                                  np.asarray(getattr(b.graph, f))[:m]), f
        assert np.array_equal(np.asarray(a.graph.vwgt)[:n],
                              np.asarray(b.graph.vwgt)[:n])
        assert np.array_equal(np.asarray(a.graph.xadj)[: n + 1],
                              np.asarray(b.graph.xadj)[: n + 1])
        validate_host(a.graph)
        if a.cmap is not None:
            assert np.array_equal(np.asarray(a.cmap)[:n_prev(a)],
                                  np.asarray(b.cmap)[:n_prev(a)])


def n_prev(level):
    return level.stats["n"]


@pytest.mark.parametrize("name", FAMILIES)
def test_partition_cut_matches_host(name):
    g = gen.suite_graph(name)
    cuts = {}
    for mode in ("device", "host"):
        cfg = PartitionConfig(k=8, coarse_target=256, max_iter=60,
                              patience=6, coarsen_mode=mode)
        cuts[mode] = partition(g, cfg).cut
    assert cuts["device"] == cuts["host"], cuts


def test_device_levels_shrink_capacity():
    g = gen.suite_graph("grid_64x32")
    dev = coarsen.multilevel_coarsen(g, coarse_target=128, mode="device")
    caps = [(lv.stats["n_max"], lv.stats["m_max"]) for lv in dev]
    assert caps[-1][0] < caps[0][0] and caps[-1][1] < caps[0][1], caps
    for lv in dev:
        assert lv.stats["n"] <= lv.stats["n_max"]
        assert lv.stats["m"] <= lv.stats["m_max"]


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
        coarsen.multilevel_coarsen(g, mode="device", schedule=bad)


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
    gc_host, cmap = coarsen.coarsen_once(g, seed=3)
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
    gc, cmap, counts = coarsen.coarsen_level(g, seed=3)
    hem_unmatched, twohop, pairs = (int(x) for x in np.asarray(counts))
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
    gc_host, cmap_host = coarsen.coarsen_once(g, seed=3)
    assert np.array_equal(np.asarray(cmap)[:n], np.asarray(cmap_host)[:n])
    assert int(gc.m) == int(gc_host.m)


def test_counted_partition_is_the_host_paths_on_a_power_law_graph():
    """Labels and cut with the counters (device path) are bit-identical to
    the host path's, which counts nothing; every level but the coarsest
    carries its counters into ``level_stats``."""
    g = gen.suite_graph("rmat_12")
    res = {mode: partition(g, PartitionConfig(
        k=8, coarse_target=256, max_iter=60, patience=6, coarsen_mode=mode))
        for mode in ("device", "host")}
    assert res["device"].cut == res["host"].cut
    assert np.array_equal(np.asarray(res["device"].parts),
                          np.asarray(res["host"].parts))
    dev = res["device"].level_stats  # coarsest first
    assert all(set(coarsen.COUNTERS) <= set(ls) for ls in dev[1:])
    assert all(ls["twohop"] == 1 for ls in dev[1:])
    assert not any("twohop" in ls for ls in res["host"].level_stats)
