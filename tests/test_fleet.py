"""Fleet partitioning (DESIGN.md §10): vmapped V-cycles over shape buckets.

The load-bearing property mirrors §9's: batching whole graphs changes the
SCHEDULE, never the VALUES — every fleet member's parts vector, cut, and
per-trial stats are bit-identical to its standalone ``partition()`` run,
on every backend, under mixed bucket occupancy (graphs of different true
sizes sharing one capacity bucket) and per-graph coarsening depths.
Plus: the bucketing policy itself, stack/unstack round-trips, the CLI's
nonzero exit on unbalanced selections, and the CI quality gate's
regression detection.
"""
import json

import numpy as np
import pytest

from repro.core import graph as gr
from repro.core.partition import PartitionConfig, partition, partition_fleet
from repro.data import graphs as gen

# grid 13x13 and 12x12 round to one capacity rung (mixed occupancy);
# grid 8x8 lands in its own smaller bucket
FLEET = ((13, 13), (12, 12), (8, 8))


def _fleet_graphs():
    return [gen.grid2d(a, b) for a, b in FLEET]


def _cfg(backend, k, **kw):
    return PartitionConfig(k=k, backend=backend, coarse_target=48,
                           max_iter=30, patience=3, **kw)


@pytest.mark.parametrize("backend", ["dense", "sorted", "ell"])
@pytest.mark.parametrize("k", [2, 8, 33])
def test_fleet_bit_identical_to_standalone(backend, k):
    """Fleet member i == standalone partition(graphs[i]): parts, cut,
    balance, level count — for every k, on every backend."""
    graphs = _fleet_graphs()
    cfg = _cfg(backend, k)
    fres = partition_fleet(graphs, cfg)
    assert len(fres.results) == len(graphs)
    # mixed occupancy must actually happen: the two big grids share a bucket
    sizes = {len(b.indices) for b in fres.buckets}
    assert 2 in sizes, [b.indices for b in fres.buckets]
    for i, g in enumerate(graphs):
        solo = partition(g, cfg)
        fleet = fres.results[i]
        assert fleet.cut == solo.cut, (backend, k, i)
        assert fleet.balanced == solo.balanced
        assert fleet.levels == solo.levels
        assert fleet.parts.shape == solo.parts.shape
        np.testing.assert_array_equal(
            np.asarray(fleet.parts), np.asarray(solo.parts)
        )


def test_fleet_composes_with_trials():
    """B graphs × T trials in one program: per-trial cuts and the selected
    best match the standalone trials run, per member."""
    graphs = _fleet_graphs()
    cfg = _cfg("dense", 8, trials=2)
    fres = partition_fleet(graphs, cfg)
    for i, g in enumerate(graphs):
        solo = partition(g, cfg)
        fleet = fres.results[i]
        assert fleet.trial_cuts == solo.trial_cuts, i
        assert fleet.trial_balanced == solo.trial_balanced
        assert fleet.best_trial == solo.best_trial
        assert fleet.cut == solo.cut
        np.testing.assert_array_equal(
            np.asarray(fleet.parts), np.asarray(solo.parts)
        )
        # trial_parts honor the standalone contract: same shape as the
        # caller's padding, rows bit-equal to the solo batch
        assert fleet.trial_parts.shape == solo.trial_parts.shape
        np.testing.assert_array_equal(
            np.asarray(fleet.trial_parts), np.asarray(solo.trial_parts)
        )


def test_bucket_graphs_policy():
    """Near-sized graphs share a rung pair; distinct sizes split; every
    graph fits its assigned capacity."""
    graphs = _fleet_graphs()
    schedule, buckets = gr.bucket_graphs(graphs)
    assert sum(len(v) for v in buckets.values()) == len(graphs)
    assigned = {i: cap for cap, idxs in buckets.items() for i in idxs}
    assert assigned[0] == assigned[1] != assigned[2]
    for i, g in enumerate(graphs):
        n_cap, m_cap = assigned[i]
        assert int(g.n) <= n_cap and int(g.m) <= m_cap
        assert (n_cap, m_cap) in [
            (nc, mc)
            for nc, _ in schedule for _, mc in schedule
        ]


def test_stack_unstack_roundtrip():
    g1 = gen.grid2d(6, 6)
    g2 = gen.grid2d(5, 5).with_capacity(g1.n_max, g1.m_max)
    gb = gr.stack_graphs([g1, g2])
    assert gb.vwgt.shape == (2, g1.n_max)
    assert gb.xadj.shape == (2, g1.n_max + 1)
    for b, g in enumerate((g1, g2)):
        back = gr.unstack_graph(gb, b)
        for leaf, orig in zip(back, g):
            np.testing.assert_array_equal(np.asarray(leaf), np.asarray(orig))
    with pytest.raises(ValueError):
        gr.stack_graphs([g1, gen.grid2d(5, 5)])


def test_fleet_overpadded_member():
    """A member padded far beyond its bucket capacity gets its results
    padded back to its own n_max (parts and trial_parts alike)."""
    from repro.core.graph import build_csr_host, graph_to_host

    g_small = gen.grid2d(8, 8)
    n, edges, ew, vw = graph_to_host(g_small)
    g_over = build_csr_host(n, edges, ew, vw, n_max=1024, m_max=1024)
    graphs = [gen.grid2d(13, 13), g_over]
    cfg = _cfg("dense", 4, trials=2)
    fres = partition_fleet(graphs, cfg)
    res = fres.results[1]
    assert res.parts.shape == (1024,)
    assert res.trial_parts.shape == (2, 1024)
    solo = partition(g_over, cfg)
    assert res.cut == solo.cut
    np.testing.assert_array_equal(np.asarray(res.parts),
                                  np.asarray(solo.parts))
    assert (np.asarray(res.parts)[n:] == 4).all()  # ghost part beyond n


def test_fleet_rejects_empty():
    with pytest.raises(ValueError):
        partition_fleet([], _cfg("dense", 4))


def test_cli_exits_nonzero_on_unbalanced(monkeypatch, capsys):
    """The CLI must fail loudly (nonzero + stderr reason) when the selected
    partition misses the balance constraint, so CI/fleet callers can gate
    on the return code."""
    from dataclasses import replace

    from repro.launch import partition_cli as cli

    real_partition = cli.partition

    def unbalanced_partition(g, cfg):
        res = real_partition(g, cfg)
        return replace(res, balanced=False, imbalance=0.5)

    monkeypatch.setattr(cli, "partition", unbalanced_partition)
    rc = cli.main(["--graph", "grid", "--size", "8", "--k", "2",
                   "--coarse-target", "16"])
    assert rc == 1
    assert "unbalanced" in capsys.readouterr().err
    # the escape hatch keeps the old always-zero behaviour available
    monkeypatch.setattr(cli, "partition", real_partition)
    rc = cli.main(["--graph", "grid", "--size", "8", "--k", "2",
                   "--coarse-target", "16"])
    assert rc == 0


def test_cli_fleet_mode(capsys):
    from repro.launch import partition_cli as cli

    rc = cli.main(["--fleet", "grid:8", "grid:7", "--k", "2",
                   "--coarse-target", "16", "--allow-unbalanced"])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert len(report["fleet"]) == 2
    assert {m for b in report["buckets"] for m in b["members"]} == {0, 1}
    for entry in report["fleet"]:
        assert entry["cut"] > 0


def test_check_baseline_gate():
    """The CI quality gate passes on identical numbers and fails on an
    injected cut/balance regression."""
    from benchmarks.bench_partitioner import compare_baseline

    base = {
        "baseline_tolerance": 0.05,
        "trials_ab": {"smoke": {"best_cut": 36, "trial_cuts": [36, 43]}},
        "fleet_ab": {"smoke": {"cuts": {"g16": 36}, "balanced": {"g16": True}}},
    }
    fresh = json.loads(json.dumps(base))
    assert compare_baseline(fresh, base) == []
    # within tolerance: still passes
    fresh["trials_ab"]["smoke"]["best_cut"] = 37
    assert compare_baseline(fresh, base) == []
    # injected cut regression: fails
    fresh["trials_ab"]["smoke"]["best_cut"] = 45
    bad = compare_baseline(fresh, base)
    assert bad and "trials_ab/smoke/best_cut" in bad[0]
    # injected balance regression: fails
    fresh["trials_ab"]["smoke"]["best_cut"] = 36
    fresh["fleet_ab"]["smoke"]["balanced"]["g16"] = False
    bad = compare_baseline(fresh, base)
    assert bad and "balanced" in bad[0]
    # a dropped/renamed smoke metric is itself a gate failure
    fresh = json.loads(json.dumps(base))
    del fresh["fleet_ab"]["smoke"]["cuts"]["g16"]
    bad = compare_baseline(fresh, base)
    assert bad and "missing from the fresh run" in bad[0]
    # incomparable reports never pass vacuously
    assert compare_baseline({}, base)
