"""Batched best-of-N trials (DESIGN.md §9).

The load-bearing property: vmapping the uncoarsening phase over a trial
axis changes the SCHEDULE, never the VALUES — trial t of a batched run is
bit-identical to a sequential ``partition()`` run with that trial's seed,
on every backend.  Plus: device-side best-trial ordering, the fused
``uncoarsen_level`` against the legacy unfused sequence, and the
mask-aware voronoi seed guard.
"""
import re
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import coarsen as co
from repro.core import connectivity as cn
from repro.core import initial, metrics, refine
from repro.core.graph import build_csr_host, stack_graphs, unstack_graph
from repro.core.partition import (
    PartitionConfig, _best_trial, partition, uncoarsen_level,
)
from repro.data import graphs as gen

TRIALS = 3


def _cfg(backend, k, **kw):
    return PartitionConfig(k=k, backend=backend, coarse_target=48,
                           max_iter=30, patience=3, **kw)


@pytest.mark.parametrize("backend", ["dense", "sorted", "ell"])
@pytest.mark.parametrize("k", [2, 8, 33])
def test_vmapped_trials_bit_identical(backend, k):
    """Batched trial t == sequential run with seed t: full parts vectors."""
    g = gen.grid2d(12, 12)
    cfg = _cfg(backend, k, trials=TRIALS)
    res = partition(g, cfg)
    assert res.trial_parts.shape == (TRIALS, g.n_max)
    for t in range(TRIALS):
        seq = partition(g, replace(cfg, trials=1, trial_seeds=(cfg.seed + t,)))
        assert res.trial_cuts[t] == seq.cut, (backend, k, t)
        assert res.trial_balanced[t] == seq.balanced
        np.testing.assert_array_equal(
            np.asarray(res.trial_parts[t]), np.asarray(seq.parts)
        )
    # the selected best is one of the trials, reported consistently
    np.testing.assert_array_equal(
        np.asarray(res.parts), np.asarray(res.trial_parts[res.best_trial])
    )
    assert res.cut == res.trial_cuts[res.best_trial]


def test_best_trial_prefers_balanced_over_lower_cut():
    """A balanced trial supersedes an unbalanced one with a lower cut."""
    bal = jnp.asarray([False, True, True, False])
    cut = jnp.asarray([10, 90, 80, 5], jnp.int32)
    msz = jnp.asarray([900, 100, 100, 950], jnp.int32)
    assert int(_best_trial(bal, cut, msz)) == 2  # lowest cut among balanced
    # no balanced trial: lowest max part weight wins, cut breaks ties
    bal0 = jnp.zeros(4, bool)
    msz2 = jnp.asarray([300, 200, 200, 400], jnp.int32)
    assert int(_best_trial(bal0, cut, msz2)) == 2  # msz tie -> cut 80 < 90
    # deterministic first-index tie-break
    assert int(_best_trial(bal0, jnp.asarray([7, 7, 7, 7], jnp.int32),
                           jnp.asarray([5, 5, 5, 5], jnp.int32))) == 0


@pytest.mark.parametrize("backend", ["dense", "sorted"])
def test_uncoarsen_level_matches_unfused(backend):
    """The fused jitted level == the legacy project/mask/build/refine
    sequence, exactly, for every trial in the batch."""
    g = gen.grid2d(16, 16)
    k = 4
    levels = co.multilevel_coarsen(stack_graphs([g]), coarse_target=64,
                                   seed=0)
    assert len(levels) >= 2
    fine, coarse = levels[-2], levels[-1]
    fine_g, coarse_g = (unstack_graph(lv.graph, 0) for lv in (fine, coarse))
    seeds = (0, 5)
    parts_b = initial.initial_partition_batch(coarse.graph, k, seeds)
    kw = dict(k=k, lam=0.03, c=0.75, backend=backend, patience=4,
              max_iter=40, b_max=2, variant="full", rebuild_every=0)
    fused_b, stats_b = uncoarsen_level(
        fine.graph, fine.cmap, parts_b, None, 0.999, **kw
    )
    for t, seed in enumerate(seeds):
        pc = initial.initial_partition(coarse_g, k, seed=seed)
        np.testing.assert_array_equal(np.asarray(parts_b[0, t]),
                                      np.asarray(pc))
        # legacy unfused path: project -> mask -> build_state -> jet_refine
        pf = co.project_partition(fine.cmap[0], pc)
        pf = jnp.where(fine_g.vertex_mask(), pf, k).astype(jnp.int32)
        conn0 = cn.build_state(fine_g, pf, k, backend)
        ref, ref_stats = refine.jet_refine(
            fine_g, pf, k, lam=0.03, c=0.75, phi=0.999, backend=backend,
            patience=4, max_iter=40, b_max=2, conn0=conn0,
        )
        np.testing.assert_array_equal(np.asarray(fused_b[0, t]),
                                      np.asarray(ref))
        for kk in ref_stats:
            assert int(stats_b[kk][0, t]) == int(ref_stats[kk]), (kk, t)


@pytest.mark.parametrize("backend", ["dense", "ell"])
def test_uncoarsen_level_conds_only_unbatched(backend):
    """At T=1 the level program keeps Alg 4.1's two ``lax.cond``s (the
    balance switch and weak-or-strong), so an iteration runs only the move
    kind it takes; at T=2 the batched predicates turn both into selects."""
    g = gen.grid2d(16, 16)
    k = 4
    levels = co.multilevel_coarsen(stack_graphs([g]), coarse_target=64,
                                   seed=0)
    fine, coarse = levels[-2], levels[-1]
    max_degree = (int(fine.stats["max_degree"][0]) if backend == "ell"
                  else None)
    kw = dict(k=k, lam=0.03, c=0.75, backend=backend, patience=4,
              max_iter=40, b_max=2, variant="full", rebuild_every=0,
              max_degree=max_degree)
    conds = {}
    for T in (1, 2):
        parts_b = initial.initial_partition_batch(coarse.graph, k,
                                                  tuple(range(T)))
        jaxpr = jax.make_jaxpr(
            lambda f, cm, pb: uncoarsen_level(f, cm, pb, None, 0.999, **kw)
        )(fine.graph, fine.cmap, parts_b)
        conds[T] = len(re.findall(r"\bcond\[", str(jaxpr)))
    assert conds == {1: 2, 2: 0}, conds


def test_coarsen_level_cond_only_unbatched():
    """A bucket of one lane coarsens unbatched, so the two-hop trigger stays
    a ``lax.cond`` and a mesh level skips the two-hop pass; across two
    lanes the batched predicate turns it into a select."""
    g = gen.grid2d(16, 16)
    conds = {}
    for B in (1, 2):
        jaxpr = jax.make_jaxpr(lambda gb: co.coarsen_level(gb, seed=0))(
            stack_graphs([g] * B))
        conds[B] = len(re.findall(r"\bcond\[", str(jaxpr)))
    assert conds == {1: 1, 2: 0}, conds


@pytest.mark.parametrize("backend", ["dense", "sorted", "ell"])
@pytest.mark.parametrize("k", [2, 33])
def test_single_trial_level_stats_match_batched(backend, k):
    """The unbatched T=1 path reports, level by level, the same stats
    (``rs_iters`` included) as trial 0 of a T=2 batch with the same seeds."""
    g = gen.grid2d(12, 12)
    cfg = _cfg(backend, k, trials=2, trial_seeds=(4, 9))
    batched = partition(g, cfg)
    single = partition(g, replace(cfg, trials=1, trial_seeds=(4,)))
    assert len(single.level_stats) == len(batched.level_stats)
    for lv1, lv2 in zip(single.level_stats, batched.level_stats):
        assert "rs_iters" in lv1
        assert 0 <= lv1["rs_iters"] <= lv1["rb_iters"]
        for key, val in lv1.items():
            got = lv2[key][0] if isinstance(lv2[key], list) else lv2[key]
            assert got == val, (backend, k, lv1["level"], key)


def test_voronoi_seeds_mask_aware():
    """Seeds never land on padding while real vertices remain; a k > n
    shortfall round-robins over real ids, deterministically."""
    n = 6
    edges = np.stack([np.arange(n - 1), np.arange(1, n)], 1)
    g = build_csr_host(n, edges, n_max=64, m_max=64)
    for k in (2, 4, 6):
        seeds = np.asarray(initial.spread_seeds(g, k, seed=3))
        assert seeds.shape == (k,)
        assert (seeds < n).all(), (k, seeds)
        assert len(set(seeds.tolist())) == k  # spread, not collapsed
    # shortfall: k=8 > n=6 — padded picks are replaced round-robin
    seeds = np.asarray(initial.spread_seeds(g, 8, seed=3))
    assert (seeds < n).all()
    parts = np.asarray(initial.voronoi_partition(g, 8, seed=3))
    assert (parts[:n] < 8).all() and (parts[n:] == 8).all()
    # deterministic across calls
    np.testing.assert_array_equal(
        seeds, np.asarray(initial.spread_seeds(g, 8, seed=3))
    )
    # k beyond even the PADDED capacity (k > n_max): the shortfall still
    # round-robins over real ids instead of raising a shape error
    tiny = build_csr_host(n, edges)  # n_max == n == 6
    seeds = np.asarray(initial.spread_seeds(tiny, 9, seed=3))
    assert seeds.shape == (9,) and (seeds < n).all()
    parts = np.asarray(initial.voronoi_partition(tiny, 9, seed=3))
    assert (parts[:n] < 9).all()


def test_initial_partition_batch_matches_scalar():
    g = gen.grid2d(10, 10)
    seeds = (0, 1, 7)
    for method in ("voronoi", "random"):
        batch = initial.initial_partition_batch(stack_graphs([g]), 5, seeds,
                                                method=method)
        for t, s in enumerate(seeds):
            np.testing.assert_array_equal(
                np.asarray(batch[0, t]),
                np.asarray(initial.initial_partition(g, 5, seed=s,
                                                     method=method)),
            )


def test_trials_one_keeps_legacy_result_shape():
    """trials=1 stays the legacy scalar contract: int stats per level."""
    g = gen.grid2d(12, 12)
    res = partition(g, _cfg("dense", 4))
    assert res.trials == 1 and res.best_trial == 0
    assert res.trial_cuts == [res.cut]
    for st in res.level_stats:
        assert isinstance(st["iterations"], int)
        assert isinstance(st["best_cost"], int)
