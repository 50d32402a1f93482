"""Table 1/2 + Fig 1-style: end-to-end partitioner quality & time breakdown.

Compares the full Jet partitioner against the same multilevel driver with
size-constrained-LP refinement (our implementable stand-in for the LP-based
competitors), across k and imbalance settings, and reports the paper's
Table 2 phase breakdown (coarsen / initial partition / uncoarsen).

Also the batched-trials A/B (DESIGN.md §9): a sequential T-loop vs one vmapped best-of-T batch, gated
on per-trial cut equivalence and on the compile count (one
``uncoarsen_level`` executable per capacity-rung signature regardless of
T), and the fleet A/B (DESIGN.md §10): a sequential per-graph loop vs one
shape-bucketed batched fleet, gated on per-graph bit-equivalence and the
per-(rung, batch)-signature executable count.  All written to
``BENCH_partitioner.json``.

``--check-baseline`` is the CI quality-regression gate: it re-runs the
smoke suite into a fresh JSON and exits nonzero when any smoke cut grows
past the baseline's tolerance tag (or a baseline-balanced member goes
unbalanced).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.graphs_suite import SUITE, load
from repro.core import coarsen as co
from repro.core import graph as gr
from repro.core import initial, metrics
from repro.core.lp_baseline import constrained_lp_refine
from repro.core.partition import (
    PartitionConfig, partition, partition_fleet, uncoarsen_level,
)


def _balance_only(g, parts, k, lam):
    """Shared rebalancing (CLP has none; the paper's effectiveness protocol
    likewise hands every refiner a balanced input)."""
    from repro.core import rebalance as rb

    W = g.total_vweight()
    for it in range(k + 4):
        sizes = metrics.part_sizes(g, parts, k)
        if bool(metrics.is_balanced(sizes, W, k, lam)):
            return parts
        fn = rb.jetrw_moves if it < 2 else rb.jetrs_moves
        move, dest = fn(g, parts, k, lam)
        parts = jnp.where(move, dest, parts)
    return parts


def _clp_multilevel(g, k, lam, seed):
    """Same multilevel pipeline, constrained-LP refinement instead of Jet
    (both get balanced inputs at every level; the variable under test is
    the LP-vs-Jetlp cut optimization)."""
    levels = co.multilevel_coarsen(gr.stack_graphs([g]),
                                   coarse_target=max(1024, 8 * k), seed=seed)
    graphs = [gr.unstack_graph(lv.graph, 0) for lv in levels]
    parts = initial.initial_partition(graphs[-1], k, seed=seed)
    for i in range(len(levels) - 1, -1, -1):
        gi = graphs[i]
        parts = _balance_only(gi, parts, k, lam)
        parts, _ = constrained_lp_refine(gi, parts, k, lam=lam, iters=24)
        if i > 0:
            parts = co.project_partition(levels[i - 1].cmap[0], parts)
            parts = jnp.where(graphs[i - 1].vertex_mask(), parts, k)
    return _balance_only(g, parts, k, lam)


def quality(ks=(8, 32), lams=(0.03,), seeds=(0,), quick=False):
    names = list(SUITE) if not quick else ["grid", "rmat"]
    if quick:
        ks, seeds = (8,), (0,)
    rows = []
    for k in ks:
        for lam in lams:
            ratios = []
            for name in names:
                g = load(name)
                jax.clear_caches()
                for seed in seeds:
                    cfg = PartitionConfig(k=k, lam=lam, seed=seed,
                                          coarse_target=max(1024, 8 * k))
                    jet = partition(g, cfg)
                    clp_parts = _clp_multilevel(g, k, lam, seed)
                    clp_cut = int(metrics.cutsize(g, clp_parts))
                    ratios.append(clp_cut / max(jet.cut, 1))
            gm = float(np.exp(np.mean(np.log(ratios))))
            rows.append((f"partitioner/clp_over_jet_k{k}_lam{lam}", gm))
    return rows


def time_breakdown(quick=False):
    names = list(SUITE) if not quick else ["grid"]
    rows = []
    for name in names:
        g = load(name)
        cfg = PartitionConfig(k=16, lam=0.03, coarse_target=1024)
        res = partition(g, cfg)
        tot = res.times["total_s"]
        rows.append((f"breakdown/{name}/coarsen_pct",
                     100 * res.times["coarsen_s"] / tot))
        rows.append((f"breakdown/{name}/uncoarsen_pct",
                     100 * res.times["uncoarsen_s"] / tot))
        rows.append((f"breakdown/{name}/total_s", tot))
    return rows


def _rung_signatures(res):
    """Distinct uncoarsen_level compile signatures a run must have hit:
    (fine n_max, fine m_max, coarse n_max, c-ratio) plus, on the ELL
    backend, the per-level static max_degree (it sizes the ELL arrays, so
    it is part of the jit key).  level_stats is ordered coarsest first;
    the coarsest call projects through the identity cmap (its own
    capacity)."""
    cfg = res.config
    sigs = set()
    for j, st in enumerate(res.level_stats):
        nc = st["n_max"] if j == 0 else res.level_stats[j - 1]["n_max"]
        c = cfg.c_finest if st["level"] == 0 else cfg.c_coarse
        md = st.get("max_degree") if cfg.backend == "ell" else None
        sigs.add((st["n_max"], st["m_max"], nc, c, md))
    return sigs


def trials_ab(names=None, k=8, trials=4, coarse_target=512, cfg_extra=None):
    """Sequential T-loop vs one vmapped best-of-T batch (DESIGN.md §9).

    Gates: (1) every vmapped trial's cut is bit-identical to the sequential
    run with that trial's seed; (2) the selected best-of-T cut is <= every
    balanced single-trial cut; (3) the batched run compiles exactly one
    ``uncoarsen_level`` executable per capacity-rung signature — T rides
    the batch axis, it never multiplies executables.
    """
    if names is None:
        names = list(SUITE)
    graphs = {n: load(n) for n in names} if isinstance(names, list) else names
    out = {}
    for name, g in graphs.items():
        base = dict(k=k, coarse_target=coarse_target, **(cfg_extra or {}))
        jax.clear_caches()
        t0 = time.perf_counter()
        seq = [
            partition(g, PartitionConfig(**base, trials=1, trial_seeds=(t,)))
            for t in range(trials)
        ]
        seq_cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for t in range(trials):
            partition(g, PartitionConfig(**base, trials=1, trial_seeds=(t,)))
        seq_warm_s = time.perf_counter() - t0

        jax.clear_caches()
        cfg_b = PartitionConfig(**base, trials=trials,
                                trial_seeds=tuple(range(trials)))
        execs0 = uncoarsen_level._cache_size()
        t0 = time.perf_counter()
        res = partition(g, cfg_b)
        bat_cold_s = time.perf_counter() - t0
        execs = uncoarsen_level._cache_size() - execs0
        t0 = time.perf_counter()
        partition(g, cfg_b)
        bat_warm_s = time.perf_counter() - t0

        # gate 1: per-trial cut equivalence, bit-identical
        for t in range(trials):
            if res.trial_cuts[t] != seq[t].cut:
                raise AssertionError(
                    f"{name}: vmapped trial {t} cut {res.trial_cuts[t]} != "
                    f"sequential cut {seq[t].cut}"
                )
        # gate 2: best-of-T never loses to a balanced single trial
        bal_cuts = [s.cut for s in seq if s.balanced]
        if bal_cuts and res.cut > min(bal_cuts):
            raise AssertionError(
                f"{name}: best-of-{trials} cut {res.cut} > best sequential "
                f"balanced cut {min(bal_cuts)}"
            )
        # gate 3: one executable per rung signature, regardless of T
        expected = len(_rung_signatures(res))
        if execs != expected:
            raise AssertionError(
                f"{name}: {execs} uncoarsen_level executables for "
                f"{expected} rung signatures — trial batching must not "
                f"multiply compiles"
            )
        out[name] = {
            "trials": trials,
            "trial_cuts": res.trial_cuts,
            "best_trial": res.best_trial,
            "best_cut": res.cut,
            "single_trial_cut": seq[0].cut,
            "seq_cold_s": seq_cold_s,
            "seq_warm_s": seq_warm_s,
            "batch_cold_s": bat_cold_s,
            "batch_warm_s": bat_warm_s,
            "warm_speedup": seq_warm_s / max(bat_warm_s, 1e-9),
            "rung_executables": execs,
        }
    return out


def _fleet_signatures(fres):
    """Distinct ``uncoarsen_level`` compile signatures a fleet run must
    have hit: (B, T, fine n_max, fine m_max, nc_max, c-ratio, ell width,
    masked).  The same counting rule as :func:`_rung_signatures`, extended
    by the batch shape and by whether some lane is frozen at the level (its
    program then carries the lanes' refine flags) — two buckets with equal
    B and equal rungs SHARE executables, which is the point of the
    shape-bucketed fleet."""
    cfg = fres.config
    sigs = set()
    for b in fres.buckets:
        B = len(b.indices)
        for j, st in enumerate(b.level_stats):
            nc = st["n_max"] if j == 0 else b.level_stats[j - 1]["n_max"]
            c = cfg.c_finest if st["level"] == 0 else cfg.c_coarse
            md = st.get("ell_width") if cfg.backend == "ell" else None
            sigs.add((B, fres.trials, st["n_max"], st["m_max"], nc, c, md,
                      not st["active"].all()))
    return sigs


def fleet_ab(graphs=None, k=8, trials=1, coarse_target=512, cfg_extra=None):
    """Sequential per-graph loop vs one shape-bucketed batched fleet
    (DESIGN.md §10).

    Gates: (1) every fleet member's cut, balance flag, and per-trial cuts
    are bit-identical to its standalone ``partition()`` run; (2) the fleet
    compiles exactly one ``uncoarsen_level`` executable per (rung,
    batch) signature — B and T ride batch axes, they never multiply
    executables; (3) the fleet exercises mixed bucket occupancy (some
    bucket holds graphs of different true sizes).
    """
    if graphs is None:
        from repro.data import graphs as gen

        # mixed sizes on purpose: grid96/grid90 round to a shared capacity
        # rung (mixed bucket occupancy), grid48 lands in its own bucket
        graphs = {
            "grid96": gen.grid2d(96, 96),
            "grid90": gen.grid2d(90, 90),
            "grid48": gen.grid2d(48, 48),
        }
    names = list(graphs)
    glist = [graphs[n] for n in names]
    base = dict(k=k, coarse_target=coarse_target, trials=trials,
                **(cfg_extra or {}))

    jax.clear_caches()
    t0 = time.perf_counter()
    seq = [partition(g, PartitionConfig(**base)) for g in glist]
    seq_cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for g in glist:
        partition(g, PartitionConfig(**base))
    seq_warm_s = time.perf_counter() - t0

    jax.clear_caches()
    execs0 = uncoarsen_level._cache_size()
    t0 = time.perf_counter()
    fres = partition_fleet(glist, PartitionConfig(**base))
    fleet_cold_s = time.perf_counter() - t0
    execs = uncoarsen_level._cache_size() - execs0
    t0 = time.perf_counter()
    partition_fleet(glist, PartitionConfig(**base))
    fleet_warm_s = time.perf_counter() - t0

    # gate 1: per-graph bit-equivalence with the standalone runs
    for i, name in enumerate(names):
        fr, sr = fres.results[i], seq[i]
        if (fr.cut, fr.balanced, fr.trial_cuts) != \
                (sr.cut, sr.balanced, sr.trial_cuts):
            raise AssertionError(
                f"fleet/{name}: batched run diverged — fleet "
                f"(cut={fr.cut}, balanced={fr.balanced}, "
                f"trial_cuts={fr.trial_cuts}) vs standalone "
                f"(cut={sr.cut}, balanced={sr.balanced}, "
                f"trial_cuts={sr.trial_cuts})"
            )
    # gate 2: one executable per (rung, batch) signature
    expected = len(_fleet_signatures(fres))
    if execs != expected:
        raise AssertionError(
            f"{execs} uncoarsen_level executables for {expected} "
            "bucket-rung signatures — fleet batching must not multiply "
            "compiles"
        )
    # gate 3: the fleet must actually exercise mixed bucket occupancy
    mixed = any(len(b.indices) >= 2 for b in fres.buckets)
    if len(glist) >= 3 and not mixed:
        raise AssertionError(
            "no bucket holds >= 2 graphs — pick fleet members whose sizes "
            "round to a shared capacity rung"
        )
    return {
        "members": names,
        "cuts": {n: fres.results[i].cut for i, n in enumerate(names)},
        "balanced": {n: fres.results[i].balanced
                     for i, n in enumerate(names)},
        "buckets": [
            {"capacity": list(b.capacity),
             "members": [names[i] for i in b.indices],
             "levels": b.levels}
            for b in fres.buckets
        ],
        "trials": trials,
        "seq_cold_s": seq_cold_s,
        "seq_warm_s": seq_warm_s,
        "fleet_cold_s": fleet_cold_s,
        "fleet_warm_s": fleet_warm_s,
        "warm_speedup": seq_warm_s / max(fleet_warm_s, 1e-9),
        "bucket_executables": execs,
    }


# ---------------------------------------------------------------------------
# CI quality-regression gate (--check-baseline)
# ---------------------------------------------------------------------------

BASELINE_TOLERANCE = 0.05  # default: a cut may grow by at most 5%


def _cut_metrics(report):
    """Flatten the quality-critical numbers of a bench report:
    ``{metric_path: (cut value | balanced flag)}``."""
    cuts, balanced = {}, {}
    for name, rec in report.get("trials_ab", {}).items():
        cuts[f"trials_ab/{name}/best_cut"] = rec["best_cut"]
        for t, c in enumerate(rec.get("trial_cuts", [])):
            cuts[f"trials_ab/{name}/trial{t}/cut"] = c
    for name, rec in report.get("fleet_ab", {}).items():
        for gname, c in rec.get("cuts", {}).items():
            cuts[f"fleet_ab/{name}/{gname}/cut"] = c
        for gname, b in rec.get("balanced", {}).items():
            balanced[f"fleet_ab/{name}/{gname}/balanced"] = b
    return cuts, balanced


def compare_baseline(fresh, baseline, tolerance=None):
    """Quality-regression check: fresh smoke numbers vs the committed
    baseline.  Returns a list of human-readable regression strings (empty
    == gate passes).  Only metrics present in BOTH reports are compared;
    the baseline may carry its own tolerance tag (``baseline_tolerance``),
    which ``tolerance`` overrides when given."""
    tol = tolerance if tolerance is not None else \
        baseline.get("baseline_tolerance", BASELINE_TOLERANCE)
    fresh_cuts, fresh_bal = _cut_metrics(fresh)
    base_cuts, base_bal = _cut_metrics(baseline)
    bad = []
    # every baseline SMOKE metric must still exist in the fresh run — a
    # renamed/dropped smoke entry would otherwise silently leave the gate
    # (full-run entries in the baseline are legitimately absent from a
    # smoke-only fresh report, so only /smoke keys are required)
    for key in sorted(k for k in set(base_cuts) | set(base_bal)
                      if "/smoke" in k):
        if key not in fresh_cuts and key not in fresh_bal:
            bad.append(
                f"{key}: present in baseline but missing from the fresh "
                "run — smoke metrics may not be dropped or renamed without "
                "regenerating the baseline"
            )
    for key in sorted(set(fresh_cuts) & set(base_cuts)):
        allowed = base_cuts[key] * (1.0 + tol)
        if fresh_cuts[key] > allowed:
            bad.append(
                f"{key}: cut {fresh_cuts[key]} exceeds baseline "
                f"{base_cuts[key]} by more than {100 * tol:.1f}%"
            )
    for key in sorted(set(fresh_bal) & set(base_bal)):
        if base_bal[key] and not fresh_bal[key]:
            bad.append(f"{key}: baseline was balanced, fresh run is not")
    common = (set(fresh_cuts) & set(base_cuts)) | \
        (set(fresh_bal) & set(base_bal))
    if not common:
        bad.append(
            "no comparable metrics between fresh report and baseline — "
            "the gate would pass vacuously; regenerate the baseline"
        )
    return bad


def check_baseline(baseline_path="BENCH_partitioner.json",
                   json_path="BENCH_partitioner.fresh.json",
                   tolerance=None,
                   serve_baseline_path="BENCH_serve.json",
                   serve_fresh_path=None):
    """Run the smoke suite fresh, then gate cut/balance against the
    committed baseline.  Returns a process exit code.

    When a serving baseline (``BENCH_serve.json``) is committed, the gate
    also covers the §11 serving path: throughput and batch occupancy from
    a fresh serve smoke are compared under the baseline's tolerance tags.
    ``serve_fresh_path`` reuses an existing fresh serve report (the CI
    serve-smoke job's artifact) instead of replaying the burst again; by
    default the dense-backend smoke is re-run here.
    """
    import os

    try:
        with open(baseline_path) as f:
            baseline = json.load(f)
    except (OSError, ValueError) as e:
        print(f"cannot read baseline {baseline_path}: {e}")
        return 2
    # start from an EMPTY fresh report: a stale json at json_path would
    # merge never-re-run numbers into the comparison and mask regressions
    try:
        os.remove(json_path)
    except OSError:
        pass
    # a fresh smoke pass across both A/Bs, merged into json_path
    main(smoke=True, json_path=json_path, trials=2)
    fresh = main(smoke=True, json_path=json_path, fleet=True)
    regressions = compare_baseline(fresh, baseline, tolerance=tolerance)

    # serving-path gate (bench_serve): same pattern — committed baseline,
    # fresh numbers, tolerance tags from the baseline JSON
    serve_baseline = None
    try:
        with open(serve_baseline_path) as f:
            serve_baseline = json.load(f)
    except (OSError, ValueError):
        print(f"no serving baseline at {serve_baseline_path} — "
              "serve gate skipped")
    if serve_baseline is not None:
        from benchmarks.bench_serve import compare_serve_baseline, serve_smoke

        if serve_fresh_path and os.path.exists(serve_fresh_path):
            with open(serve_fresh_path) as f:
                serve_fresh = json.load(f)
        else:
            # serve_smoke MERGES into its json — start empty so stale
            # backend sections can't mask a serving regression
            try:
                os.remove("BENCH_serve.fresh.json")
            except OSError:
                pass
            serve_fresh = serve_smoke(
                backends=("dense",), json_path="BENCH_serve.fresh.json")
        # NOT forwarding `tolerance`: it is the cut-growth override, and
        # loosening cuts must not loosen the structural occupancy gate —
        # the serve gate reads its own tags from the serving baseline
        regressions += compare_serve_baseline(serve_fresh, serve_baseline)

    if regressions:
        print(f"QUALITY GATE FAILED vs {baseline_path}:")
        for r in regressions:
            print(f"  {r}")
        return 1
    print(f"quality gate OK vs {baseline_path} "
          f"({json_path} holds the fresh numbers)")
    return 0


def main(quick=False, smoke=False, json_path="BENCH_partitioner.json",
         trials=0, fleet=False):
    trials_full = trials or 4  # full-run default when --trials is omitted
    report = {}
    if smoke:
        # CI guard: tiny graphs, one rep — exercises the batched
        # best-of-N path (N = --trials, at least 2), or with --fleet the
        # shape-bucketed fleet path, end to end so the bench script can't
        # silently rot.  Smoke runs MERGE into an existing report so the
        # smoke steps compose into one gate-able JSON.
        from repro.data import graphs as gen

        try:
            with open(json_path) as f:
                report = json.load(f)
        except (OSError, ValueError):
            report = {}
        if fleet:
            fab = fleet_ab(
                graphs={"g16": gen.grid2d(16, 16), "g15": gen.grid2d(15, 15),
                        "g8": gen.grid2d(8, 8)},
                k=4, trials=max(trials, 1), coarse_target=32,
                cfg_extra={"max_iter": 40, "patience": 4},
            )
            report.setdefault("fleet_ab", {})["smoke"] = fab
            print(json.dumps(fab, indent=1))
        else:
            tab = trials_ab(names={"smoke": gen.grid2d(16, 16)}, k=4,
                            trials=max(trials, 2), coarse_target=32,
                            cfg_extra={"max_iter": 40, "patience": 4})
            report.setdefault("trials_ab", {}).update(tab)
            print(json.dumps(tab["smoke"], indent=1))
        report.setdefault("baseline_tolerance", BASELINE_TOLERANCE)
        with open(json_path, "w") as f:
            json.dump(report, f, indent=1)
        print(f"-> {json_path}")
        return report

    # full runs also MERGE: the committed JSON doubles as the CI quality
    # baseline, whose smoke entries a from-scratch rewrite would destroy
    try:
        with open(json_path) as f:
            report = json.load(f)
    except (OSError, ValueError):
        report = {}

    rows = quality(quick=quick)
    print("# end-to-end: geomean(CLP-multilevel cut / Jet cut); >1 = Jet wins")
    for name, v in rows:
        print(f"{name},{v:.4f}")
    rows2 = time_breakdown(quick=quick)
    print("# Table 2-style phase breakdown (note: host-loop timings on CPU)")
    for name, v in rows2:
        print(f"{name},{v:.2f}")
    tab = trials_ab(names=["grid", "rmat"] if quick else None,
                    trials=trials_full)
    print(f"# trials A/B: sequential {trials_full}-loop vs vmapped batch "
          "(warm)")
    for name, rec in tab.items():
        print(f"trials_ab/{name}/warm_speedup,{rec['warm_speedup']:.3f}")
        print(f"trials_ab/{name}/best_of_{trials_full}_cut,{rec['best_cut']}")
        print(f"trials_ab/{name}/single_trial_cut,{rec['single_trial_cut']}")
    fab = fleet_ab(coarse_target=1024, trials=trials_full)
    print("# fleet A/B: sequential per-graph loop vs shape-bucketed batch")
    print(f"fleet_ab/mixed/warm_speedup,{fab['warm_speedup']:.3f}")
    print(f"fleet_ab/mixed/bucket_executables,{fab['bucket_executables']}")
    report["quality"] = dict(rows)
    report["breakdown"] = dict(rows2)
    report.setdefault("trials_ab", {}).update(tab)
    report.setdefault("fleet_ab", {})["mixed"] = fab
    report.setdefault("baseline_tolerance", BASELINE_TOLERANCE)
    with open(json_path, "w") as f:
        json.dump(report, f, indent=1)
    print(f"-> {json_path}")
    return report


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny graph, 1 rep — CI guard for the bench script")
    ap.add_argument("--trials", type=int, default=0,
                    help="trial count for the batched best-of-N A/B "
                         "(default 4 for full runs, at least 2 for the "
                         "--smoke one)")
    ap.add_argument("--fleet", action="store_true",
                    help="with --smoke: run the shape-bucketed fleet A/B "
                         "smoke instead of the trials one")
    ap.add_argument("--check-baseline", action="store_true",
                    help="CI quality gate: run the smoke suite fresh and "
                         "exit nonzero if cut/balance regress against the "
                         "committed baseline JSON")
    ap.add_argument("--baseline", default="BENCH_partitioner.json",
                    help="baseline JSON for --check-baseline")
    ap.add_argument("--serve-baseline", default="BENCH_serve.json",
                    help="serving baseline JSON for --check-baseline "
                         "(skipped when absent)")
    ap.add_argument("--serve-fresh", default=None,
                    help="reuse this fresh serve report for the serving "
                         "gate instead of re-running the serve smoke")
    ap.add_argument("--tolerance", type=float, default=None,
                    help="override the baseline's cut-growth tolerance")
    ap.add_argument("--json", default=None,
                    help="report JSON path (default: the committed "
                         "BENCH_partitioner.json; with --check-baseline, a "
                         "separate BENCH_partitioner.fresh.json so the "
                         "baseline is never clobbered)")
    a = ap.parse_args()
    if a.check_baseline:
        raise SystemExit(check_baseline(
            baseline_path=a.baseline,
            json_path=a.json or "BENCH_partitioner.fresh.json",
            tolerance=a.tolerance,
            serve_baseline_path=a.serve_baseline,
            serve_fresh_path=a.serve_fresh,
        ))
    main(quick=a.quick, smoke=a.smoke,
         json_path=a.json or "BENCH_partitioner.json", trials=a.trials,
         fleet=a.fleet)
