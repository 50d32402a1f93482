"""Serving-path benchmark + CI smoke: the §11 micro-batching server.

Replays a mixed-shape, mixed-k request burst through an in-process
:class:`~repro.launch.partition_serve.PartitionServer` and emits
``BENCH_serve.json`` with p50/p95 latency, throughput, the
batch-occupancy histogram, and compile-cache hit counts.

``--smoke`` is the CI serving gate.  Per backend it asserts:

* every coalesced response is bit-identical to its standalone
  ``partition()`` run (``run_workload(verify=True)``);
* at least one dispatched bucket had mixed occupancy (>= 2 real lanes
  holding different true sizes — the workload pairs near-sized grids on
  purpose);
* exactly one ``uncoarsen_level`` executable per (rung, k)
  signature — the fixed-lanes discipline keeps the batch axis out of the
  compile key;
* after the AOT warmup pass, replaying the workload compiles ZERO new
  executables (and, when a persistent compile cache is wired, zero
  compilation-cache misses).

The committed ``BENCH_serve.json`` doubles as the CI serving baseline:
``bench_partitioner.py --check-baseline`` gates fresh throughput and
batch occupancy against it using the ``baseline_tolerance`` /
``throughput_tolerance`` tags.
"""
from __future__ import annotations

import argparse
import json

import jax

from repro.core.partition import PartitionConfig
from repro.launch.compile_cache import cache_stats, enable_compile_cache

# occupancy is structural (same workload -> same batches) so the default
# cut-style tolerance applies; throughput is wall-clock on shared CI
# runners, so its gate only catches order-of-magnitude collapses
BASELINE_TOLERANCE = 0.25
THROUGHPUT_TOLERANCE = 0.9

SMOKE_SPEC = {
    # near-sized grids: 13x13 and 12x12 round to one capacity rung on the
    # (192, 1280) serve ladder (mixed-occupancy bucket); 6x6 lands in its
    # own bucket behind a filler lane
    "families": [{"graph": "grid", "size": 13},
                 {"graph": "grid", "size": 12},
                 {"graph": "grid", "size": 6}],
    "ks": [2, 4],
    "count": 12,
    "rate_rps": 2000.0,   # burst: arrivals well inside one window
    "trials": 1,
    "seed": 0,
}


def _smoke_serve_cfg(backend: str):
    from repro.launch.partition_serve import ServeConfig

    pcfg = PartitionConfig(k=4, backend=backend, coarse_target=32,
                           max_iter=40, patience=4)
    # window >> the burst's arrival span, so a slow CI runner still
    # coalesces the whole burst into one deterministic batch
    return ServeConfig(ladder_n=192, ladder_m=1280, window_s=0.025, lanes=2,
                       partition=pcfg)


def serve_smoke(backends=("dense", "sorted", "ell"),
                json_path="BENCH_serve.json"):
    """The CI serving gate; returns the (written) report dict."""
    from repro.launch.serve_cli import run_workload

    # merge into an existing report (bench_partitioner smoke convention):
    # backends can be run in separate invocations into one gate-able JSON
    try:
        with open(json_path) as f:
            report = json.load(f)
    except (OSError, ValueError):
        report = {}
    for backend in backends:
        # fresh jit cache per backend: the executable-count gates compare
        # cache-size deltas against signature counts, which an earlier
        # in-process bench (check_baseline runs the partitioner smokes
        # first) would contaminate — same discipline as fleet_ab
        jax.clear_caches()
        cache0 = cache_stats().snapshot()
        rep = run_workload(_smoke_serve_cfg(backend),
                           SMOKE_SPEC, warmup=True, verify=True)
        occ = {int(kk): vv
               for kk, vv in rep["server"]["occupancy_hist"].items()}

        # gate: mixed occupancy actually happened — some bucket held >= 2
        # real members of genuinely different sizes (not two copies of
        # one family that merely shared a rung)
        mixed = any(
            b["real"] >= 2 and len(set(b["member_n_max"])) >= 2
            for d in rep["dispatch_buckets"] for b in d
        )
        if not mixed:
            raise AssertionError(
                f"serve smoke [{backend}]: no dispatched bucket held >= 2 "
                f"differently-sized members (occupancy {occ}) — the "
                "near-sized grids must share a rung"
            )
        # gate: the replay compiled nothing after warmup
        if rep["post_warmup_new_executables"] != 0:
            raise AssertionError(
                f"serve smoke [{backend}]: replay compiled "
                f"{rep['post_warmup_new_executables']} new "
                "uncoarsen_level executables after warmup — the AOT "
                "grid must cover the workload"
            )
        # gate: one executable per (rung, k) signature — the AOT grid
        # compiled each of its signatures exactly once, and the replay's
        # signature set stayed inside the grid
        if rep["warmup"]["new_executables"] != rep["warmup_signatures"]:
            raise AssertionError(
                f"serve smoke [{backend}]: warmup compiled "
                f"{rep['warmup']['new_executables']} executables for "
                f"{rep['warmup_signatures']} (rung, k) signatures — "
                "batching must not multiply compiles"
            )
        if not rep["replay_covered_by_warmup"]:
            raise AssertionError(
                f"serve smoke [{backend}]: the replay hit signatures "
                "outside the warmup grid — the AOT pass must cover the "
                "workload's (rung, k) set"
            )
        cache_delta = {
            kk: vv - cache0.get(kk, 0)
            for kk, vv in cache_stats().snapshot().items()
        }
        report[backend] = {
            "requests": rep["requests"],
            "bit_identical": rep["bit_identical"],
            "throughput_rps": rep["throughput_rps"],
            "p50_latency_ms": rep["p50_latency_ms"],
            "p95_latency_ms": rep["p95_latency_ms"],
            "occupancy_hist": rep["server"]["occupancy_hist"],
            "mean_occupancy": rep["server"]["mean_occupancy"],
            "dispatches": rep["server"]["dispatches"],
            "filler_lanes": rep["server"]["filler_lanes"],
            "serve_signatures": rep["serve_signatures"],
            "warmup_s": rep["warmup"]["warmup_s"],
            "warmup_executables": rep["warmup"]["new_executables"],
            "post_warmup_new_executables":
                rep["post_warmup_new_executables"],
            "compile_cache_events": cache_delta,
        }
        print(f"[serve-smoke:{backend}] {rep['requests']} req, "
              f"p50 {rep['p50_latency_ms']:.1f} ms, "
              f"occupancy {rep['server']['occupancy_hist']}, "
              f"{rep['serve_signatures']} signatures, "
              f"0 post-warmup compiles")

    report["baseline_tolerance"] = BASELINE_TOLERANCE
    report["throughput_tolerance"] = THROUGHPUT_TOLERANCE
    with open(json_path, "w") as f:
        json.dump(report, f, indent=1)
    print(f"-> {json_path}")
    return report


def compare_serve_baseline(fresh, baseline, tolerance=None):
    """Serving-path regression check (mirrors ``compare_baseline``):
    per-backend mean batch occupancy may not drop by more than the
    baseline's ``baseline_tolerance`` (occupancy is structural under a
    fixed workload), throughput by more than ``throughput_tolerance``
    (loose — CI wall clocks are noisy), and bit-equivalence plus the
    zero-post-warmup-compile property must still hold.  Returns
    human-readable regression strings (empty == gate passes)."""
    tol = tolerance if tolerance is not None else \
        baseline.get("baseline_tolerance", BASELINE_TOLERANCE)
    tput_tol = baseline.get("throughput_tolerance", THROUGHPUT_TOLERANCE)
    backends = [kk for kk in baseline
                if isinstance(baseline[kk], dict) and "mean_occupancy"
                in baseline[kk]]
    bad = []
    common = [b for b in backends if b in fresh]
    if backends and not common:
        bad.append(
            "serve: no backend section shared between fresh report and "
            "baseline — the serving gate would pass vacuously; regenerate "
            "BENCH_serve.json"
        )
    for b in common:
        fb, bb = fresh[b], baseline[b]
        if not fb.get("bit_identical", False):
            bad.append(f"serve/{b}: responses no longer bit-identical to "
                       "standalone partition()")
        if fb.get("post_warmup_new_executables", 0) != 0:
            bad.append(
                f"serve/{b}: {fb['post_warmup_new_executables']} "
                "executables compiled after warmup (baseline: 0)"
            )
        floor = bb["mean_occupancy"] * (1.0 - tol)
        if fb["mean_occupancy"] < floor:
            bad.append(
                f"serve/{b}: mean batch occupancy {fb['mean_occupancy']:.2f}"
                f" fell below baseline {bb['mean_occupancy']:.2f} by more "
                f"than {100 * tol:.0f}%"
            )
        tput_floor = bb["throughput_rps"] * (1.0 - tput_tol)
        if fb["throughput_rps"] < tput_floor:
            bad.append(
                f"serve/{b}: throughput {fb['throughput_rps']:.2f} rps "
                f"fell below {tput_floor:.2f} (baseline "
                f"{bb['throughput_rps']:.2f} - {100 * tput_tol:.0f}%)"
            )
    return bad


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI serving gate: tiny burst, all gates on")
    ap.add_argument("--backends", default="dense,sorted,ell",
                    help="comma-separated backend list for --smoke")
    ap.add_argument("--json", default="BENCH_serve.json")
    a = ap.parse_args()
    if not a.smoke:
        ap.error("only --smoke is implemented; use serve_cli for ad-hoc "
                 "replays")
    enable_compile_cache()
    serve_smoke(backends=tuple(a.backends.split(",")), json_path=a.json)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
