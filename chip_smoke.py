"""Run the Jet partitioner's main path once on one TPU chip and check it.

    python chip_smoke.py

Exits nonzero, printing no result, unless JAX's first device is a TPU.
Each phase goes through a user entry point and prints one JSON line:

  A  ``partition()`` of a 1024x1024 grid (n = 1,048,576) into k=64 parts on
     the default dense backend; cut, labels and balance are recomputed in
     numpy from the host CSR, and the cut must beat the 64-strip cut.
  B  ``partition()`` of the same grid on ``backend="ell"``: cut and parts
     must equal A's, and the finest level's compiled program must hold the
     Pallas kernel (``tpu_custom_call``).
  C  best-of-4 trials on a 120x120 grid (4 levels), each trial equal to its
     own trials=1 run, and a ``partition_fleet`` of it and a 110x110 grid
     (3 levels, in the same capacity bucket, so one lane stops coarsening
     a level before the other) whose members equal their standalone runs
     bit for bit.
  D  a ``PartitionServer`` warmed for the same two grids at k in {8, 64},
     answering 16 concurrent requests; each response equals its standalone
     run, and the replay compiles nothing.

Compiling is most of the run: every capacity rung of every graph is its
own TPU program, 5-90 s each, and the host compiles several programs at
once faster than one after the other.  So A, B and C (then D's warm-up
and reference runs) run side by side in three threads of this process;
D's replay and B's checks run after them, alone, so that their counts of
zero compiles cover the whole process.  A phase's compile counts are its
own thread's; wall times of the side-by-side phases overlap.

The last line of standard output is ``{"ok": true, "device": {...}}``.
JAX's persistent compilation cache is placed by
:func:`repro.launch.compile_cache.enable_compile_cache`.
"""
from __future__ import annotations

import asyncio
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import replace
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.partition import (  # noqa: E402
    PartitionConfig, partition, partition_fleet, uncoarsen_level,
)
from repro.data import graphs as gen  # noqa: E402
from repro.launch.compile_cache import cache_stats, enable_compile_cache  # noqa: E402
from repro.launch.partition_serve import PartitionServer, ServeConfig  # noqa: E402


class SmokeFailure(Exception):
    """A phase's result failed its check."""


def _check(ok, message: str) -> None:
    if not ok:
        raise SmokeFailure(message)


class _Window:
    """Wall clock and compile-event deltas over one phase: the events of
    the calling thread, or with ``all_threads`` those of the process."""

    def __init__(self, all_threads: bool = False):
        self.this_thread = not all_threads

    def __enter__(self):
        self.stats = cache_stats()
        self.before = self.stats.snapshot(self.this_thread)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.wall_s = time.perf_counter() - self.t0
        d = self.stats.delta(self.before,
                             self.stats.snapshot(self.this_thread))
        self.record = {
            "wall_s": self.wall_s,
            "compiles": int(d.get("compiles", 0)),
            "compile_s": d.get("compile_s", 0.0),
            "cache_hits": int(d.get("cache_hits", 0)),
            "cache_misses": int(d.get("cache_misses", 0)),
        }
        return False


def _emit(record: dict) -> None:
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        record["peak_hbm_bytes"] = stats["peak_bytes_in_use"]
    print(json.dumps(record), flush=True)


def _host_csr(g) -> dict:
    n, m = int(g.n), int(g.m)
    return {"n": n, "m": m, "esrc": np.asarray(g.esrc)[:m],
            "adjncy": np.asarray(g.adjncy)[:m],
            "adjwgt": np.asarray(g.adjwgt)[:m], "vwgt": np.asarray(g.vwgt)[:n]}


def check_partition(csr: dict, parts, k: int, lam: float, cut: int) -> None:
    """Numpy recomputation: labels, reported cut, exact integer balance."""
    p = np.asarray(parts)[: csr["n"]]
    _check(p.min() >= 0 and p.max() < k,
           f"part ids in [{p.min()}, {p.max()}], expected [0, {k})")
    diff = p[csr["esrc"]] != p[csr["adjncy"]]
    cut_np = int(csr["adjwgt"][diff].sum()) // 2
    _check(cut_np == cut, f"reported cut {cut} != numpy cut {cut_np}")
    sizes = np.bincount(p, weights=csr["vwgt"], minlength=k).astype(np.int64)
    limit = int(np.floor((1.0 + lam) * int(csr["vwgt"].sum()) / k))
    _check(sizes.max() <= limit, f"max part {sizes.max()} > limit {limit}")


def _result_record(phase, csr, cfg, res, window: dict) -> dict:
    return {"phase": phase, "n": csr["n"], "m": csr["m"], "k": cfg.k,
            "T": cfg.trials, "cut": res.cut, "imbalance": res.imbalance,
            "levels": res.levels, "times": res.times} | window


def phase_one_shot(g, k: int, strip_cut: int,
                   base: PartitionConfig = PartitionConfig()):
    """A: ``partition()`` on the default (dense) backend."""
    cfg = replace(base, k=k)
    csr = _host_csr(g)
    with _Window() as win:
        res = partition(g, cfg)
        np.asarray(res.parts)
    check_partition(csr, res.parts, k, cfg.lam, res.cut)
    _check(res.cut < strip_cut, f"cut {res.cut} >= strip cut {strip_cut}")
    _emit(_result_record("A_one_shot_dense", csr, cfg, res, win.record)
          | {"strip_cut": strip_cut})
    return res


def finest_level_text(g, res, cfg) -> str:
    """Compiled text of the finest ``uncoarsen_level`` call of ``res``.

    Lowered from the same shapes and options as that call, so JAX hands
    back the executable the call compiled instead of compiling again.
    """
    fine, coarse = res.level_stats[-1], res.level_stats[max(len(
        res.level_stats) - 2, 0)]

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, np.int32)

    lowered = uncoarsen_level.lower(
        jax.tree_util.tree_map(lambda x: sds((1,) + x.shape), g),
        sds((1, fine["n_max"])), sds((1, cfg.trials, coarse["n_max"])),
        None, cfg.phi, k=cfg.k, lam=cfg.lam, c=cfg.c_finest,
        backend=cfg.backend, patience=cfg.patience, max_iter=cfg.max_iter,
        b_max=cfg.b_max, variant=cfg.variant, rebuild_every=cfg.rebuild_every,
        max_degree=fine.get("max_degree") if cfg.backend == "ell" else None,
    )
    return lowered.compile().as_text()


def run_ell(g, k: int, base: PartitionConfig = PartitionConfig()):
    """B's ``partition()`` on the ELL backend (the Pallas ``jet_gain``
    path); runs beside A and is checked against it by :func:`phase_ell`."""
    cfg = replace(base, k=k, backend="ell")
    with _Window() as win:
        res = partition(g, cfg)
        np.asarray(res.parts)
    return res, cfg, win.record


def phase_ell(g, dense, ell) -> None:
    """B: the ELL run ``ell`` (from :func:`run_ell`) equals A's dense run
    of the same graph, cut and parts, and its finest level holds the
    compiled kernel."""
    res, cfg, window = ell
    csr = _host_csr(g)
    check_partition(csr, res.parts, cfg.k, cfg.lam, res.cut)
    _check(res.cut == dense.cut
           and np.array_equal(np.asarray(res.parts), np.asarray(dense.parts)),
           f"ELL cut {res.cut} != dense cut {dense.cut}, or parts differ")
    with _Window(all_threads=True) as kwin:
        kernel = "tpu_custom_call" in finest_level_text(g, res, cfg)
    _check(kwin.record["compiles"] == 0, "kernel check compiled anew")
    _check(kernel, "finest ELL level compiled without the Pallas kernel")
    _emit(_result_record("B_ell_equals_dense", csr, cfg, res, window)
          | {"tpu_custom_call": kernel, "equals_dense": True,
             "kernel_check": kwin.record})


def _same(a, b) -> bool:
    return (a.cut == b.cut and a.balanced == b.balanced
            and a.trial_cuts == b.trial_cuts
            and np.array_equal(np.asarray(a.parts), np.asarray(b.parts)))


def phase_trials_fleet(families: dict, k: int, trials: int,
                       base: PartitionConfig = PartitionConfig()):
    """C: best-of-T trials on the first family == its per-seed runs, and a
    T=1 fleet of all families == their standalone runs."""
    g = next(iter(families.values()))
    cfg = replace(base, k=k, trials=trials)
    csr = _host_csr(g)
    with _Window() as win:
        res = partition(g, cfg)
        np.asarray(res.parts)
    check_partition(csr, res.parts, k, cfg.lam, res.cut)
    for t, seed in enumerate(range(cfg.seed, cfg.seed + trials)):
        one = partition(g, replace(cfg, trials=1, trial_seeds=(seed,)))
        _check(res.trial_cuts[t] == one.cut
               and res.trial_balanced[t] == one.balanced
               and np.array_equal(np.asarray(res.trial_parts[t]),
                                  np.asarray(one.parts)),
               f"trial {t} != its trials=1 run")
    one_cfg = replace(base, k=k)
    with _Window() as fwin:
        fleet = partition_fleet(list(families.values()), one_cfg)
        np.asarray(fleet.results[-1].parts)
    for (name, fg), member in zip(families.items(), fleet.results):
        solo = partition(fg, one_cfg)
        _check(_same(member, solo), f"fleet member {name} != standalone")
        check_partition(_host_csr(fg), solo.parts, k, base.lam, solo.cut)
    _emit(_result_record("C_trials_fleet", csr, cfg, res, win.record)
          | {"trial_cuts": res.trial_cuts, "best_trial": res.best_trial,
             "fleet": list(families), "fleet_T": 1,
             "fleet_cuts": [r.cut for r in fleet.results],
             "fleet_window": fwin.record, "bit_identical": True})
    return res, fleet


def warm_server(families: dict, ks, lanes: int,
                base: PartitionConfig = PartitionConfig()) -> dict:
    """D, first half: a ``PartitionServer`` warmed for ``families`` x
    ``ks``, and the standalone ``partition()`` runs its responses must
    equal."""
    graphs = list(families.values())
    scfg = ServeConfig(ladder_n=max(g.n_max for g in graphs),
                       ladder_m=max(g.m_max for g in graphs),
                       window_s=0.05, lanes=lanes, partition=base)
    server = PartitionServer(scfg)
    with _Window() as win:
        warm = server.warmup(graphs, ks=tuple(ks))
    solo = {(name, k): partition(families[name], replace(base, k=k))
            for name in families for k in ks}
    for (name, k), r in solo.items():
        check_partition(_host_csr(families[name]), r.parts, k, base.lam,
                        r.cut)
    return {"server": server, "warmup_s": warm["warmup_s"],
            "window": win.record, "solo": solo}


def phase_served(families: dict, ks, copies: int, warmed: dict):
    """D: the server of :func:`warm_server` answers concurrent requests
    equal to their standalone runs, compiling nothing in the process."""
    server, solo = warmed["server"], warmed["solo"]
    reqs = [(name, k) for _ in range(copies) for name in families for k in ks]

    async def replay():
        async with server:
            return await asyncio.gather(*(
                server.submit(families[name], k=k) for name, k in reqs))

    execs0 = uncoarsen_level._cache_size()
    with _Window(all_threads=True) as win:
        results = asyncio.run(replay())
    new_execs = uncoarsen_level._cache_size() - execs0
    _check(win.record["compiles"] == 0 and new_execs == 0,
           f"replay after warmup compiled {win.record['compiles']} programs")
    for (name, k), r in zip(reqs, results):
        _check(_same(r, solo[(name, k)]),
               f"served {name} k={k} != standalone")
    m = server.metrics()
    _emit({"phase": "D_served", "n": [int(g.n) for g in families.values()],
           "m": [int(g.m) for g in families.values()], "k": list(ks),
           "T": server.cfg.partition.trials,
           "cut": {f"{name}/k{k}": r.cut for (name, k), r in solo.items()},
           "imbalance": max(r.imbalance for r in solo.values()),
           "levels": {f"{name}/k{k}": r.levels
                      for (name, k), r in solo.items()},
           "requests": len(reqs), "bit_identical": True,
           "post_warmup_compiles": win.record["compiles"],
           "warmup_s": warmed["warmup_s"],
           "warmup_compiles": warmed["window"]["compiles"],
           "warmup_window": warmed["window"],
           "p50_latency_ms": m["p50_latency_ms"],
           "occupancy_hist": m["occupancy_hist"]} | win.record)
    return results


def run_all(big, k: int, strip_cut: int, families: dict, *, trials: int,
            ks, lanes: int, copies: int,
            base: PartitionConfig = PartitionConfig()) -> None:
    """Phases A-D: A, B and C (then D's warm-up) side by side, then D's
    replay and B's checks alone.  Any failure propagates."""
    def small():
        phase_trials_fleet(families, k, trials, base)
        return warm_server(families, ks, lanes, base)

    with ThreadPoolExecutor(max_workers=3) as pool:
        jobs = [pool.submit(phase_one_shot, big, k, strip_cut, base),
                pool.submit(run_ell, big, k, base), pool.submit(small)]
        dense, ell, warmed = (j.result() for j in jobs)
    phase_served(families, ks, copies, warmed)
    phase_ell(big, dense, ell)


def device() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def main() -> int:
    dev = device()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU, JAX's first device is {dev['platform']}",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    t0 = time.perf_counter()
    # C and D share two grids in one capacity bucket with different level
    # counts, so D's k=64 programs are C's fleet's
    families = {"grid:120": gen.grid2d(120, 120),
                "grid:110": gen.grid2d(110, 110)}
    run_all(gen.grid2d(1024, 1024), 64, 63 * 1024, families, trials=4,
            ks=(8, 64), lanes=2, copies=4)
    print(json.dumps({"phase": "all", "wall_s": time.perf_counter() - t0}),
          flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
