"""The Jet partitioner — the multilevel driver (Alg 2.1).

coarsen -> initial partition (coarsest) -> [project -> Jet refine] per level.
Host drives the level loop (shapes change per level); everything inside a
level is jitted.

One driver serves every entry point: :func:`partition` (one graph),
:func:`partition_fleet` and :func:`partition_fleet_stacked` (shape buckets
of B graphs, DESIGN.md §10) and :func:`refine_only`.  It works on stacked
``(B, ...)`` buckets — a lone graph is a bucket of one lane — and every
step is written once per graph and per trial and mapped over the lane axis
B and the trial axis T by :func:`~repro.core.graph.map_lanes`, which runs a
size-1 axis unbatched (DESIGN.md §9).  :func:`uncoarsen_level` fuses
project -> mask -> ConnState build -> Jet refinement into a single jitted
program keyed on the shape-schedule rung, so kernels compile once per rung
regardless of T; the best trial (balanced first, then lowest cut — the
same ordering as Alg 4.1's best tracking) is selected on device by one
jitted epilogue.  The uncoarsening phase performs exactly ONE blocking
host transfer, after every bucket's level loop.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import coarsen as co
from repro.core import connectivity as cn
from repro.core import graph as gr
from repro.core import initial, metrics, refine
from repro.core.spans import span


@dataclass
class PartitionConfig:
    k: int = 8
    lam: float = 0.03                 # balance slack (paper: 1-10%)
    phi: float = 0.999                # quality/runtime tolerance (paper §4)
    c_finest: float = 0.25            # Eq 4.3 ratio, finest level
    c_coarse: float = 0.75            # Eq 4.3 ratio, other levels
    coarse_target: int = 4096         # paper coarsens to 4-8k vertices
    max_levels: int = 40              # coarsening depth cap
    stall_ratio: float = 0.95         # terminate when a level shrinks less
    bucket_ratio: float = 1.6         # shape-schedule geometric shrink
    bucket_safety: float = 1.25       # headroom multiplier on the shrink
    bucket_align: int = 64            # capacity rung alignment
    patience: int = 12                # iterations without a new best
    max_iter: int = 300
    b_max: int = 2                    # weak rebalances before strong
    backend: str = "dense"            # connectivity backend: dense|sorted|ell
    rebuild_every: int = 0            # full ConnState rebuild period (0=never,
                                      # 1=paper's always-rebuild fallback)
    init_method: str = "voronoi"      # random|voronoi
    variant: str = "full"             # Jetlp variant (Table 3 ablations)
    seed: int = 0
    trials: int = 1                   # best-of-N trials, vmapped over one
                                      # shared hierarchy (DESIGN.md §9)
    trial_seeds: tuple | None = None  # per-trial init seeds; default
                                      # (seed, seed+1, ..., seed+trials-1)


@dataclass
class PartitionResult:
    parts: jnp.ndarray
    cut: int
    imbalance: float
    balanced: bool
    levels: int
    times: dict = field(default_factory=dict)
    level_stats: list = field(default_factory=list)
    config: Any = None
    trials: int = 1
    best_trial: int = 0               # index into the trial batch
    trial_cuts: list = field(default_factory=list)      # per-trial best cut
    trial_balanced: list = field(default_factory=list)  # per-trial balance
    trial_parts: Any = None           # (T, n_max) finest-level parts batch


def _resolve_trial_seeds(cfg: PartitionConfig) -> tuple:
    if cfg.trials < 1:
        raise ValueError(f"trials must be >= 1, got {cfg.trials}")
    if cfg.trial_seeds is None:
        return tuple(cfg.seed + t for t in range(cfg.trials))
    seeds = tuple(int(s) for s in cfg.trial_seeds)
    if len(seeds) != cfg.trials:
        raise ValueError(
            f"trial_seeds has {len(seeds)} entries but trials={cfg.trials}"
        )
    return seeds


def _uncoarsen_graph(
    fine, cmap, parts_t, active, phi, *,
    k, lam, c, backend, patience, max_iter, b_max, variant, rebuild_every,
    max_degree,
):
    """project -> ghost-mask -> build_state -> Alg 4.1 loop of one graph,
    mapped over its (T, nc_max) trial batch.  ``active`` is the lane's
    refine flag, threaded into the loop condition so a frozen lane passes
    its (identity-projected) partition through untouched; None where every
    lane of the level is live."""

    def one_trial(parts_coarse):
        with jax.named_scope("uncoarsen.project"):
            parts = co.project_partition(cmap, parts_coarse)
            parts = jnp.where(fine.vertex_mask(), parts, k).astype(jnp.int32)
        with jax.named_scope("uncoarsen.build_state"):
            conn0 = cn.build_state(fine, parts, k, backend,
                                   max_degree=max_degree)
        return refine._refine_loop(
            fine, parts, conn0, phi,
            k=k, lam=lam, c=c, backend=backend, patience=patience,
            max_iter=max_iter, b_max=b_max, variant=variant,
            rebuild_every=rebuild_every, active=active,
        )

    return gr.map_lanes(one_trial, parts_t)


@partial(
    jax.jit,
    static_argnames=(
        "k", "lam", "c", "backend", "patience", "max_iter", "b_max",
        "variant", "rebuild_every", "max_degree",
    ),
)
def uncoarsen_level(
    fine,
    cmap: jnp.ndarray,
    parts: jnp.ndarray,
    active: jnp.ndarray | None,
    phi,
    *,
    k: int,
    lam: float,
    c: float,
    backend: str,
    patience: int,
    max_iter: int,
    b_max: int,
    variant: str,
    rebuild_every: int,
    max_degree: int | None = None,
):
    """One uncoarsening level of a bucket, fused into one program.

    ``fine`` is a stacked (B, ...) graph at the level's capacity, ``cmap``
    (B, n_max) (the identity at the coarsest level), ``parts`` (B, T,
    nc_max) coarse parts and ``active`` (B,) bool, each lane's refine flag
    (None where every lane is live: always so for one lane, whose levels
    exist only where it coarsened).  project -> ghost-mask -> ConnState
    build -> Jet refinement run as a single XLA program, mapped over B and
    T by :func:`~repro.core.graph.map_lanes`: at B = T = 1 unbatched, so
    the loop's ``lax.cond``s stay conds.  Returns the refined (B, T, n_max)
    batch plus per-trial stats arrays, each (B, T).

    Compilation is keyed on the capacity rung — (B, T, fine.n_max,
    fine.m_max, nc_max) plus the static knobs — so re-running on a
    same-bucket level hits the cache.  Static per-lane arrays (the graph,
    the ELL adjacency) stay unbatched over T: only genuinely per-trial
    state carries a T axis (see DESIGN.md §9 for the ConnState
    batch-polymorphism rules).
    """
    return gr.map_lanes(
        lambda g, cm, pt, act: _uncoarsen_graph(
            g, cm, pt, act, phi,
            k=k, lam=lam, c=c, backend=backend, patience=patience,
            max_iter=max_iter, b_max=b_max, variant=variant,
            rebuild_every=rebuild_every, max_degree=max_degree,
        ),
        fine, cmap, parts, active,
    )


def _best_trial(balanced: jnp.ndarray, cut: jnp.ndarray,
                maxsize: jnp.ndarray) -> jnp.ndarray:
    """Device-side best-of-T selection (same ordering as Alg 4.1's best
    tracking): a balanced trial always beats an unbalanced one; among
    balanced trials the lowest cut wins; if no trial balanced, the lowest
    max part weight wins with the lower cut breaking ties.  ``argmin``
    takes the first index on remaining ties, so selection is deterministic.
    """
    INF = jnp.int32(0x7FFFFFFF)
    idx_bal = jnp.argmin(jnp.where(balanced, cut, INF)).astype(jnp.int32)
    m0 = jnp.min(maxsize)
    idx_imb = jnp.argmin(jnp.where(maxsize == m0, cut, INF)).astype(jnp.int32)
    return jnp.where(jnp.any(balanced), idx_bal, idx_imb)


@partial(jax.jit, static_argnames=("k", "lam"))
def _epilogue(gb, parts, refine_stats, *, k: int, lam: float):
    """Per-lane best-trial selection and final metrics, and the per-level
    refinement stats stacked to (L, B, T): one program for every caller.
    Returns the best (B, n_max) parts and the pytree the one blocking
    transfer fetches."""
    fstats = refine_stats[-1]

    def one(g, parts_t, bb, bc, bm):
        idx = _best_trial(bb, bc, bm)
        best = parts_t[idx]
        sizes = metrics.part_sizes(g, best, k)
        W = g.total_vweight()
        return best, {
            "best_idx": idx,
            "cut": metrics.cutsize(g, best),
            "imbalance": metrics.imbalance(sizes, W, k),
            "balanced": metrics.is_balanced(sizes, W, k, lam),
        }

    best, summary = gr.map_lanes(
        one, gb, parts, fstats["best_balanced"], fstats["best_cost"],
        fstats["best_maxsize"])
    return best, summary | {
        "stats": {kk: jnp.stack([s[kk] for s in refine_stats])
                  for kk in refine_stats[0]},
        "trial_cuts": fstats["best_cost"],
        "trial_balanced": fstats["best_balanced"],
    }


@dataclass
class FleetBucket:
    """Host-side record of one shape bucket's run (for reports and the
    executable-count accounting in ``bench_partitioner.fleet_ab``)."""

    capacity: tuple          # (n_cap, m_cap) rung-0 capacity of the bucket
    indices: list            # fleet indices of the member graphs
    levels: int              # batched hierarchy depth (levels list length)
    level_stats: list = field(default_factory=list)  # coarsest-first metas


@dataclass
class FleetResult:
    """``partition_fleet`` output: per-graph results in input order plus
    the bucket/schedule accounting."""

    results: list            # list[PartitionResult], input order
    buckets: list            # list[FleetBucket]
    times: dict = field(default_factory=dict)
    trials: int = 1
    config: Any = None


def _vcycle(buckets, cfg: PartitionConfig, schedule, times_extra=None):
    """The V-cycle of every bucket: coarsen, initial partition, then
    :func:`_uncoarsen_and_fetch`.  Returns ``{tag: PartitionResult}``, the
    :class:`FleetBucket` records and the phase times."""
    times = dict(times_extra or {})
    t0 = time.perf_counter() - sum(times.values())
    seeds = _resolve_trial_seeds(cfg)
    with span("partition.coarsen", times, "coarsen_s"):
        hierarchies = [co.multilevel_coarsen(
            sb.graph, schedule, coarse_target=cfg.coarse_target,
            max_levels=cfg.max_levels, stall_ratio=cfg.stall_ratio,
            seed=cfg.seed) for sb in buckets]
    with span("partition.initial"):
        starts = [initial.initial_partition_batch(
            levels[-1].graph, cfg.k, seeds, method=cfg.init_method)
            for levels in hierarchies]
    return _uncoarsen_and_fetch(buckets, hierarchies, starts, cfg, times, t0)


def _uncoarsen_and_fetch(buckets, hierarchies, starts, cfg: PartitionConfig,
                         times, t0):
    """Uncoarsen every bucket from its coarsest (B, T, n) ``starts``, fetch
    every result in ONE blocking transfer, and build the results."""
    k = cfg.k
    pending = []  # (bucket, host metas, parts, best parts, fetch pytree)
    with span("partition.uncoarsen", times, "uncoarsen_s"):
        for sb, levels, parts in zip(buckets, hierarchies, starts):
            refine_stats, metas = [], []  # coarsest first
            for i in range(len(levels) - 1, -1, -1):
                lv = levels[i]
                gi = lv.graph
                B, n_cap = gi.vwgt.shape
                # static ELL width: max over lanes, from the coarsening
                # stats — frozen lanes are included (their build_state runs)
                max_deg = (int(lv.stats["max_degree"].max())
                           if cfg.backend == "ell" else None)
                # the coarsest level projects through the identity, so it
                # shares the call signature of every other level
                cmap = (lv.cmap if lv.cmap is not None else
                        jax.lax.broadcasted_iota(jnp.int32, (B, n_cap), 1))
                with span("uncoarsen.level", level=i,
                          n_max=lv.stats["n_max"], m_max=lv.stats["m_max"]):
                    parts, stats = uncoarsen_level(
                        gi, cmap, parts,
                        None if lv.active.all() else jnp.asarray(lv.active),
                        cfg.phi, k=k, lam=cfg.lam,
                        c=cfg.c_finest if i == 0 else cfg.c_coarse,
                        backend=cfg.backend, patience=cfg.patience,
                        max_iter=cfg.max_iter, b_max=cfg.b_max,
                        variant=cfg.variant, rebuild_every=cfg.rebuild_every,
                        max_degree=max_deg,
                    )
                refine_stats.append(stats)
                meta = {"level": i, "active": lv.active} | lv.stats
                if max_deg is not None:
                    meta["ell_width"] = max_deg
                metas.append(meta)
            best, summary = _epilogue(levels[0].graph, parts, refine_stats,
                                      k=k, lam=cfg.lam)
            pending.append((sb, metas, parts, best, summary))
        with span("partition.fetch"):
            host_all = jax.device_get([p[-1] for p in pending])
    times["total_s"] = time.perf_counter() - t0

    results: dict = {}
    records = []
    for (sb, metas, parts, best, _), host in zip(pending, host_all):
        records.append(FleetBucket(capacity=sb.capacity,
                                   indices=list(sb.tags),
                                   levels=len(metas), level_stats=metas))
        for j, tag in enumerate(sb.tags):
            if tag is None:  # filler lane: batch-width ballast only
                continue
            results[tag] = PartitionResult(
                # parts AND trial_parts line up with the caller's own
                # padding (trial row t has the same shape as parts)
                parts=_fit_parts(best[j], sb.orig_n_max[j], k),
                cut=int(host["cut"][j]),
                imbalance=float(host["imbalance"][j]),
                balanced=bool(host["balanced"][j]),
                levels=int(sum(m["active"][j] for m in metas)),
                times=dict(times),
                level_stats=[_lane_level_stats(m, host["stats"], li, j)
                             for li, m in enumerate(metas)],
                config=cfg,
                trials=parts.shape[1],
                best_trial=int(host["best_idx"][j]),
                trial_cuts=[int(x) for x in host["trial_cuts"][j]],
                trial_balanced=[bool(x) for x in host["trial_balanced"][j]],
                trial_parts=_fit_parts(parts[j], sb.orig_n_max[j], k),
            )
    return results, records, times


def _fit_parts(p, n_max: int, k: int):
    """Parts (..., cap) cut or padded (ghost part ``k``) to ``n_max``."""
    cap = p.shape[-1]
    if n_max == cap:
        return p
    if n_max < cap:
        return p[..., :n_max]
    pad = [(0, 0)] * (p.ndim - 1) + [(0, n_max - cap)]
    return jnp.pad(p, pad, constant_values=k)


def _lane_level_stats(meta: dict, stats: dict, li: int, j: int) -> dict:
    """Lane ``j``'s entry of level ``li``: its sizes and activity, its
    coarsening counters where its matching ran, and its refinement stats
    (ints at T = 1, per-trial lists otherwise)."""
    entry = {
        "level": meta["level"],
        "n": int(meta["n"][j]), "m": int(meta["m"][j]),
        "max_degree": int(meta["max_degree"][j]),
        "n_max": meta["n_max"], "m_max": meta["m_max"],
        "active": bool(meta["active"][j]),
    }
    if meta["counted"][j]:
        entry |= {kk: int(meta[kk][j]) for kk in co.COUNTERS}
    for kk, v in stats.items():
        per = v[li, j]
        entry[kk] = int(per[0]) if len(per) == 1 else [int(x) for x in per]
    return entry


def _one_lane(g) -> gr.StackedBucket:
    """``g`` as a bucket of one lane at its own capacity."""
    return gr.StackedBucket(capacity=(g.n_max, g.m_max),
                            graph=gr.stack_graphs([g]), tags=(0,),
                            orig_n_max=(g.n_max,))


def partition(g, cfg: PartitionConfig) -> PartitionResult:
    """Full multilevel partition of ``g`` into ``cfg.k`` parts.

    ``g`` runs as a bucket of one lane on a capacity ladder whose rung 0 is
    its own exact capacity, so the parts line up with its padding.  With
    ``cfg.trials = T > 1``, the whole uncoarsening phase runs vmapped over
    T seed trials on the shared hierarchy and the returned partition is the
    device-selected best trial; ``trial_cuts`` / ``trial_balanced`` /
    ``trial_parts`` expose the full batch.  Trial ``t`` is bit-identical to
    a ``trials=1`` run with ``trial_seeds=(seeds[t],)``.
    """
    with span("partition", n_max=g.n_max, m_max=g.m_max, k=cfg.k,
              trials=cfg.trials):
        schedule = co.shape_schedule(
            g.n_max, g.m_max, ratio=cfg.bucket_ratio,
            safety=cfg.bucket_safety, stall_ratio=cfg.stall_ratio,
            align=cfg.bucket_align,
        )
        results, _, _ = _vcycle([_one_lane(g)], cfg, schedule)
        return results[0]


def partition_fleet_stacked(
    buckets, cfg: PartitionConfig, schedule, times_extra=None,
) -> FleetResult:
    """Partition pre-stacked shape buckets — the serving entry point.

    ``buckets`` is a list of :class:`~repro.core.graph.StackedBucket`
    (e.g. from a :class:`~repro.core.graph.BucketAssembler` flush) and
    ``schedule`` the fixed §8 capacity ladder they were assembled on.
    Runs the same V-cycle as :func:`partition_fleet` but skips admission
    entirely — bucket assignment, re-padding, and stacking already
    happened, possibly incrementally as requests arrived.

    Returns a :class:`FleetResult` whose ``results`` is a ``{tag:
    PartitionResult}`` dict keyed by the buckets' lane tags; filler lanes
    (tag ``None``) are computed (they pin the batch width so compiled
    signatures stay stable) but dropped from ``results``.  Phase times are
    aggregates over the whole call — one program serves every member — so
    each member's ``times`` carries ``shared_across_fleet``.
    """
    if not buckets:
        raise ValueError("partition_fleet_stacked needs at least one bucket")
    with span("partition_fleet",
              n_max=max(sb.capacity[0] for sb in buckets),
              m_max=max(sb.capacity[1] for sb in buckets), k=cfg.k,
              trials=cfg.trials, buckets=len(buckets)):
        results, records, times = _vcycle(buckets, cfg, schedule,
                                          times_extra)
    for r in results.values():
        r.times["shared_across_fleet"] = True
    return FleetResult(results=results, buckets=records, times=times,
                       trials=cfg.trials, config=cfg)


def partition_fleet(graphs, cfg: PartitionConfig,
                    schedule=None) -> FleetResult:
    """Partition a fleet of graphs as shape-bucketed batched V-cycles.

    Graphs are grouped into static shape buckets on one shared §8 capacity
    ladder (`graph.bucket_graphs`); each bucket's members are stacked along
    a leading batch axis and run through coarsening, initial partitioning,
    and uncoarsening vmapped over B graphs × T trials — one jitted
    executable per (rung, k) signature serves the whole bucket.  Per-graph
    termination (coarsening depth, stalls) is select-masked per lane, so
    every graph's cut and parts vector is bit-identical to its standalone
    ``partition()`` run (tests/test_fleet.py).

    With ``schedule`` given, bucketing runs on that fixed ladder instead
    of one derived from the fleet max — the serving path, where rung
    stability across calls keeps compiled executables warm (§11).

    Host syncs: one batched (n, m) fetch at admission, one (B, 6) stat
    fetch per coarsening level per bucket (same cadence as standalone), and
    exactly ONE blocking transfer for all uncoarsening results of the whole
    fleet, after every bucket's level loop has been dispatched.
    """
    graphs = list(graphs)
    if not graphs:
        raise ValueError("partition_fleet needs at least one graph")
    t0 = time.perf_counter()
    schedule, bucket_map = gr.bucket_graphs(
        graphs, ratio=cfg.bucket_ratio, safety=cfg.bucket_safety,
        stall_ratio=cfg.stall_ratio, align=cfg.bucket_align,
        schedule=schedule,
    )
    buckets = []
    for cap in sorted(bucket_map, reverse=True):
        idxs = bucket_map[cap]
        members = [
            g if (g.n_max, g.m_max) == cap else g.with_capacity(*cap)
            for g in (graphs[i] for i in idxs)
        ]
        buckets.append(gr.StackedBucket(
            capacity=cap,
            graph=gr.stack_graphs(members),
            tags=tuple(idxs),
            orig_n_max=tuple(graphs[i].n_max for i in idxs),
        ))
    bucket_s = time.perf_counter() - t0

    sres = partition_fleet_stacked(buckets, cfg, schedule,
                                   times_extra={"bucket_s": bucket_s})
    results: list = [None] * len(graphs)
    for tag, r in sres.results.items():
        results[tag] = r
    return FleetResult(results=results, buckets=sres.buckets,
                       times=sres.times, trials=sres.trials, config=cfg)


def refine_only(g, parts0, cfg: PartitionConfig) -> PartitionResult:
    """Refinement-effectiveness mode: refine an imported partition on the
    finest graph only (paper §5.1 effectiveness tests) — one
    :func:`uncoarsen_level` call through the identity, on a hierarchy of
    one level, and the shared epilogue."""
    sb = _one_lane(g)
    levels = co.multilevel_coarsen(sb.graph, max_levels=0)
    parts = jnp.asarray(np.asarray(parts0), dtype=jnp.int32)[None, None]
    results, _, _ = _uncoarsen_and_fetch([sb], [levels], [parts], cfg, {},
                                         time.perf_counter())
    return results[0]
