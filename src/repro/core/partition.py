"""The Jet partitioner — multilevel driver (Alg 2.1) with batched trials.

coarsen -> initial partition (coarsest) -> [project -> Jet refine] per level.
Host drives the level loop (shapes change per level); everything inside a
level is jitted.

Trial batching (DESIGN.md §9): the uncoarsening half runs vmapped over T
independent seed trials on ONE shared hierarchy.  :func:`uncoarsen_level`
fuses project -> mask -> ConnState build -> Jet refinement into a single
jitted program keyed on the shape-schedule rung, so kernels compile once
per rung regardless of T; the best trial (balanced first, then lowest cut —
the same ordering as Alg 4.1's best tracking) is selected on device and
only materialized at the finest level.  The uncoarsening phase performs
exactly ONE blocking host transfer, after the level loop.
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
    coarsen_mode: str = "device"      # device (jitted levels) | host (legacy
                                      # numpy repack) — see DESIGN.md §8
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


def _uncoarsen_trials(
    fine, cmap, parts_batch, phi, active, *,
    k, lam, c, backend, patience, max_iter, b_max, variant, rebuild_every,
    max_degree,
):
    """project -> ghost-mask -> build_state -> Alg 4.1 loop, vmapped over T.

    The shared body of :func:`uncoarsen_level` (trial batching) and
    :func:`uncoarsen_level_fleet` (graph × trial batching).  ``active`` is
    None on the single-graph path; on the fleet path it is the lane's
    refine-active flag, threaded into the loop condition so frozen lanes
    pass their (identity-projected) partition through untouched.

    A single-graph batch of one trial runs unbatched, so the loop's
    ``lax.cond``s stay conds and each iteration computes only the move kind
    it takes; under the vmap they become selects (DESIGN.md §9).
    """

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

    if active is None and parts_batch.shape[0] == 1:
        return jax.tree_util.tree_map(lambda x: x[None],
                                      one_trial(parts_batch[0]))
    return jax.vmap(one_trial)(parts_batch)


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
    parts_batch: jnp.ndarray,
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
    """One uncoarsening level, fused and vmapped over the trial axis
    (run unbatched at T=1, see :func:`_uncoarsen_trials`).

    project -> ghost-mask -> ConnState build -> Jet refinement loop as a
    single XLA program.  ``parts_batch`` is (T, nc_max) coarse parts (pass
    the identity cmap at the coarsest level); returns the refined (T,
    n_max) batch plus per-trial stats arrays, all shape (T,).

    Compilation is keyed on the capacity rung — (fine.n_max, fine.m_max,
    nc_max, T) plus the static knobs — so re-running on a same-bucket level
    hits the cache.  Static per-trial arrays (the graph, the ELL adjacency)
    stay unbatched inside the vmap: only genuinely per-trial state carries
    a T axis (see DESIGN.md §9 for the ConnState batch-polymorphism rules).
    """
    return _uncoarsen_trials(
        fine, cmap, parts_batch, phi, None,
        k=k, lam=lam, c=c, backend=backend, patience=patience,
        max_iter=max_iter, b_max=b_max, variant=variant,
        rebuild_every=rebuild_every, max_degree=max_degree,
    )


@partial(
    jax.jit,
    static_argnames=(
        "k", "lam", "c", "backend", "patience", "max_iter", "b_max",
        "variant", "rebuild_every", "max_degree",
    ),
)
def uncoarsen_level_fleet(
    fine,
    cmap: jnp.ndarray,
    parts_batch: jnp.ndarray,
    active: jnp.ndarray,
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
    """One uncoarsening level vmapped over graphs × trials (DESIGN.md §10).

    ``fine`` is a stacked (B, ...) graph at this level's shared bucket
    capacity, ``cmap`` (B, n_max), ``parts_batch`` (B, T, nc_max), and
    ``active`` (B,) bool — the per-lane refine flag from the batched
    coarsening driver.  Inactive lanes (their own hierarchy is shallower
    than the bucket's) project through their identity cmap and skip the
    refinement loop entirely: their loop condition is false at iteration 0,
    so the carry freezes and the partition passes through bit-untouched.

    Compilation is keyed on (B, T, rung shapes) plus the static knobs —
    one executable per (rung, k) signature serves all B lanes and T trials.
    """

    def one_graph(g, cm, pb, act):
        return _uncoarsen_trials(
            g, cm, pb, phi, act,
            k=k, lam=lam, c=c, backend=backend, patience=patience,
            max_iter=max_iter, b_max=b_max, variant=variant,
            rebuild_every=rebuild_every, max_degree=max_degree,
        )

    return jax.vmap(one_graph)(fine, cmap, parts_batch, active)


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
def _fleet_epilogue(gb, parts_bt, best_balanced, best_cost, best_maxsize,
                    *, k: int, lam: float):
    """Per-lane best-trial selection + final metrics, all on device."""

    def one(g, parts_t, bb, bc, bm):
        idx = _best_trial(bb, bc, bm)
        parts = parts_t[idx]
        sizes = metrics.part_sizes(g, parts, k)
        W = g.total_vweight()
        return {
            "best_idx": idx,
            "parts": parts,
            "cut": metrics.cutsize(g, parts),
            "imbalance": metrics.imbalance(sizes, W, k),
            "balanced": metrics.is_balanced(sizes, W, k, lam),
        }

    return jax.vmap(one)(gb, parts_bt, best_balanced, best_cost, best_maxsize)


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


def partition_fleet_stacked(
    buckets, cfg: PartitionConfig, schedule, times_extra=None,
) -> FleetResult:
    """Partition pre-stacked shape buckets — the serving entry point.

    ``buckets`` is a list of :class:`~repro.core.graph.StackedBucket`
    (e.g. from a :class:`~repro.core.graph.BucketAssembler` flush) and
    ``schedule`` the fixed §8 capacity ladder they were assembled on.
    Runs the same batched V-cycle as :func:`partition_fleet` but skips
    admission entirely — bucket assignment, re-padding, and stacking
    already happened, possibly incrementally as requests arrived.

    Returns a :class:`FleetResult` whose ``results`` is a ``{tag:
    PartitionResult}`` dict keyed by the buckets' lane tags; filler lanes
    (tag ``None``) are computed (they pin the batch width so compiled
    signatures stay stable) but dropped from ``results``.
    """
    if not buckets:
        raise ValueError("partition_fleet_stacked needs at least one bucket")
    with span("partition_fleet",
              n_max=max(sb.capacity[0] for sb in buckets),
              m_max=max(sb.capacity[1] for sb in buckets), k=cfg.k,
              trials=cfg.trials, buckets=len(buckets)):
        return _partition_fleet_stacked(buckets, cfg, schedule, times_extra)


def _partition_fleet_stacked(buckets, cfg, schedule, times_extra):
    k = cfg.k
    seeds = _resolve_trial_seeds(cfg)
    trials = cfg.trials
    times = {"coarsen_s": 0.0, "uncoarsen_s": 0.0, "fetch_s": 0.0}
    if times_extra:  # e.g. the wrapper's admission/bucketing time, so
        times.update(times_extra)  # member times keep the full accounting
    t_start = time.perf_counter()

    pending = []  # (bucket record, metas, fetch pytree, device parts_bt)
    for sb in buckets:
        cap = sb.capacity
        idxs = list(sb.tags)
        B = len(idxs)
        gb = sb.graph

        with span("partition.coarsen", times, "coarsen_s"):
            levels = co.multilevel_coarsen_fleet(
                gb, schedule,
                coarse_target=cfg.coarse_target, max_levels=cfg.max_levels,
                stall_ratio=cfg.stall_ratio, seed=cfg.seed,
            )

        with span("partition.initial"):
            parts_bt = initial.initial_partition_fleet(
                levels[-1].graph, k, seeds, method=cfg.init_method
            )

        with span("partition.uncoarsen", times, "uncoarsen_s"):
            stats_per_level = []
            metas = []
            for i in range(len(levels) - 1, -1, -1):
                lv = levels[i]
                gi = lv.graph
                c = cfg.c_finest if i == 0 else cfg.c_coarse
                # static ELL width: max over lanes, from the coarsening stats —
                # frozen lanes are included (their build_state runs too)
                max_deg = (
                    int(lv.stats["max_degree"].max()) if cfg.backend == "ell"
                    else None
                )
                n_cap_i = gi.vwgt.shape[1]
                if i == len(levels) - 1:
                    cmap = jnp.broadcast_to(
                        jnp.arange(n_cap_i, dtype=jnp.int32), (B, n_cap_i)
                    )
                else:
                    cmap = lv.cmap
                with span("uncoarsen.level", level=i,
                          n_max=lv.stats["n_max"], m_max=lv.stats["m_max"]):
                    parts_bt, stats = uncoarsen_level_fleet(
                        gi, cmap, parts_bt, jnp.asarray(lv.active), cfg.phi,
                        k=k, lam=cfg.lam, c=c, backend=cfg.backend,
                        patience=cfg.patience, max_iter=cfg.max_iter,
                        b_max=cfg.b_max, variant=cfg.variant,
                        rebuild_every=cfg.rebuild_every, max_degree=max_deg,
                    )
                stats_per_level.append(stats)
                meta = {
                    "level": i,
                    "n_max": lv.stats["n_max"], "m_max": lv.stats["m_max"],
                    "n": lv.stats["n"], "m": lv.stats["m"],
                    "max_degree": lv.stats["max_degree"],
                    "active": lv.active,
                }
                if max_deg is not None:
                    meta["ell_width"] = max_deg
                metas.append(meta)

            fstats = stats_per_level[-1]
            ep = _fleet_epilogue(
                levels[0].graph, parts_bt,
                fstats["best_balanced"], fstats["best_cost"],
                fstats["best_maxsize"], k=k, lam=cfg.lam,
            )
            fetch = {
                "stats": {  # (L, B, T)
                    kk: jnp.stack([s[kk] for s in stats_per_level])
                    for kk in stats_per_level[0]
                },
                **ep,
                "trial_cuts": fstats["best_cost"],        # (B, T)
                "trial_balanced": fstats["best_balanced"],
            }
        bucket = FleetBucket(capacity=cap, indices=idxs, levels=len(levels),
                             level_stats=metas)
        pending.append((bucket, sb.orig_n_max, metas, fetch, parts_bt))

    # the ONE blocking transfer of the whole fleet's uncoarsening phase
    with span("partition.fetch", times, "fetch_s"):
        host_all = jax.device_get([p[3] for p in pending])
    times["total_s"] = (sum((times_extra or {}).values())
                        + time.perf_counter() - t_start)

    results: dict = {}
    out_buckets = []
    for (bucket, orig_n_max, metas, _, parts_bt), host in \
            zip(pending, host_all):
        out_buckets.append(bucket)
        cap_n = bucket.capacity[0]
        for j, tag in enumerate(bucket.indices):
            if tag is None:  # filler lane: batch-width ballast only
                continue
            own_n_max = orig_n_max[j]
            p = np.asarray(host["parts"][j])
            # parts AND trial_parts line up with the caller's own padding
            # (standalone contract: trial row t has the same shape as parts)
            tp = parts_bt[j]
            if own_n_max <= cap_n:
                p = p[:own_n_max]
                tp = tp[:, :own_n_max]
            else:
                p = np.concatenate(
                    [p, np.full(own_n_max - cap_n, k, p.dtype)]
                )
                tp = jnp.pad(tp, ((0, 0), (0, own_n_max - cap_n)),
                             constant_values=k)
            level_stats = []
            for li, meta in enumerate(metas):
                per = {kk: host["stats"][kk][li, j]
                       for kk in host["stats"]}
                entry = {
                    "level": meta["level"],
                    "n": int(meta["n"][j]), "m": int(meta["m"][j]),
                    "max_degree": int(meta["max_degree"][j]),
                    "n_max": meta["n_max"], "m_max": meta["m_max"],
                    "active": bool(meta["active"][j]),
                }
                if trials == 1:
                    entry |= {kk: int(vv[0]) for kk, vv in per.items()}
                else:
                    entry |= {kk: [int(x) for x in vv]
                              for kk, vv in per.items()}
                level_stats.append(entry)
            results[tag] = PartitionResult(
                parts=jnp.asarray(p),
                cut=int(host["cut"][j]),
                imbalance=float(host["imbalance"][j]),
                balanced=bool(host["balanced"][j]),
                levels=int(sum(m["active"][j] for m in metas)),
                # phase times are fleet-wide aggregates (one program serves
                # every member) — flagged so readers never attribute the
                # whole fleet's cost to a single graph
                times=dict(times, shared_across_fleet=True),
                level_stats=level_stats,
                config=cfg,
                trials=trials,
                best_trial=int(host["best_idx"][j]),
                trial_cuts=[int(x) for x in host["trial_cuts"][j]],
                trial_balanced=[bool(x) for x in host["trial_balanced"][j]],
                trial_parts=tp,
            )
    return FleetResult(results=results, buckets=out_buckets, times=times,
                       trials=trials, config=cfg)


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

    Host syncs: one batched (n, m) fetch at admission, one (B, 3) stat
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


def _uncoarsen(g, levels, parts_b, cfg: PartitionConfig):
    """The uncoarsening phase of :func:`partition`, from the coarsest
    level's initial ``parts_b`` to the one blocking fetch.  Returns the
    best trial's parts, the (T, n_max) parts batch, the fetched host
    values and the per-level host metadata."""
    k = cfg.k
    # refine coarsest, then uncoarsen.  Each level is ONE jitted
    # `uncoarsen_level` call (project -> mask -> ConnState build -> Alg 4.1
    # loop) vmapped over the trial axis; per-trial stats stay on device and
    # are fetched in a single transfer after the loop.
    stats_per_level = []   # dicts of (T,) traced stat arrays, coarsest first
    meta_per_level = []    # host-side size stats captured during coarsening
    for i in range(len(levels) - 1, -1, -1):
        gi = levels[i].graph
        lv_stats = levels[i].stats
        c = cfg.c_finest if i == 0 else cfg.c_coarse
        if cfg.backend == "ell":
            # static max degree from the stats captured during coarsening —
            # no extra device->host sync per level
            max_deg = (
                lv_stats["max_degree"] if lv_stats is not None
                else int(np.max(np.asarray(gi.degrees())))
            )
        else:
            max_deg = None
        if i == len(levels) - 1:
            # coarsest level: no projection — the identity cmap keeps the
            # call signature (and therefore the compiled executable) shared
            cmap = jnp.arange(gi.n_max, dtype=jnp.int32)
        else:
            cmap = levels[i].cmap
        with span("uncoarsen.level", level=i, n_max=gi.n_max,
                  m_max=gi.m_max):
            parts_b, stats = uncoarsen_level(
                gi, cmap, parts_b, cfg.phi,
                k=k, lam=cfg.lam, c=c, backend=cfg.backend,
                patience=cfg.patience, max_iter=cfg.max_iter,
                b_max=cfg.b_max, variant=cfg.variant,
                rebuild_every=cfg.rebuild_every, max_degree=max_deg,
            )
        stats_per_level.append(stats)
        meta = (
            dict(lv_stats)  # sizes, and the level's coarsening counters
            if lv_stats is not None
            else {"n": int(gi.n), "m": int(gi.m),
                  "n_max": gi.n_max, "m_max": gi.m_max}
        )
        if max_deg is not None:
            meta["max_degree"] = max_deg
        meta_per_level.append({"level": i} | meta)

    # shape_schedule rung 0 is the caller's exact capacity, so the finest
    # parts batch always lines up with g's padding
    assert parts_b.shape[1] == g.n_max, (parts_b.shape, g.n_max)

    # device epilogue: best-trial selection + final metrics, then the ONE
    # blocking transfer of the whole uncoarsening phase
    fstats = stats_per_level[-1]
    best_idx = _best_trial(
        fstats["best_balanced"], fstats["best_cost"], fstats["best_maxsize"]
    )
    parts = parts_b[best_idx]
    sizes = metrics.part_sizes(g, parts, k)
    W = g.total_vweight()
    fetch = {
        "stats": {
            kk: jnp.stack([s[kk] for s in stats_per_level])  # (L, T)
            for kk in stats_per_level[0]
        },
        "best_idx": best_idx,
        "cut": metrics.cutsize(g, parts),
        "imbalance": metrics.imbalance(sizes, W, k),
        "balanced": metrics.is_balanced(sizes, W, k, cfg.lam),
        "trial_cuts": fstats["best_cost"],
        "trial_balanced": fstats["best_balanced"],
    }
    with span("partition.fetch"):
        host = jax.device_get(fetch)
    return parts, parts_b, host, meta_per_level


def partition(g, cfg: PartitionConfig) -> PartitionResult:
    """Full multilevel partition of ``g`` into ``cfg.k`` parts.

    With ``cfg.trials = T > 1``, the whole uncoarsening phase runs vmapped
    over T seed trials on the shared hierarchy and the returned partition
    is the device-selected best trial; ``trial_cuts`` / ``trial_balanced``
    / ``trial_parts`` expose the full batch.  Trial ``t`` is bit-identical
    to a ``trials=1`` run with ``trial_seeds=(seeds[t],)``.
    """
    with span("partition", n_max=g.n_max, m_max=g.m_max, k=cfg.k,
              trials=cfg.trials):
        return _partition(g, cfg)


def _partition(g, cfg: PartitionConfig) -> PartitionResult:
    k = cfg.k
    seeds = _resolve_trial_seeds(cfg)
    trials = cfg.trials
    times: dict = {}
    t0 = time.perf_counter()
    with span("partition.coarsen", times, "coarsen_s"):
        levels = co.multilevel_coarsen(
            g,
            coarse_target=cfg.coarse_target,
            max_levels=cfg.max_levels,
            stall_ratio=cfg.stall_ratio,
            seed=cfg.seed,
            mode=cfg.coarsen_mode,
            bucket_ratio=cfg.bucket_ratio,
            bucket_safety=cfg.bucket_safety,
            bucket_align=cfg.bucket_align,
        )

    with span("partition.initial"):
        gc = levels[-1].graph
        parts_b = initial.initial_partition_batch(gc, k, seeds,
                                                  method=cfg.init_method)

    with span("partition.uncoarsen", times, "uncoarsen_s"):
        parts, parts_b, host, meta_per_level = _uncoarsen(
            g, levels, parts_b, cfg)
    times["total_s"] = time.perf_counter() - t0

    level_stats = []
    for j, meta in enumerate(meta_per_level):
        per = {kk: host["stats"][kk][j] for kk in host["stats"]}
        if trials == 1:
            level_stats.append(meta | {kk: int(vv[0]) for kk, vv in per.items()})
        else:
            level_stats.append(
                meta | {kk: [int(x) for x in vv] for kk, vv in per.items()}
            )

    return PartitionResult(
        parts=parts,
        cut=int(host["cut"]),
        imbalance=float(host["imbalance"]),
        balanced=bool(host["balanced"]),
        levels=len(levels),
        times=times,
        level_stats=level_stats,
        config=cfg,
        trials=trials,
        best_trial=int(host["best_idx"]),
        trial_cuts=[int(x) for x in host["trial_cuts"]],
        trial_balanced=[bool(x) for x in host["trial_balanced"]],
        trial_parts=parts_b,
    )


def refine_only(g, parts0, cfg: PartitionConfig) -> PartitionResult:
    """Refinement-effectiveness mode: refine an imported partition on the
    finest graph only (paper §5.1 effectiveness tests)."""
    if cfg.backend == "ell":
        # static ELL width resolved ONCE, up front — not mid-call inside
        # jet_refine, which would block the device queue between the parts
        # normalization and the loop launch
        max_deg = int(np.max(np.asarray(g.degrees())))
    else:
        max_deg = None
    parts, stats = refine.jet_refine(
        g,
        jnp.asarray(np.asarray(parts0), dtype=jnp.int32),
        cfg.k,
        lam=cfg.lam,
        c=cfg.c_finest,
        phi=cfg.phi,
        backend=cfg.backend,
        patience=cfg.patience,
        max_iter=cfg.max_iter,
        b_max=cfg.b_max,
        variant=cfg.variant,
        rebuild_every=cfg.rebuild_every,
        max_degree=max_deg,
    )
    sizes = metrics.part_sizes(g, parts, cfg.k)
    W = g.total_vweight()
    return PartitionResult(
        parts=parts,
        cut=int(metrics.cutsize(g, parts)),
        imbalance=float(metrics.imbalance(sizes, W, cfg.k)),
        balanced=bool(metrics.is_balanced(sizes, W, cfg.k, cfg.lam)),
        levels=1,
        level_stats=[{kk: int(vv) for kk, vv in stats.items()}],
        config=cfg,
    )
