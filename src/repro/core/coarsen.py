"""GPU-style coarsening re-derived for TPU: HEM + two-hop matching + contraction.

Paper §3.1: heavy-edge matching first; if >25% of vertices remain unmatched,
add two-hop matches (leaves, twins, relatives).  Contraction (Alg 3.1)
deduplicates coarse edges — the paper uses per-vertex hashtables; we use a
lexicographic sort + segmented sum (TPU idiom, deterministic).

Two coarsening paths share the matching/contraction kernels (DESIGN.md §8):

* **device** (default): :func:`coarsen_level` runs a whole level — HEM
  rounds, the two-hop trigger (``lax.cond`` on the device-computed
  unmatched fraction), ``coarse_map``, ``contract_edges``, and the
  coarse-CSR build — as ONE jitted function with zero host transfers.
  The driver re-buckets the result into a precomputed geometric
  :func:`shape_schedule` of (n_max, m_max) capacities, so kernels compile
  once per capacity rung instead of once per exact size.  The only host
  syncs left are one 6-int32 stat fetch per level: the coarse graph's
  size (termination check + capacity selection) and the level's matching
  counters (:data:`COUNTERS`).  The level's device phases carry the scopes
  ``coarsen.hem``, ``coarsen.twohop``, ``coarsen.contract`` and
  ``coarsen.csr``.
* **host** (legacy): :func:`coarsen_once` repacks the coarse graph into
  tight arrays on host via numpy — kept as the equivalence/bench baseline.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import Graph, csr_from_edge_runs
from repro.core.spans import span

_KNUTH = jnp.uint32(2654435761)


def _bij_hash(x: jnp.ndarray, seed: int) -> jnp.ndarray:
    """Invertible-ish 32-bit mix used only for random tie-breaking."""
    h = (x.astype(jnp.uint32) ^ jnp.uint32(seed)) * _KNUTH
    h = h ^ (h >> 16)
    return h


def _seg_pick_dst(elig, value, dst, esrc, n_max, seed):
    """Per-source argmax over eligible edges: max value, random tie-break.

    Returns (cand (N,), has (N,)) — chosen dst per vertex or -1.
    Three deterministic passes: max value; max hash among ties; max dst among
    hash ties (hash collisions only weaken randomization, never correctness).
    """
    NEG = jnp.int32(-1)
    v1 = jnp.where(elig, value, NEG)
    best_v = jax.ops.segment_max(v1, esrc, num_segments=n_max)
    tie1 = elig & (value == best_v[esrc]) & (best_v[esrc] > NEG)
    h = (_bij_hash(dst, seed) >> jnp.uint32(1)).astype(jnp.int32)  # non-negative
    h1 = jnp.where(tie1, h, NEG)
    best_h = jax.ops.segment_max(h1, esrc, num_segments=n_max)
    tie2 = tie1 & (h == best_h[esrc])
    d1 = jnp.where(tie2, dst, NEG)
    cand = jax.ops.segment_max(d1, esrc, num_segments=n_max)
    return cand, cand >= 0


@partial(jax.jit, static_argnames=("rounds",))
def heavy_edge_matching(g: Graph, rounds: int = 8, seed: int = 0) -> jnp.ndarray:
    """Parallel handshake HEM. Returns match (N,): mate id, or -1 unmatched.

    Padding vertices are matched to themselves (excluded from everything).
    """
    n_max = g.n_max
    vid = jnp.arange(n_max, dtype=jnp.int32)
    vmask = g.vertex_mask()
    match = jnp.where(vmask, jnp.int32(-1), vid)  # pads self-matched

    def body(r, match):
        unmatched = match < 0
        elig = g.edge_mask() & unmatched[g.esrc] & unmatched[g.adjncy]
        cand, has = _seg_pick_dst(
            elig, g.adjwgt, g.adjncy, g.esrc, n_max, seed * 1000003 + r
        )
        cand = jnp.where(has & unmatched, cand, jnp.int32(-1))
        # mutual handshake
        cand_of_cand = jnp.where(cand >= 0, cand[jnp.clip(cand, 0, n_max - 1)], -2)
        ok = (cand >= 0) & (cand_of_cand == vid)
        return jnp.where(ok, cand, match)

    return jax.lax.fori_loop(0, rounds, body, match)


def _pair_by_key(key: jnp.ndarray, elig: jnp.ndarray, match: jnp.ndarray,
                 seed: int = 0):
    """Pair eligible vertices sharing a key: sort by key, pair ranks (0,1),(2,3)...

    within each equal-key group (group-aligned so odd-size groups leave
    exactly one vertex unpaired).  Within a group, vertices are ordered by a
    seeded hash of their id, so which pairs form varies per level seed.
    """
    n_max = key.shape[0]
    INF = jnp.int32(2147483647)
    skey = jnp.where(elig, key, INF)
    vid = jnp.arange(n_max, dtype=jnp.int32)
    h = (_bij_hash(vid, seed) >> jnp.uint32(1)).astype(jnp.int32)
    o1 = jnp.argsort(h, stable=True)
    o2 = jnp.argsort(skey[o1], stable=True)
    order = o1[o2]  # eligible first by key; within a key, by seeded hash
    sk = skey[order]
    pos = jnp.arange(n_max, dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool), sk[1:] != sk[:-1]])
    group_id = jnp.cumsum(first.astype(jnp.int32)) - 1
    group_start = jnp.zeros((n_max,), jnp.int32).at[group_id].max(
        jnp.where(first, pos, 0)
    )
    rank = pos - group_start[group_id]
    valid = sk < INF
    next_same = jnp.concatenate([sk[1:] == sk[:-1], jnp.zeros((1,), bool)])
    is_lead = valid & (rank % 2 == 0) & next_same
    partner_pos = jnp.where(is_lead, pos + 1, pos - 1)
    is_follow = valid & (rank % 2 == 1)
    paired = is_lead | is_follow
    partner = order[jnp.clip(partner_pos, 0, n_max - 1)]
    new_match = match.at[order].set(
        jnp.where(paired, partner, match[order])
    )
    return new_match


@jax.jit
def twohop_matching(
    g: Graph, match: jnp.ndarray, mm_max_degree: int = 64, seed: int = 0
):
    """Leaves, twins, relatives (paper §3.1) via sort-pairing.

    ``seed`` salts the twin neighborhood hashes so each level's twin/relative
    pairing is decorrelated from every other level's.
    """
    n_max = g.n_max
    vid = jnp.arange(n_max, dtype=jnp.int32)
    vmask = g.vertex_mask()
    deg = g.degrees()

    # --- leaves: unmatched degree-1 vertices grouped by their sole neighbor
    unmatched = (match < 0) & vmask
    sole = g.adjncy[jnp.clip(g.xadj[:-1], 0, g.m_max - 1)]
    elig = unmatched & (deg == 1)
    match = _pair_by_key(jnp.where(elig, sole, 0), elig, match, seed * 4 + 1)

    # --- twins: unmatched vertices with identical neighborhoods (hash groups)
    unmatched = (match < 0) & vmask
    em = g.edge_mask()
    s_a = seed * 1000003 + 11
    s_b = seed * 1000003 + 23
    h1 = jnp.where(em, (_bij_hash(g.adjncy, s_a) >> jnp.uint32(2)).astype(jnp.int32), 0)
    h2 = jnp.where(em, (_bij_hash(g.adjncy, s_b) >> jnp.uint32(2)).astype(jnp.int32), 0)
    s1 = jax.ops.segment_sum(h1, g.esrc, num_segments=n_max)
    s2 = jax.ops.segment_sum(h2, g.esrc, num_segments=n_max)
    nbhash = ((s1 * jnp.int32(31) + s2) ^ (deg * jnp.int32(0x61C88647))) & jnp.int32(
        0x7FFFFFFF
    )
    elig = unmatched & (deg >= 1)
    match = _pair_by_key(jnp.where(elig, nbhash, 0), elig, match, seed * 4 + 2)

    # --- relatives: pair unmatched vertices within a matchmaker's neighborhood
    unmatched = (match < 0) & vmask
    matched = ~unmatched & vmask
    is_mm = matched & (deg <= mm_max_degree)
    # does this matchmaker have unmatched neighbors? (not strictly needed:
    # only unmatched vertices choose keys)
    e_mm = em & is_mm[g.adjncy] & unmatched[g.esrc]
    INF = jnp.int32(2147483647)
    mm_key = jax.ops.segment_min(
        jnp.where(e_mm, g.adjncy, INF), g.esrc, num_segments=n_max
    )
    elig = unmatched & (mm_key < INF)
    match = _pair_by_key(jnp.where(elig, mm_key, 0), elig, match, seed * 4 + 3)
    return match


@jax.jit
def coarse_map(g: Graph, match: jnp.ndarray):
    """Map fine vertices to coarse ids. Returns (cmap (N,), nc scalar).

    Singletons map alone; pairs map together; coarse ids ordered by leader id
    (preserves locality).  Padding vertices map to nc.. (ghost tail).
    """
    n_max = g.n_max
    vid = jnp.arange(n_max, dtype=jnp.int32)
    vmask = g.vertex_mask()
    mate = jnp.where(match < 0, vid, match)
    mate = jnp.where(vmask, mate, vid)
    leader = jnp.minimum(vid, mate)
    is_leader = (vid == leader) & vmask
    rank = jnp.cumsum(is_leader.astype(jnp.int32)) - 1
    nc = jnp.sum(is_leader.astype(jnp.int32))
    cmap = jnp.where(vmask, rank[leader], nc + (vid - g.n))
    return cmap, nc


@jax.jit
def contract_edges(g: Graph, cmap: jnp.ndarray):
    """Alg 3.1 re-derived: sort coarse (cu, cv) keys, segment-sum duplicates.

    Returns padded run arrays sorted lexicographically by (cu, cv):
      (cu_run, cv_run, w_run, run_valid, n_runs, vwgt_c (N,))
    """
    m_max = g.m_max
    cu = cmap[g.esrc]
    cv = cmap[g.adjncy]
    keep = g.edge_mask() & (cu != cv)
    BIG = jnp.int32(2147483647)
    cu_s = jnp.where(keep, cu, BIG)
    cv_s = jnp.where(keep, cv, BIG)
    # lexicographic (cu, cv) via two stable argsorts
    o1 = jnp.argsort(cv_s, stable=True)
    o2 = jnp.argsort(cu_s[o1], stable=True)
    order = o1[o2]
    su, sv, sw = cu_s[order], cv_s[order], jnp.where(keep, g.adjwgt, 0)[order]
    first = jnp.concatenate(
        [jnp.ones((1,), bool), (su[1:] != su[:-1]) | (sv[1:] != sv[:-1])]
    )
    run_id = jnp.cumsum(first.astype(jnp.int32)) - 1
    w_run = jax.ops.segment_sum(sw, run_id, num_segments=m_max)
    cu_run = jnp.full((m_max,), BIG).at[run_id].min(su)
    cv_run = jnp.full((m_max,), BIG).at[run_id].min(sv)
    run_valid = cu_run != BIG
    n_runs = jnp.sum(run_valid.astype(jnp.int32))
    vwgt_c = jax.ops.segment_sum(g.vwgt, cmap, num_segments=g.n_max)
    return cu_run, cv_run, w_run, run_valid, n_runs, vwgt_c


COUNTERS = ("hem_unmatched", "twohop", "twohop_pairs")
"""Counters of one device coarsening level, in :func:`coarsen_level`'s
order: real vertices HEM left unmatched, whether the two-hop pass ran
(0 or 1), and the pairs it added."""


class CoarsenLevel(NamedTuple):
    graph: Graph
    cmap: jnp.ndarray  # fine vertex -> coarse vertex of the NEXT level
    # host ints: n, m, max_degree, n_max, m_max, and on the device path the
    # COUNTERS of the matching run on this graph, where one ran
    stats: dict | None = None


def _round_up(x: int, mult: int = 8) -> int:
    return ((x + mult - 1) // mult) * mult


def coarsen_once(
    g: Graph,
    twohop_threshold: float = 0.25,
    mm_max_degree: int = 64,
    seed: int = 0,
) -> tuple[Graph, jnp.ndarray]:
    """One coarsening level, legacy host-repack path.

    Returns (coarse graph (tight arrays), cmap).  Kept as the equivalence
    baseline for :func:`coarsen_level`; prefer the device path in drivers.
    """
    match = heavy_edge_matching(g, seed=seed)
    n = int(g.n)
    unmatched = int(
        np.asarray(jnp.sum(((match < 0) & g.vertex_mask()).astype(jnp.int32)))
    )
    # float32 on purpose: bit-identical to coarsen_level's on-device trigger
    # (a float64 division here could disagree near the threshold for huge n)
    frac = np.float32(unmatched) / np.float32(max(n, 1))
    if frac > np.float32(twohop_threshold):
        match = twohop_matching(g, match, mm_max_degree, seed)
    cmap, nc_dev = coarse_map(g, match)
    cu_run, cv_run, w_run, run_valid, n_runs_dev, vwgt_c = contract_edges(g, cmap)
    nc = int(nc_dev)
    n_runs = int(n_runs_dev)
    # host repack into tight padded arrays
    cu = np.asarray(cu_run)[:n_runs]
    cv = np.asarray(cv_run)[:n_runs]
    w = np.asarray(w_run)[:n_runs]
    vw = np.asarray(vwgt_c)[:nc]
    n_max_c = _round_up(max(nc, 1))
    m_max_c = _round_up(max(n_runs, 1))
    xadj = np.zeros(n_max_c + 1, dtype=np.int64)
    np.add.at(xadj, cu + 1, 1)
    xadj = np.cumsum(xadj)
    xadj_p = np.full(n_max_c + 1, n_runs, dtype=np.int32)
    xadj_p[: nc + 1] = xadj[: nc + 1]
    adjncy_p = np.zeros(m_max_c, dtype=np.int32)
    adjncy_p[:n_runs] = cv
    adjwgt_p = np.zeros(m_max_c, dtype=np.int32)
    adjwgt_p[:n_runs] = w
    vwgt_p = np.zeros(n_max_c, dtype=np.int32)
    vwgt_p[:nc] = vw
    esrc_p = np.zeros(m_max_c, dtype=np.int32)
    esrc_p[:n_runs] = cu
    gc = Graph(
        xadj=jnp.asarray(xadj_p),
        adjncy=jnp.asarray(adjncy_p),
        adjwgt=jnp.asarray(adjwgt_p),
        vwgt=jnp.asarray(vwgt_p),
        esrc=jnp.asarray(esrc_p),
        n=jnp.asarray(nc, dtype=jnp.int32),
        m=jnp.asarray(n_runs, dtype=jnp.int32),
    )
    return gc, cmap


# ---------------------------------------------------------------------------
# Device-resident coarsening (DESIGN.md §8)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("hem_rounds",))
def coarsen_level(
    g: Graph,
    seed: int = 0,
    twohop_threshold: float = 0.25,
    mm_max_degree: int = 64,
    hem_rounds: int = 8,
) -> tuple[Graph, jnp.ndarray]:
    """One whole coarsening level as a single jitted function — no host syncs.

    HEM rounds, the two-hop trigger (``lax.cond`` on the device-computed
    unmatched fraction), ``coarse_map``, ``contract_edges``, and the
    device-side coarse-CSR build all run in one XLA program.  The coarse
    graph comes back padded at the FINE graph's capacities (``nc <= n`` and
    ``n_runs <= m`` guarantee they fit); the driver re-buckets it with
    :meth:`Graph.with_capacity` after reading the level stats.

    ``seed``/``twohop_threshold``/``mm_max_degree`` are traced, so changing
    them never recompiles; only the capacity bucket (array shapes) does.

    Returns ``(gc, cmap, counts)``: ``counts`` is an int32 (3,) array of
    the level's :data:`COUNTERS`, computed on the device.
    """
    vmask = g.vertex_mask()
    with jax.named_scope("coarsen.hem"):
        match = heavy_edge_matching(g, rounds=hem_rounds, seed=seed)
        unmatched = jnp.sum(((match < 0) & vmask).astype(jnp.int32))
        frac = (unmatched.astype(jnp.float32)
                / jnp.maximum(g.n, 1).astype(jnp.float32))
        twohop = frac > twohop_threshold
    with jax.named_scope("coarsen.twohop"):
        match = jax.lax.cond(
            twohop,
            lambda m: twohop_matching(g, m, mm_max_degree, seed),
            lambda m: m,
            match,
        )
        # two-hop pairs only vertices HEM left unmatched, two at a time
        left = jnp.sum(((match < 0) & vmask).astype(jnp.int32))
    with jax.named_scope("coarsen.contract"):
        cmap, nc = coarse_map(g, match)
        cu_run, cv_run, w_run, run_valid, n_runs, vwgt_c = contract_edges(
            g, cmap)
    with jax.named_scope("coarsen.csr"):
        gc = csr_from_edge_runs(
            cu_run, cv_run, w_run, run_valid, n_runs, vwgt_c, nc,
            n_max=g.n_max, m_max=g.m_max,
        )
    counts = jnp.stack([unmatched, twohop.astype(jnp.int32),
                        (unmatched - left) // 2])
    return gc, cmap, counts


@jax.jit
def _level_stats_dev(g: Graph) -> jnp.ndarray:
    """(n, m, max_degree) as one int32 device array — fetched in ONE transfer."""
    return jnp.stack(
        [g.n, g.m, jnp.max(g.degrees()).astype(jnp.int32)]
    ).astype(jnp.int32)


@jax.jit
def _level_stats_counts_dev(g: Graph, counts: jnp.ndarray) -> jnp.ndarray:
    """:func:`_level_stats_dev` of the coarse graph followed by the
    counters of the level that made it, for the same ONE transfer."""
    return jnp.concatenate([_level_stats_dev(g), counts])


@partial(jax.jit, static_argnames=("n_max", "m_max"))
def _rebucket(g: Graph, n_max: int, m_max: int) -> Graph:
    return g.with_capacity(n_max, m_max)


def _fetch_stats(g: Graph, level: int = 0,
                 counts: jnp.ndarray | None = None) -> tuple[dict, dict]:
    """Host stats of ``g``, and the :data:`COUNTERS` of the level that
    made it where ``counts`` is given (else empty), in one transfer."""
    with span("coarsen.fetch", level=level):
        if counts is None:
            vals = [int(x) for x in np.asarray(_level_stats_dev(g))]
        else:
            vals = [int(x) for x in
                    np.asarray(_level_stats_counts_dev(g, counts))]
    n, m, max_deg = vals[:3]
    return ({"n": n, "m": m, "max_degree": max_deg,
             "n_max": g.n_max, "m_max": g.m_max},
            dict(zip(COUNTERS, vals[3:])))


def shape_schedule(
    n_max: int,
    m_max: int,
    ratio: float = 1.6,
    safety: float = 1.25,
    stall_ratio: float = 0.95,
    align: int = 64,
    floor: int = 64,
) -> tuple[tuple[int, int], ...]:
    """Geometric capacity ladder for the device coarsening path.

    Each rung shrinks both capacities by ``min(safety / ratio, stall_ratio)``
    — HEM halves at best (``ratio``), rarely that fast (``safety`` headroom),
    and a level shrinking less than ``stall_ratio`` terminates coarsening
    anyway, so a smaller per-rung factor would only create rungs no level
    can ever land in.  Rungs are aligned so distinct graphs share buckets
    (and therefore compiled kernels).  Descending; rung 0 always fits the
    input graph.
    """
    if ratio <= 0 or safety <= 0 or align <= 0:
        raise ValueError(
            f"ratio/safety/align must be positive, got {ratio}/{safety}/{align}"
        )
    f = min(safety / ratio, stall_ratio)
    if not 0.0 < f < 1.0:
        raise ValueError(
            f"per-rung shrink min(safety/ratio, stall_ratio)={f} must be in "
            f"(0, 1), got ratio={ratio} safety={safety} "
            f"stall_ratio={stall_ratio}"
        )
    # Rung 0 is the input's EXACT capacity (not aligned up): the finest
    # level must keep the caller's padding so the final parts vector lines
    # up with the caller's graph.
    rungs = [(max(n_max, 1), max(m_max, 1))]
    n, m = rungs[0]
    while n > floor or m > floor:
        n = max(int(n * f), 1)
        m = max(int(m * f), 1)
        rung = (_round_up(n, align), _round_up(m, align))
        if rung[0] <= rungs[-1][0] and rung[1] <= rungs[-1][1]:
            if rung != rungs[-1]:
                rungs.append(rung)
        # alignment can lift a tiny rung above its predecessor — skip it
    return tuple(rungs)


def select_capacity(
    schedule: tuple[tuple[int, int], ...], n: int, m: int
) -> tuple[int, int]:
    """Smallest fitting capacity, chosen per axis.

    Vertex and edge counts shrink at different rates (meshes lose vertices
    faster than edges early on), so each axis picks its own smallest
    fitting rung — a joint pick would strand a level in an oversized
    bucket whenever one axis lags.  Rung 0 always fits both.
    """
    n_cap = min(nc for nc, _ in schedule if nc >= n)
    m_cap = min(mc for _, mc in schedule if mc >= m)
    return (n_cap, m_cap)


# ---------------------------------------------------------------------------
# Fleet coarsening — vmapped levels over a shape bucket (DESIGN.md §10)
# ---------------------------------------------------------------------------


class FleetLevel(NamedTuple):
    """One level of a bucket's batched hierarchy.

    ``graph`` is a stacked ``(B, ...)`` :class:`Graph`; ``cmap`` is
    ``(B, n_max)`` into the next level (identity rows for frozen lanes;
    None at the coarsest level).  ``active[b]`` says lane ``b`` is still
    *real* at this level — its own hierarchy reaches this deep, so the
    uncoarsening driver runs refinement for it here; frozen lanes pass
    their partition through untouched.  ``stats`` holds per-lane host
    numbers (``n``/``m``/``max_degree`` as (B,) arrays) plus the shared
    ``n_max``/``m_max`` capacity ints.
    """

    graph: Graph
    cmap: jnp.ndarray | None
    active: np.ndarray
    stats: dict | None


@jax.jit
def _stats_fleet(gb: Graph) -> jnp.ndarray:
    """(B, 3) int32 per-lane (n, m, max_degree) — one transfer per level."""
    return jax.vmap(_level_stats_dev)(gb)


@jax.jit
def _coarsen_step_fleet(gb: Graph, seed, twohop_threshold, mm_max_degree):
    """One coarsening level for every lane of a bucket, plus its stats.

    ``seed``/thresholds are traced scalars shared by all lanes, exactly as
    the standalone driver passes them — a lane's matching trajectory is the
    one its solo run would walk (the two-hop ``lax.cond`` select-masks per
    lane under vmap).
    """

    def one(g):
        gc, cmap, _ = coarsen_level(g, seed, twohop_threshold, mm_max_degree)
        return gc, cmap, _level_stats_dev(gc)

    return jax.vmap(one)(gb)


@partial(jax.jit, static_argnames=("n_max", "m_max"))
def _freeze_rebucket_fleet(
    gc: Graph, cmap: jnp.ndarray, fine: Graph, success: jnp.ndarray,
    *, n_max: int, m_max: int,
) -> tuple[Graph, jnp.ndarray]:
    """Select-mask failed lanes back to their fine graph, then re-bucket.

    Lanes that terminated (reached ``coarse_target`` earlier, or stalled
    this level) keep their fine graph frozen with an identity cmap — the
    batched analogue of the standalone driver's ``break``.  All lanes are
    then re-bucketed to the shared next capacity, which is selected to fit
    the batch max per axis, so frozen lanes always fit.
    """

    def one(gc_i, cmap_i, fine_i, s):
        g = jax.tree_util.tree_map(
            lambda a, b: jnp.where(s, a, b), gc_i, fine_i
        )
        ident = jnp.arange(cmap_i.shape[0], dtype=jnp.int32)
        return g.with_capacity(n_max, m_max), jnp.where(s, cmap_i, ident)

    return jax.vmap(one)(gc, cmap, fine, success)


def multilevel_coarsen_fleet(
    gb: Graph,
    schedule: tuple[tuple[int, int], ...],
    coarse_target: int = 4096,
    max_levels: int = 40,
    stall_ratio: float = 0.95,
    seed: int = 0,
    twohop_threshold: float = 0.25,
    mm_max_degree: int = 64,
) -> list[FleetLevel]:
    """Batched MLCoarsen over one shape bucket: list of levels, finest first.

    The whole bucket advances in lockstep — batch level ``i`` is every
    lane's own level ``i`` — but each lane terminates on ITS own schedule
    (``coarse_target`` / ``stall_ratio`` / ``max_levels``), mirroring the
    standalone driver's per-graph ``break``s via select-masking: a
    terminated lane's graph rides along frozen (identity cmap) and its
    ``active`` flag goes false for all deeper levels.  Per-level host syncs
    are one (B, 3) stat fetch, same cadence as the standalone driver.
    """
    B = gb.vwgt.shape[0]
    n_max, m_max = gb.vwgt.shape[1], gb.adjncy.shape[1]
    with span("coarsen.fetch", level=0):
        st0 = np.asarray(_stats_fleet(gb))
    n, m, md = (st0[:, j].astype(np.int64) for j in range(3))
    if schedule[0][0] < n_max or schedule[0][1] < m_max:
        raise ValueError(
            f"schedule rung 0 {schedule[0]} is below the bucket capacity "
            f"({n_max}, {m_max}) — bucket with bucket_graphs first"
        )
    dead = np.zeros(B, bool)
    depth = np.zeros(B, np.int64)
    raw: list[tuple] = []
    for lvl in range(max_levels):
        active = ~dead & (n > coarse_target)
        if not active.any():
            break
        with span("coarsen.level", level=lvl, n_max=n_max, m_max=m_max):
            gc, cmap, stc = _coarsen_step_fleet(
                gb, seed + lvl, twohop_threshold, mm_max_degree
            )
            with span("coarsen.fetch", level=lvl + 1):
                stc = np.asarray(stc).astype(np.int64)  # per-level host sync
            stalled = stc[:, 0] > stall_ratio * n
            success = active & ~stalled
            dead |= active & stalled
            if not success.any():
                break
            new_n = np.where(success, stc[:, 0], n)
            new_m = np.where(success, stc[:, 1], m)
            new_md = np.where(success, stc[:, 2], md)
            cap = select_capacity(schedule, int(new_n.max()),
                                  int(new_m.max()))
            gb2, cmap = _freeze_rebucket_fleet(
                gc, cmap, gb, jnp.asarray(success), n_max=cap[0],
                m_max=cap[1]
            )
        raw.append((gb, cmap,
                    {"n": n, "m": m, "max_degree": md,
                     "n_max": n_max, "m_max": m_max}))
        depth += success
        gb, n, m, md = gb2, new_n, new_m, new_md
        n_max, m_max = cap
    raw.append((gb, None, {"n": n, "m": m, "max_degree": md,
                           "n_max": n_max, "m_max": m_max}))
    return [
        FleetLevel(graph=g, cmap=c, active=depth >= i, stats=s)
        for i, (g, c, s) in enumerate(raw)
    ]


def multilevel_coarsen(
    g: Graph,
    coarse_target: int = 4096,
    max_levels: int = 40,
    stall_ratio: float = 0.95,
    seed: int = 0,
    mode: str = "device",
    schedule: tuple[tuple[int, int], ...] | None = None,
    twohop_threshold: float = 0.25,
    mm_max_degree: int = 64,
    bucket_ratio: float = 1.6,
    bucket_safety: float = 1.25,
    bucket_align: int = 64,
) -> list[CoarsenLevel]:
    """MLCoarsen (Alg 2.1 line 1): list of levels, finest first.

    ``levels[i].cmap`` maps level-i vertices into level-(i+1)'s graph.
    The last entry's cmap is None (coarsest graph).  Every level carries
    host ``stats`` (n, m, max_degree, capacities) captured in one per-level
    transfer, so downstream consumers (ELL backend, ConnState build) never
    re-sync.  On the device path a level's stats also hold the
    :data:`COUNTERS` of the matching run on its graph: every level but the
    coarsest, and the coarsest too where its own attempt stalled.

    ``mode="device"`` (default) runs each level via :func:`coarsen_level`
    and re-buckets results along ``schedule`` (a :func:`shape_schedule`
    ladder); the only host decisions are the termination check and the
    capacity selection.  ``mode="host"`` is the legacy per-level numpy
    repack via :func:`coarsen_once`.
    """
    if mode not in ("device", "host"):
        raise ValueError(f"unknown coarsen mode {mode!r}")
    cur = g
    stats0, _ = _fetch_stats(cur)
    if mode == "device":
        if schedule is None:
            schedule = shape_schedule(
                g.n_max, g.m_max, ratio=bucket_ratio, safety=bucket_safety,
                stall_ratio=stall_ratio, align=bucket_align,
            )
        if schedule[0][0] < stats0["n"] or schedule[0][1] < stats0["m"]:
            raise ValueError(
                f"schedule rung 0 {schedule[0]} cannot hold the input graph "
                f"(n={stats0['n']}, m={stats0['m']}) — with_capacity would "
                "silently truncate real vertices/edges"
            )
        if (cur.n_max, cur.m_max) != schedule[0]:
            cur = _rebucket(cur, *schedule[0])
            stats0 = {**stats0, "n_max": schedule[0][0],
                      "m_max": schedule[0][1]}

    def step(fine, lvl):
        """One level, its stats and the fine level's counters; per-level
        host syncs live here."""
        if mode == "host":
            gc, cmap = coarsen_once(
                fine, twohop_threshold=twohop_threshold,
                mm_max_degree=mm_max_degree, seed=seed + lvl,
            )
            return (gc, cmap) + _fetch_stats(gc, lvl + 1)
        gc, cmap, counts = coarsen_level(
            fine, seed=seed + lvl, twohop_threshold=twohop_threshold,
            mm_max_degree=mm_max_degree,
        )
        # The ONLY device-path host sync: 6 int32 (termination + capacity,
        # and the counters).
        st, counted = _fetch_stats(gc, lvl + 1, counts)
        cap = select_capacity(schedule, st["n"], st["m"])
        if cap != (gc.n_max, gc.m_max):
            gc = _rebucket(gc, *cap)
            st = {**st, "n_max": cap[0], "m_max": cap[1]}
        return gc, cmap, st, counted

    levels: list[CoarsenLevel] = []
    stats = stats0
    for lvl in range(max_levels):
        if stats["n"] <= coarse_target:
            break
        with span("coarsen.level", level=lvl, n_max=cur.n_max,
                  m_max=cur.m_max):
            gc, cmap, stats_c, counted = step(cur, lvl)
        stats = stats | counted
        if stats_c["n"] > stall_ratio * stats["n"]:  # stalled
            break
        levels.append(CoarsenLevel(graph=cur, cmap=cmap, stats=stats))
        cur, stats = gc, stats_c
    levels.append(CoarsenLevel(graph=cur, cmap=None, stats=stats))
    return levels


def project_partition(cmap: jnp.ndarray, parts_coarse: jnp.ndarray) -> jnp.ndarray:
    """ProjectPartition (Alg 2.1 line 6): fine parts = coarse parts[cmap]."""
    nc_max = parts_coarse.shape[0]
    return parts_coarse[jnp.clip(cmap, 0, nc_max - 1)]
