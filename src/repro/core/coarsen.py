"""GPU-style coarsening re-derived for TPU: HEM + two-hop matching + contraction.

Paper §3.1: heavy-edge matching first; if >25% of vertices remain unmatched,
add two-hop matches (leaves, twins, relatives).  Contraction (Alg 3.1)
deduplicates coarse edges — the paper uses per-vertex hashtables; we use a
lexicographic sort + segmented sum (TPU idiom, deterministic).

Device-resident levels (DESIGN.md §8): :func:`coarsen_level` runs a whole
level — HEM rounds, the two-hop trigger (``lax.cond`` on the
device-computed unmatched fraction), ``coarse_map``, ``contract_edges``,
and the coarse-CSR build — as ONE jitted function with zero host
transfers, for every lane of a stacked ``(B, ...)`` graph
(:func:`~repro.core.graph.map_lanes`).  :func:`multilevel_coarsen`
re-buckets each level into a precomputed geometric :func:`shape_schedule`
of (n_max, m_max) capacities, so kernels compile once per capacity rung
instead of once per exact size.  The only host sync is one ``(B, 6)``
int32 stat fetch per level: each lane's coarse size (termination check +
capacity selection), its maximum degree, and the level's matching
counters (:data:`COUNTERS`).  The level's device phases carry the scopes
``coarsen.hem``, ``coarsen.twohop``, ``coarsen.contract`` and
``coarsen.csr``.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import Graph, csr_from_edge_runs, map_lanes
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
    """One level of a stacked ``(B, ...)`` hierarchy.

    ``cmap`` is ``(B, n_max)`` into the next level (identity rows for
    frozen lanes; None at the coarsest level).  ``active[b]`` says lane
    ``b`` is still *real* at this level — its own hierarchy reaches this
    deep, so the uncoarsening driver refines it here; frozen lanes pass
    their partition through untouched.  ``stats`` holds per-lane host
    numbers as ``(B,)`` arrays — ``n``, ``m``, ``max_degree``, the
    :data:`COUNTERS` of the matching run on this graph, and ``counted``,
    whether one ran for that lane — plus the shared ``n_max``/``m_max``.
    """

    graph: Graph
    cmap: jnp.ndarray | None
    active: np.ndarray
    stats: dict


def _round_up(x: int, mult: int = 8) -> int:
    return ((x + mult - 1) // mult) * mult


# ---------------------------------------------------------------------------
# Device-resident coarsening (DESIGN.md §8)
# ---------------------------------------------------------------------------


def _graph_stats(g: Graph) -> jnp.ndarray:
    """(n, m, max_degree) of one graph as one int32 array."""
    return jnp.stack(
        [g.n, g.m, jnp.max(g.degrees()).astype(jnp.int32)]
    ).astype(jnp.int32)


def _coarsen_graph(g: Graph, seed, twohop_threshold, mm_max_degree,
                   hem_rounds: int):
    """One level of one graph: the coarse graph at ``g``'s capacities, the
    cmap, and the coarse graph's ``_graph_stats`` followed by the level's
    :data:`COUNTERS`."""
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
    return gc, cmap, jnp.concatenate([_graph_stats(gc), counts])


@partial(jax.jit, static_argnames=("hem_rounds",))
def coarsen_level(
    gb: Graph,
    seed: int = 0,
    twohop_threshold: float = 0.25,
    mm_max_degree: int = 64,
    hem_rounds: int = 8,
) -> tuple[Graph, jnp.ndarray, jnp.ndarray]:
    """One whole coarsening level of every lane of a stacked ``(B, ...)``
    graph as a single jitted function — no host syncs.

    HEM rounds, the two-hop trigger (``lax.cond`` on the device-computed
    unmatched fraction; a select where B > 1), ``coarse_map``,
    ``contract_edges``, and the device-side coarse-CSR build all run in
    one XLA program.  The coarse graphs come back padded at the FINE
    capacities (``nc <= n`` and ``n_runs <= m`` guarantee they fit); the
    driver re-buckets them after reading the level stats.

    ``seed``/``twohop_threshold``/``mm_max_degree`` are traced and shared
    by every lane, so changing them never recompiles; only the capacity
    bucket (array shapes) does.

    Returns ``(gc, cmap, stats)``: ``stats`` is ``(B, 6)`` int32, each
    lane's coarse ``n``, ``m`` and maximum degree, then the level's
    :data:`COUNTERS`, all computed on the device.
    """
    return map_lanes(
        lambda g: _coarsen_graph(g, seed, twohop_threshold, mm_max_degree,
                                 hem_rounds), gb)


@jax.jit
def _lane_stats(gb: Graph) -> jnp.ndarray:
    """(B, 3) int32 ``_graph_stats`` of every lane, for ONE transfer."""
    return map_lanes(_graph_stats, gb)


@partial(jax.jit, static_argnames=("n_max", "m_max"))
def _rebucket(gb: Graph, n_max: int, m_max: int) -> Graph:
    return map_lanes(lambda g: g.with_capacity(n_max, m_max), gb)


@jax.jit
def _freeze(gc: Graph, cmap: jnp.ndarray, fine: Graph,
            success: jnp.ndarray) -> tuple[Graph, jnp.ndarray]:
    """Select-mask the lanes that did not coarsen back to their fine graph
    with an identity cmap: the batched form of a lone graph's ``break``."""

    def one(gc_i, cmap_i, fine_i, s):
        g = jax.tree_util.tree_map(lambda a, b: jnp.where(s, a, b),
                                   gc_i, fine_i)
        ident = jnp.arange(cmap_i.shape[0], dtype=jnp.int32)
        return g, jnp.where(s, cmap_i, ident)

    return map_lanes(one, gc, cmap, fine, success)


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


def multilevel_coarsen(
    gb: Graph,
    schedule: tuple[tuple[int, int], ...] | None = None,
    coarse_target: int = 4096,
    max_levels: int = 40,
    stall_ratio: float = 0.95,
    seed: int = 0,
    twohop_threshold: float = 0.25,
    mm_max_degree: int = 64,
) -> list[CoarsenLevel]:
    """MLCoarsen (Alg 2.1 line 1) of a stacked ``(B, ...)`` bucket: list of
    levels, finest first.

    ``levels[i].cmap`` maps level-i vertices into level-(i+1)'s graph.
    The bucket advances in lockstep — batch level ``i`` is every lane's own
    level ``i``, with the seed ``seed + i`` a lone run would use — but each
    lane terminates on ITS own schedule (``coarse_target`` /
    ``stall_ratio`` / ``max_levels``): a terminated lane rides along frozen
    with an identity cmap, and its ``active`` flag goes false for all
    deeper levels.  A lane's level carries its :data:`COUNTERS` where its
    matching ran: every level but its coarsest, and the coarsest too where
    its own attempt stalled.

    Each level's capacity is the smallest ``schedule`` rung (a
    :func:`shape_schedule` ladder; by default one from the bucket's
    capacity) that fits the batch max per axis.  The host syncs are one
    ``(B, 3)`` stat fetch of the input and one ``(B, 6)`` fetch per level;
    the freeze runs only when some lane did not coarsen, the re-bucket
    only when the capacity changes, so a one-lane bucket runs exactly
    ``coarsen_level`` and ``_rebucket`` per level.
    """
    B = gb.vwgt.shape[0]
    n_max, m_max = gb.vwgt.shape[1], gb.adjncy.shape[1]
    if schedule is None:
        schedule = shape_schedule(n_max, m_max, stall_ratio=stall_ratio)
    with span("coarsen.fetch", level=0):
        st = np.asarray(_lane_stats(gb)).astype(np.int64)
    if schedule[0][0] < st[:, 0].max() or schedule[0][1] < st[:, 1].max():
        raise ValueError(
            f"schedule rung 0 {schedule[0]} cannot hold the input graphs "
            f"(n={st[:, 0].max()}, m={st[:, 1].max()}) — with_capacity "
            "would silently truncate real vertices/edges"
        )
    dead = np.zeros(B, bool)
    depth = np.zeros(B, np.int64)
    raw: list[tuple] = []  # (graph, cmap, stats), finest first
    stalled = None  # stats of a level at which every attempt stalled
    for lvl in range(max_levels):
        attempt = ~dead & (st[:, 0] > coarse_target)
        if not attempt.any():
            break
        with span("coarsen.level", level=lvl, n_max=n_max, m_max=m_max):
            gc, cmap, stc = coarsen_level(
                gb, seed + lvl, twohop_threshold, mm_max_degree)
            with span("coarsen.fetch", level=lvl + 1):
                stc = np.asarray(stc).astype(np.int64)  # per-level host sync
            success = attempt & (stc[:, 0] <= stall_ratio * st[:, 0])
            dead |= attempt & ~success
            stats = _host_stats(st, n_max, m_max, stc[:, 3:], attempt)
            if not success.any():  # gb is every lane's coarsest level
                stalled = stats
                break
            if not success.all():
                gc, cmap = _freeze(gc, cmap, gb, jnp.asarray(success))
            st = np.where(success[:, None], stc[:, :3], st)
            cap = select_capacity(schedule, int(st[:, 0].max()),
                                  int(st[:, 1].max()))
            if cap != (n_max, m_max):
                gc = _rebucket(gc, *cap)
        raw.append((gb, cmap, stats))
        depth += success
        gb, (n_max, m_max) = gc, cap
    if stalled is None:  # no lane attempted the coarsest level
        stalled = _host_stats(st, n_max, m_max,
                               np.zeros((B, len(COUNTERS)), np.int64),
                               np.zeros(B, bool))
    raw.append((gb, None, stalled))
    return [CoarsenLevel(graph=g, cmap=c, active=depth >= i, stats=s)
            for i, (g, c, s) in enumerate(raw)]


def _host_stats(st, n_max, m_max, counts, counted) -> dict:
    return {"n": st[:, 0], "m": st[:, 1], "max_degree": st[:, 2],
            "n_max": n_max, "m_max": m_max, "counted": counted,
            **dict(zip(COUNTERS, counts.T))}


def project_partition(cmap: jnp.ndarray, parts_coarse: jnp.ndarray) -> jnp.ndarray:
    """ProjectPartition (Alg 2.1 line 6): fine parts = coarse parts[cmap]."""
    nc_max = parts_coarse.shape[0]
    return parts_coarse[jnp.clip(cmap, 0, nc_max - 1)]
