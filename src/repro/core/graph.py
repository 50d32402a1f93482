"""Padded CSR graph container — the core data structure of the Jet partitioner.

TPU discipline: every array has a static (padded) shape; the *true* sizes
``n`` (vertices) and ``m`` (directed edges) ride along as traced int32
scalars.  Padding vertices have weight 0 and degree 0; padding edges have
weight 0 and src/dst 0, so every weighted reduction ignores them for free.
Count-style reductions must apply :func:`edge_mask` / :func:`vertex_mask`.

The graph stores each undirected edge twice (as in CSR adjacency used by
Metis/Jet).  ``esrc[e]`` is the source vertex of directed edge ``e`` —
stored explicitly so edge-parallel kernels avoid a searchsorted per access.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


def _fit(a: jnp.ndarray, size: int, pad: str = "zeros") -> jnp.ndarray:
    """Slice or pad a 1-D array to a static length (jit-safe)."""
    cur = a.shape[0]
    if size == cur:
        return a
    if size < cur:
        return a[:size]
    if pad == "edge":
        return jnp.pad(a, (0, size - cur), mode="edge")
    return jnp.pad(a, (0, size - cur))


class Graph(NamedTuple):
    """Padded CSR graph. Shapes: xadj (N+1,), adjncy/adjwgt/esrc (M,), vwgt (N,)."""

    xadj: jnp.ndarray    # int32 (N+1,) row offsets; xadj[v+1]==xadj[v] for pads
    adjncy: jnp.ndarray  # int32 (M,) neighbor (dst) ids; 0 for padding edges
    adjwgt: jnp.ndarray  # int32 (M,) edge weights; 0 for padding edges
    vwgt: jnp.ndarray    # int32 (N,) vertex weights; 0 for padding vertices
    esrc: jnp.ndarray    # int32 (M,) source vertex of each directed edge
    n: jnp.ndarray       # int32 scalar, true vertex count (n <= N)
    m: jnp.ndarray       # int32 scalar, true directed edge count (m <= M)

    @property
    def n_max(self) -> int:
        return self.vwgt.shape[0]

    @property
    def m_max(self) -> int:
        return self.adjncy.shape[0]

    def vertex_mask(self) -> jnp.ndarray:
        return jnp.arange(self.n_max, dtype=jnp.int32) < self.n

    def edge_mask(self) -> jnp.ndarray:
        return jnp.arange(self.m_max, dtype=jnp.int32) < self.m

    def degrees(self) -> jnp.ndarray:
        return self.xadj[1:] - self.xadj[:-1]

    def total_vweight(self) -> jnp.ndarray:
        return jnp.sum(self.vwgt)

    def total_eweight(self) -> jnp.ndarray:
        """Sum of undirected edge weights (each edge stored twice)."""
        return jnp.sum(self.adjwgt) // 2

    def with_capacity(self, n_max: int, m_max: int) -> "Graph":
        """Re-bucket to new padded capacities (jit-safe).

        Requires ``n <= n_max`` and ``m <= m_max`` — padding invariants are
        preserved: the grown ``xadj`` tail repeats ``xadj[-1] == m``, and
        grown edge/vertex arrays are zero.  ``n``/``m`` stay traced.
        """
        return Graph(
            xadj=_fit(self.xadj, n_max + 1, pad="edge"),
            adjncy=_fit(self.adjncy, m_max),
            adjwgt=_fit(self.adjwgt, m_max),
            vwgt=_fit(self.vwgt, n_max),
            esrc=_fit(self.esrc, m_max),
            n=self.n,
            m=self.m,
        )


# ---------------------------------------------------------------------------
# Fleet batching — stacked graphs and shape buckets (DESIGN.md §10)
# ---------------------------------------------------------------------------

def stack_graphs(graphs: "list[Graph]") -> Graph:
    """Stack same-capacity graphs along a leading batch axis.

    The result is a plain :class:`Graph` pytree whose every leaf carries a
    leading ``(B, ...)`` axis — the lane axis of the V-cycle driver, which
    maps its per-graph steps over it with :func:`map_lanes`.  The
    ``n_max`` / ``m_max`` properties read leaf ``shape[0]`` and are
    therefore meaningless on a stacked graph; use the per-leaf shapes
    (``vwgt.shape == (B, N)``) or :func:`unstack_graph` instead.
    """
    if not graphs:
        raise ValueError("stack_graphs needs at least one graph")
    cap = (graphs[0].n_max, graphs[0].m_max)
    for g in graphs[1:]:
        if (g.n_max, g.m_max) != cap:
            raise ValueError(
                f"stack_graphs needs uniform capacities, got {cap} vs "
                f"{(g.n_max, g.m_max)} — re-bucket with with_capacity first"
            )
    return Graph(*(
        jnp.stack([getattr(g, f) for g in graphs]) for f in Graph._fields
    ))


def unstack_graph(gb: Graph, b: int) -> Graph:
    """Member ``b`` of a stacked graph (device-side slice, no copy)."""
    return Graph(*(leaf[b] for leaf in gb))


def map_lanes(fn, *args):
    """``fn`` applied along the leading axis of every leaf of ``args``.

    The one batching rule of the V-cycle (DESIGN.md §9): an axis of size 1
    is squeezed and ``fn`` runs unbatched, so its ``lax.cond``s stay conds
    and compute only the branch taken; any other size is ``jax.vmap``ped,
    where a batched predicate turns a cond into a select.  The values are
    the same either way.  Per-graph steps map it over the lane axis B,
    per-trial steps over the trial axis T; ``None`` arguments pass through.
    """
    size = jax.tree_util.tree_leaves(args)[0].shape[0]
    if size == 1:
        out = fn(*jax.tree_util.tree_map(lambda x: x[0], args))
        return jax.tree_util.tree_map(lambda x: x[None], out)
    return jax.vmap(fn)(*args)


def bucket_graphs(
    graphs: "list[Graph]",
    ratio: float = 1.6,
    safety: float = 1.25,
    stall_ratio: float = 0.95,
    align: int = 64,
    schedule: "tuple[tuple[int, int], ...] | None" = None,
):
    """Group a fleet of graphs into static shape buckets on a shared ladder.

    Builds ONE §8 capacity ladder spanning the whole fleet (top rung =
    fleet max, aligned to ``align``) and assigns each graph the smallest
    fitting ``(n_cap, m_cap)`` rung pair, chosen per axis like
    :func:`~repro.core.coarsen.select_capacity`.  Graphs of different true
    sizes land in the same bucket whenever they round to the same rungs —
    that sharing is the whole point: one compiled executable per (bucket,
    level-rung) signature serves every member.

    With ``schedule`` given, the ladder is NOT rebuilt from the fleet max:
    assignment runs on the caller's fixed ladder, so rung pairs (and
    therefore compiled-executable signatures) stay stable across calls —
    the serving contract (DESIGN.md §11).  Every graph must fit the
    ladder's top rung; oversized graphs raise ``ValueError``.

    Returns ``(schedule, buckets)`` where ``buckets`` maps a capacity pair
    to the list of graph indices assigned to it (insertion-ordered by first
    member).  Admission is a host decision, so it costs one blocking fetch
    of all (n, m) pairs here — the last admission sync before results.
    """
    from repro.core.coarsen import select_capacity, shape_schedule, _round_up

    if not graphs:
        raise ValueError("bucket_graphs needs at least one graph")
    sizes = [(int(n), int(m))
             for n, m in jax.device_get([(g.n, g.m) for g in graphs])]
    if schedule is None:
        n_top = _round_up(max(max(n for n, _ in sizes), 1), align)
        m_top = _round_up(max(max(m for _, m in sizes), 1), align)
        schedule = shape_schedule(n_top, m_top, ratio=ratio, safety=safety,
                                  stall_ratio=stall_ratio, align=align)
    else:
        n_top = max(nc for nc, _ in schedule)
        m_top = max(mc for _, mc in schedule)
        for i, (n, m) in enumerate(sizes):
            if n > n_top or m > m_top:
                raise ValueError(
                    f"graph {i} (n={n}, m={m}) exceeds the fixed ladder's "
                    f"top rung ({n_top}, {m_top}) — raise the ladder or "
                    "partition it standalone"
                )
    buckets: dict[tuple[int, int], list[int]] = {}
    for i, (n, m) in enumerate(sizes):
        buckets.setdefault(select_capacity(schedule, n, m), []).append(i)
    return schedule, buckets


class StackedBucket(NamedTuple):
    """One pre-stacked shape bucket, ready for ``partition_fleet_stacked``.

    ``graph`` is a stacked ``(B, ...)`` :class:`Graph` at ``capacity``;
    ``tags`` carries one caller id per lane (``None`` marks a filler lane
    — a real graph stacked only to pin the batch width, whose result the
    driver drops); ``orig_n_max`` records each lane's own padding so
    results can be restored to the caller's shape contract.
    """

    capacity: tuple
    graph: Graph
    tags: tuple
    orig_n_max: tuple


class BucketAssembler:
    """Incremental bucket assembly on a FIXED capacity ladder (§11 serving).

    ``add`` queues graphs host-side (no device work); ``flush`` performs
    ONE batched (n, m) admission fetch, assigns each graph its smallest
    fitting rung pair on the pinned ladder, re-pads members with
    :meth:`Graph.with_capacity`, and returns stacked buckets.  Unlike
    :func:`bucket_graphs`' default path — which derives the ladder from
    the fleet max, so two fleets can disagree on rungs — the ladder here
    is pinned at construction, keeping compiled-executable signatures
    stable across flushes: the whole point of warm serving.

    ``lanes`` pins every flushed bucket to a fixed batch width: buckets
    with fewer members are padded with filler copies of their first
    member (``tags`` entry ``None``), buckets with more are split into
    ``lanes``-wide chunks.  A fixed width keeps B out of the signature
    degrees of freedom — one executable per (rung, k), whatever the
    arrival pattern.  ``lanes=None`` stacks each bucket at its natural
    occupancy (the ``partition_fleet`` behavior).
    """

    def __init__(self, schedule, lanes: "int | None" = None):
        if lanes is not None and lanes < 1:
            raise ValueError(f"lanes must be >= 1, got {lanes}")
        self.schedule = tuple(schedule)
        self.lanes = lanes
        self._pending: list = []  # (tag, Graph)

    def __len__(self) -> int:
        return len(self._pending)

    def add(self, tag, g: Graph) -> None:
        self._pending.append((tag, g))

    def flush(self) -> "list[StackedBucket]":
        if not self._pending:
            return []
        tags = [t for t, _ in self._pending]
        graphs = [g for _, g in self._pending]
        self._pending = []
        _, bucket_map = bucket_graphs(graphs, schedule=self.schedule)
        out = []
        for cap in sorted(bucket_map, reverse=True):
            idxs = bucket_map[cap]
            members = [
                g if (g.n_max, g.m_max) == cap else g.with_capacity(*cap)
                for g in (graphs[i] for i in idxs)
            ]
            width = self.lanes or len(members)
            for lo in range(0, len(members), width):
                chunk = members[lo: lo + width]
                chunk_tags = [tags[i] for i in idxs[lo: lo + width]]
                chunk_nmax = [graphs[i].n_max for i in idxs[lo: lo + width]]
                fill = width - len(chunk)
                if fill:
                    chunk = chunk + [chunk[0]] * fill
                    chunk_tags += [None] * fill
                    chunk_nmax += [cap[0]] * fill
                out.append(StackedBucket(
                    capacity=cap,
                    graph=stack_graphs(chunk),
                    tags=tuple(chunk_tags),
                    orig_n_max=tuple(chunk_nmax),
                ))
        return out


def csr_from_edge_runs(
    cu: jnp.ndarray,
    cv: jnp.ndarray,
    w: jnp.ndarray,
    valid: jnp.ndarray,
    n_edges: jnp.ndarray,
    vwgt: jnp.ndarray,
    n_vertices: jnp.ndarray,
    *,
    n_max: int,
    m_max: int,
) -> Graph:
    """Device-side CSR constructor from deduplicated edge runs (jit-safe).

    ``cu``/``cv``/``w`` are edge runs sorted lexicographically by (cu, cv)
    with all valid runs contiguous at the front (``valid`` marks them);
    ``n_edges``/``n_vertices`` are traced true counts.  Builds ``xadj`` by
    segment-count + cumsum entirely on device — no host repack.
    """
    counts = jnp.zeros(n_max, dtype=jnp.int32).at[
        jnp.where(valid, cu, 0)
    ].add(valid.astype(jnp.int32), mode="drop")
    xadj = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts).astype(jnp.int32)]
    )
    return Graph(
        xadj=xadj,
        adjncy=_fit(jnp.where(valid, cv, 0).astype(jnp.int32), m_max),
        adjwgt=_fit(jnp.where(valid, w, 0).astype(jnp.int32), m_max),
        vwgt=_fit(vwgt.astype(jnp.int32), n_max),
        esrc=_fit(jnp.where(valid, cu, 0).astype(jnp.int32), m_max),
        n=n_vertices.astype(jnp.int32),
        m=n_edges.astype(jnp.int32),
    )


def build_csr_host(
    n: int,
    edges: np.ndarray,
    eweights: np.ndarray | None = None,
    vweights: np.ndarray | None = None,
    n_max: int | None = None,
    m_max: int | None = None,
) -> Graph:
    """Host-side CSR builder from an undirected edge list (u, v) pairs.

    Removes self loops, deduplicates parallel edges (summing weights), and
    symmetrizes.  ``edges`` is (E, 2) int; weights default to 1.
    """
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    if eweights is None:
        eweights = np.ones(edges.shape[0], dtype=np.int64)
    else:
        eweights = np.asarray(eweights, dtype=np.int64)
    # Drop self loops.
    keep = edges[:, 0] != edges[:, 1]
    edges, eweights = edges[keep], eweights[keep]
    # Canonicalize + dedup (sum weights of parallel edges).
    lo = np.minimum(edges[:, 0], edges[:, 1])
    hi = np.maximum(edges[:, 0], edges[:, 1])
    key = lo * n + hi
    order = np.argsort(key, kind="stable")
    key, lo, hi, eweights = key[order], lo[order], hi[order], eweights[order]
    uniq, inv = np.unique(key, return_inverse=True)
    w = np.zeros(uniq.shape[0], dtype=np.int64)
    np.add.at(w, inv, eweights)
    lo = (uniq // n).astype(np.int64)
    hi = (uniq % n).astype(np.int64)
    # Symmetrize.
    src = np.concatenate([lo, hi])
    dst = np.concatenate([hi, lo])
    ew = np.concatenate([w, w])
    order = np.argsort(src * n + dst, kind="stable")
    src, dst, ew = src[order], dst[order], ew[order]
    m = src.shape[0]
    xadj = np.zeros(n + 1, dtype=np.int64)
    np.add.at(xadj, src + 1, 1)
    xadj = np.cumsum(xadj)
    if vweights is None:
        vweights = np.ones(n, dtype=np.int64)
    else:
        vweights = np.asarray(vweights, dtype=np.int64)

    n_max = int(n_max) if n_max is not None else int(n)
    m_max = int(m_max) if m_max is not None else int(m)
    assert n_max >= n and m_max >= m, (n_max, n, m_max, m)

    xadj_p = np.full(n_max + 1, m, dtype=np.int32)
    xadj_p[: n + 1] = xadj
    adjncy_p = np.zeros(m_max, dtype=np.int32)
    adjncy_p[:m] = dst
    adjwgt_p = np.zeros(m_max, dtype=np.int32)
    adjwgt_p[:m] = ew
    vwgt_p = np.zeros(n_max, dtype=np.int32)
    vwgt_p[:n] = vweights
    esrc_p = np.zeros(m_max, dtype=np.int32)
    esrc_p[:m] = src
    return Graph(
        xadj=jnp.asarray(xadj_p),
        adjncy=jnp.asarray(adjncy_p),
        adjwgt=jnp.asarray(adjwgt_p),
        vwgt=jnp.asarray(vwgt_p),
        esrc=jnp.asarray(esrc_p),
        n=jnp.asarray(n, dtype=jnp.int32),
        m=jnp.asarray(m, dtype=jnp.int32),
    )


def graph_to_host(g: Graph) -> tuple[int, np.ndarray, np.ndarray, np.ndarray]:
    """Return (n, edges(u<v), eweights, vweights) on host, unpadded."""
    n = int(g.n)
    m = int(g.m)
    src = np.asarray(g.esrc)[:m]
    dst = np.asarray(g.adjncy)[:m]
    w = np.asarray(g.adjwgt)[:m]
    keep = src < dst
    return n, np.stack([src[keep], dst[keep]], axis=1), w[keep], np.asarray(g.vwgt)[:n]


def validate_host(g: Graph) -> None:
    """Structural invariants — host-side, for tests."""
    n, m = int(g.n), int(g.m)
    xadj = np.asarray(g.xadj)
    adjncy = np.asarray(g.adjncy)
    adjwgt = np.asarray(g.adjwgt)
    esrc = np.asarray(g.esrc)
    assert xadj[0] == 0 and xadj[n] == m
    assert np.all(np.diff(xadj[: n + 1]) >= 0)
    assert np.all(xadj[n:] == m)
    assert np.all(adjncy[:m] >= 0) and np.all(adjncy[:m] < n)
    assert np.all(adjwgt[:m] > 0)
    assert np.all(adjwgt[m:] == 0)
    # esrc consistent with xadj
    expect_src = np.repeat(np.arange(n), np.diff(xadj[: n + 1]))
    assert np.array_equal(esrc[:m], expect_src)
    # no self loops
    assert np.all(adjncy[:m] != esrc[:m])
    # symmetric with equal weights
    fwd = {}
    for e in range(m):
        fwd[(int(esrc[e]), int(adjncy[e]))] = int(adjwgt[e])
    for (u, v), w in fwd.items():
        assert fwd.get((v, u)) == w, f"asymmetric edge {(u, v)}"
