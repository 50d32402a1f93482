"""Initial partitioning of the coarsest graph.

The paper calls Metis on a 4-8k-vertex coarsest graph (GPU initial
partitioning is "left for future work").  Metis isn't available here, so we
provide two JAX-native methods — both get polished by a Jet refinement pass
at the coarsest level (the multilevel driver always refines level l):

* ``random``  — hash-based balanced random assignment (PuLP-style start).
* ``voronoi`` — multi-source BFS region growing from k spread-out seeds
  (graph-growing initial partitioning, Karypis-Kumar style), which gives
  connected-ish parts that refinement improves much faster.

Both methods are seeded with a *traced* int32 scalar — all hashing is
elementwise integer arithmetic, so :func:`initial_partition_batch` can map
one trace over a batch of graphs and trial seeds (DESIGN.md §9) and trial
``t`` of the batch is bit-identical to the scalar call with ``seeds[t]``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import connectivity as cn
from repro.core.graph import Graph, map_lanes

_KNUTH = jnp.uint32(2654435761)
# Padding sort key: strictly above every real vertex key (real keys are
# hashes >> 1, so <= 0x7FFFFFFF) — a real vertex can never tie with padding.
_PAD_KEY = jnp.uint32(0xFFFFFFFF)

METHODS = ("random", "voronoi")


def _seed32(seed) -> jnp.ndarray:
    """Seed as a traced uint32 scalar (vmap-able over a trial axis)."""
    return jnp.asarray(seed).astype(jnp.uint32)


def random_partition(g: Graph, k: int, seed=0) -> jnp.ndarray:
    """Balanced random assignment: sort vertices by hash, deal round-robin.

    ``seed`` may be a Python int or a traced int32 scalar.
    """
    vid = jnp.arange(g.n_max, dtype=jnp.uint32)
    s = _seed32(seed)
    h = (vid ^ (s * jnp.uint32(7919) + jnp.uint32(13))) * _KNUTH
    h = jnp.where(g.vertex_mask(), h >> jnp.uint32(1), _PAD_KEY)
    order = jnp.argsort(h)
    rank = jnp.zeros((g.n_max,), jnp.int32).at[order].set(
        jnp.arange(g.n_max, dtype=jnp.int32)
    )
    parts = (rank % k).astype(jnp.int32)
    return jnp.where(g.vertex_mask(), parts, k)


@partial(jax.jit, static_argnames=("k",))
def _voronoi_grow(g: Graph, seeds: jnp.ndarray, k: int) -> jnp.ndarray:
    """Multi-source BFS: unassigned vertices adopt the strongest adjacent part."""
    vmask = g.vertex_mask()
    vid = jnp.arange(g.n_max, dtype=jnp.int32)
    # scatter-min keeps duplicate seeds (k > n shortfall) deterministic:
    # the smallest part id claiming a vertex wins
    parts0 = jnp.full((g.n_max,), k, jnp.int32).at[seeds].min(
        jnp.arange(k, dtype=jnp.int32)
    )
    parts0 = jnp.where(vmask, parts0, k)

    def cond(state):
        parts, changed, it = state
        return changed & (it < g.n_max)

    def body(state):
        parts, _, it = state
        # unassigned vertices: adopt the best-connected real part (cols 0..k-1)
        unassigned = (parts == k) & vmask
        mat = cn.conn_matrix(g, parts, k + 1)
        masked = mat[:, :k]
        best = jnp.argmax(masked, axis=1).astype(jnp.int32)
        has = jnp.max(masked, axis=1) > 0
        newp = jnp.where(unassigned & has, best, parts)
        changed = jnp.any(newp != parts)
        return newp, changed, it + 1

    parts, _, _ = jax.lax.while_loop(cond, body, (parts0, jnp.bool_(True), 0))
    # disconnected leftovers: deal round-robin
    left = (parts == k) & vmask
    parts = jnp.where(left, vid % k, parts)
    return parts


def spread_seeds(g: Graph, k: int, seed=0) -> jnp.ndarray:
    """k spread-out seed vertices from a seeded hash, mask-aware.

    Padding keys (:data:`_PAD_KEY`) sort strictly after every real key, so a
    padded vertex can only be picked when ``k`` exceeds the number of real
    vertices; any such shortfall is replaced round-robin over real vertex
    ids, deterministically.
    """
    vid = jnp.arange(g.n_max, dtype=jnp.uint32)
    s = _seed32(seed)
    h = (vid ^ (s * jnp.uint32(104729) + jnp.uint32(7))) * _KNUTH
    h = jnp.where(g.vertex_mask(), h >> jnp.uint32(1), _PAD_KEY)
    cand = jnp.argsort(h)[: min(k, g.n_max)].astype(jnp.int32)
    if k > g.n_max:
        # k exceeds even the padded capacity: the missing candidates are
        # forced onto the round-robin fallback below (id n_max is never < n)
        cand = jnp.concatenate([
            cand, jnp.full((k - g.n_max,), g.n_max, jnp.int32)
        ])
    fallback = jnp.arange(k, dtype=jnp.int32) % jnp.maximum(g.n, 1)
    return jnp.where(cand < g.n, cand, fallback)


def voronoi_partition(g: Graph, k: int, seed=0) -> jnp.ndarray:
    """Graph-growing from k hash-spread seeds.

    ``seed`` may be a Python int or a traced int32 scalar.
    """
    return _voronoi_grow(g, spread_seeds(g, k, seed), k)


def initial_partition(g: Graph, k: int, seed=0, method: str = "voronoi"):
    if method == "random":
        return random_partition(g, k, seed)
    if method == "voronoi":
        return voronoi_partition(g, k, seed)
    raise ValueError(f"unknown initial partition method {method!r}")


@partial(jax.jit, static_argnames=("k", "method"))
def _initial_batch(gb: Graph, seeds: jnp.ndarray, k: int, method: str):
    fn = random_partition if method == "random" else voronoi_partition
    with jax.named_scope("initial"):
        return map_lanes(
            lambda g: map_lanes(lambda s: fn(g, k, s), seeds), gb)


def initial_partition_batch(
    gb: Graph, k: int, seeds, method: str = "voronoi"
) -> jnp.ndarray:
    """(B, T, n_max) int32 seeded initial partitions of a stacked
    ``(B, ...)`` graph in ONE trace.

    Lane ``b``, trial ``t`` is bit-identical to
    ``initial_partition(unstack_graph(gb, b), k, seeds[t])`` — the hashing
    is elementwise integer arithmetic and mask-aware, and the BFS
    while-loop's batching rule freezes each lane's and trial's carry once
    its own condition goes false, so batching changes the schedule, never
    the values (DESIGN.md §9).
    """
    if method not in METHODS:
        raise ValueError(f"unknown initial partition method {method!r}")
    seeds = jnp.asarray(seeds, dtype=jnp.int32)
    if seeds.ndim != 1:
        raise ValueError(f"seeds must be 1-D (one per trial), got {seeds.shape}")
    return _initial_batch(gb, seeds, k, method)
