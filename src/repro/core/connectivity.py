"""Vertex-part connectivity — the Jet refinement data structure (paper §4.3).

The paper uses per-vertex GPU hashtables sized ``min(k, degree(v))``.  TPUs
have no efficient random-access atomics, so we provide two bulk array
backends behind one interface:

* ``dense``  — an (N, k+1) scatter-add connectivity matrix.  O(n*k) memory,
  fastest for small/medium k; every query is a masked row reduction.
* ``sorted`` — sorts per-edge (src, part) keys and segment-sums runs, then
  reduces runs per vertex.  O(m) memory like the paper's structure, fully
  deterministic (the paper documents hashtable-insert races as its source of
  nondeterminism; a stable sort has none).

Both backends answer the queries refinement needs (paper §4.3):
  1. conn(v, P_s(v)) and the best alternative part + its connectivity (Jetlp)
  2. best *valid-destination* part + connectivity (Jetrw)
  3. sum & count of connectivity over valid destinations (Jetrs)
  4. update after a move list (paper Alg 4.4)

Stateful interface (DESIGN.md §3): :class:`ConnState` packages the backend
structure together with delta-maintained part sizes and the current cutsize.
It is built once per level (:func:`build_state`), threaded through the
refinement ``lax.while_loop`` and advanced after each move list
(:func:`apply_moves`): the dense matrix is re-derived from the post-move
parts by the one scatter-add of M scalars that builds it, the sorted and
ELL structures are rewritten where a neighbor moved, the sizes advance by
a delta and the cut by one edge pass.  The ``rebuild_every`` escape hatch
(:func:`rebuild_state`) refreshes everything from scratch; for the dense
matrix that is the same arithmetic.  Advanced and rebuilt state agree
bit-exactly (integer arithmetic only); tests/test_conn_state.py asserts
this.

Batch polymorphism (DESIGN.md §§9-10): every function here is a pure jitted
function of arrays — no shape-dependent Python branches on values, no host
reads of traced quantities — so the whole interface lifts under ``jax.vmap``
over a leading trial axis, and again over a leading graph axis (the fleet
path vmaps graphs × trials).  Inside a trial-vmapped trace only genuinely
per-trial state grows the batch dimension (``mat`` / ``edge_dst_part`` /
``ell_parts``, ``sizes``, ``cut``); the static ELL adjacency
(``ell_nbr``/``ell_wgt``) and the graph stay unbatched, and the while-loop
carry fixpoint keeps them so.  Under the outer graph vmap the graph and the
ELL adjacency DO carry the B axis (each lane is a different graph), stored
once per lane, not once per (lane, trial).  The dense backend's batched
matrix is O(B·T·n·k) memory — steer large-T/B runs to ``sorted``/``ell``.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.graph import Graph

BACKENDS = ("dense", "sorted", "ell")


class ConnQueries(NamedTuple):
    """Per-vertex connectivity answers, all shape (N,)."""

    conn_self: jnp.ndarray   # conn(v, P_s(v))
    best_part: jnp.ndarray   # argmax_{p != P_s(v)} conn(v, p); == k if none
    best_conn: jnp.ndarray   # its connectivity (0 if none)


# ---------------------------------------------------------------------------
# dense backend
# ---------------------------------------------------------------------------

def conn_matrix(g: Graph, parts: jnp.ndarray, k: int) -> jnp.ndarray:
    """(N, k+1) connectivity matrix via scatter-add over directed edges.

    Column k is the ghost part (padding); padding edges carry weight 0 so
    they contribute nothing wherever they scatter.
    """
    dst_part = parts[g.adjncy]
    mat = jnp.zeros((g.n_max, k + 1), dtype=jnp.int32)
    return mat.at[g.esrc, dst_part].add(g.adjwgt)


def queries_from_matrix(mat: jnp.ndarray, parts: jnp.ndarray, k: int) -> ConnQueries:
    n_max = mat.shape[0]
    rows = jnp.arange(n_max, dtype=jnp.int32)
    conn_self = mat[rows, parts]
    cols = jnp.arange(k + 1, dtype=jnp.int32)
    # mask own part and the ghost column
    masked = jnp.where(
        (cols[None, :] == parts[:, None]) | (cols[None, :] == k), -1, mat
    )
    best_part = jnp.argmax(masked, axis=1).astype(jnp.int32)
    best_conn = jnp.max(masked, axis=1)
    none = best_conn <= 0  # weights positive: conn 0 means not adjacent
    best_part = jnp.where(none, k, best_part)
    best_conn = jnp.where(none, 0, best_conn)
    return ConnQueries(conn_self, best_part, best_conn)


@partial(jax.jit, static_argnames=("k",))
def dense_queries(g: Graph, parts: jnp.ndarray, k: int) -> ConnQueries:
    return queries_from_matrix(conn_matrix(g, parts, k), parts, k)


# ---------------------------------------------------------------------------
# sorted backend — O(m) memory
# ---------------------------------------------------------------------------

_INVALID = jnp.uint32(0xFFFFFFFF)


def runs_from_dst_part(g: Graph, dst_part: jnp.ndarray, k: int):
    """Sort directed edges by (src, dst_part) and segment-sum equal keys.

    ``dst_part`` is the per-edge destination part (M,) — either gathered
    from a parts vector or maintained incrementally in a :class:`ConnState`.
    Returns ``(run_vertex, run_part, run_conn, run_valid)``, each (M,).
    Invalid runs have ``run_vertex == g.n_max`` (ghost segment).
    """
    m_max = g.m_max
    key = g.esrc.astype(jnp.uint32) * jnp.uint32(k + 1) + dst_part.astype(jnp.uint32)
    key = jnp.where(g.edge_mask(), key, _INVALID)
    order = jnp.argsort(key)
    skey = key[order]
    sw = g.adjwgt[order]
    first = jnp.concatenate([jnp.ones((1,), bool), skey[1:] != skey[:-1]])
    run_id = jnp.cumsum(first.astype(jnp.int32)) - 1
    run_conn = jax.ops.segment_sum(sw, run_id, num_segments=m_max)
    run_key = jnp.full((m_max,), _INVALID).at[run_id].min(skey)
    valid = run_key != _INVALID
    run_vertex = jnp.where(
        valid, (run_key // jnp.uint32(k + 1)).astype(jnp.int32), g.n_max
    )
    run_part = (run_key % jnp.uint32(k + 1)).astype(jnp.int32)
    return run_vertex, run_part, run_conn, valid


def sorted_runs(g: Graph, parts: jnp.ndarray, k: int):
    """Runs built from scratch: gather each edge's destination part."""
    return runs_from_dst_part(g, parts[g.adjncy], k)


def _seg_argmax_part(
    values: jnp.ndarray,
    part_ids: jnp.ndarray,
    seg: jnp.ndarray,
    mask: jnp.ndarray,
    n_seg: int,
    k: int,
):
    """Per-segment (max value, smallest part id attaining it). Deterministic."""
    vals = jnp.where(mask, values, 0)
    best = jax.ops.segment_max(vals, seg, num_segments=n_seg)
    best = jnp.maximum(best, 0)
    seg_c = jnp.clip(seg, 0, n_seg - 1)
    is_best = mask & (values == best[seg_c]) & (values > 0)
    cand = jnp.where(is_best, part_ids, k)  # k sorts after all real parts
    part = -jax.ops.segment_max(jnp.where(is_best, -cand, -k), seg, num_segments=n_seg)
    none = best <= 0
    return jnp.where(none, 0, best), jnp.where(none, k, part).astype(jnp.int32)


def queries_from_runs(g: Graph, runs, parts: jnp.ndarray, k: int) -> ConnQueries:
    run_vertex, run_part, run_conn, valid = runs
    n_seg = g.n_max + 1
    vclip = jnp.clip(run_vertex, 0, g.n_max - 1)
    own = valid & (run_part == parts[vclip])
    conn_self = jax.ops.segment_sum(
        jnp.where(own, run_conn, 0), run_vertex, num_segments=n_seg
    )[: g.n_max]
    alt = valid & ~own
    best_conn, best_part = _seg_argmax_part(
        run_conn, run_part, run_vertex, alt, n_seg, k
    )
    return ConnQueries(
        conn_self=conn_self.astype(jnp.int32),
        best_part=best_part[: g.n_max],
        best_conn=best_conn[: g.n_max].astype(jnp.int32),
    )


@partial(jax.jit, static_argnames=("k",))
def sorted_queries(g: Graph, parts: jnp.ndarray, k: int) -> ConnQueries:
    return queries_from_runs(g, sorted_runs(g, parts, k), parts, k)


def ell_queries(g: Graph, parts: jnp.ndarray, k: int) -> ConnQueries:
    """Pallas jet_gain kernel backend (ELL-tiled VMEM sweep).

    The TPU-native replacement for the sorted/hashtable connectivity pass —
    interpret-mode on CPU (slow; use for validation), compiled on TPU.
    """
    from repro.kernels.jet_gain.ops import csr_to_ell, jet_gain

    nbr, wgt = csr_to_ell(g)
    cs, bp, bc = jet_gain(nbr, wgt, parts, k)
    return ConnQueries(conn_self=cs, best_part=bp, best_conn=bc)


def queries(g: Graph, parts: jnp.ndarray, k: int, backend: str = "dense") -> ConnQueries:
    if backend == "dense":
        return dense_queries(g, parts, k)
    if backend == "sorted":
        return sorted_queries(g, parts, k)
    if backend == "ell":
        return ell_queries(g, parts, k)
    raise ValueError(f"unknown connectivity backend {backend!r}")


# ---------------------------------------------------------------------------
# incremental update (paper Alg 4.4)
# ---------------------------------------------------------------------------

def update_conn_matrix(mat: jnp.ndarray, g: Graph, parts_old: jnp.ndarray,
                       move: jnp.ndarray, dest: jnp.ndarray) -> jnp.ndarray:
    """Incremental connectivity update after a move list (paper Alg 4.4).

    Two edge-parallel passes: decrement every neighbor's connectivity to the
    mover's source part, increment to its destination part.  The paper falls
    back to a full rebuild beyond 10% moves; on TPU both are the same two
    scatter-adds, so the incremental form is always safe.
    """
    src_moved = move[g.esrc]
    w = jnp.where(src_moved, g.adjwgt, 0)
    p_old = parts_old[g.esrc]
    p_new = dest[g.esrc]
    mat = mat.at[g.adjncy, p_old].add(-w)
    mat = mat.at[g.adjncy, p_new].add(w)
    return mat


# ---------------------------------------------------------------------------
# stateful interface — ConnState threaded through the refinement loop
# ---------------------------------------------------------------------------

class ConnState(NamedTuple):
    """Persistent per-level refinement state (paper §4.3 + Alg 4.4).

    Exactly one backend's structure is populated; the others hold zero-size
    placeholders so the pytree shape is uniform inside ``lax.while_loop``.
    ``sizes`` is delta-maintained alongside the structure; ``cut`` is
    advanced by a one-pass edge reduction over the post-move parts (the
    cheapest exact form under static shapes — see ``metrics.delta_cutsize``)
    and carried here so queries, balance checks, and best-tracking never
    recompute it.  The dense ``mat`` advances the same way: re-derived from
    the post-move parts by :func:`conn_matrix` (see :func:`apply_moves`).
    """

    sizes: jnp.ndarray          # (k,) int32 part weights
    cut: jnp.ndarray            # int32 scalar current cutsize
    mat: jnp.ndarray            # dense: (N, k+1) int32; else (0, 0)
    edge_dst_part: jnp.ndarray  # sorted: (M,) int32 dst part per edge; else (0,)
    ell_nbr: jnp.ndarray        # ell: (N, D) int32 neighbor ids; else (0, 0)
    ell_wgt: jnp.ndarray        # ell: (N, D) int32 edge weights; else (0, 0)
    ell_parts: jnp.ndarray      # ell: (N, D) int32 neighbor parts; else (0, 0)
    moves_applied: jnp.ndarray  # int32 move lists since last full (re)build


def _e1() -> jnp.ndarray:
    return jnp.zeros((0,), jnp.int32)


def _e2() -> jnp.ndarray:
    return jnp.zeros((0, 0), jnp.int32)


def build_state(
    g: Graph,
    parts: jnp.ndarray,
    k: int,
    backend: str = "dense",
    max_degree: int | None = None,
) -> ConnState:
    """Build the full state from a parts vector (once per level).

    ``parts`` must already map padding vertices to the ghost part ``k``.
    ``max_degree`` (ell only) must be static when tracing under jit.
    """
    from repro.core import metrics

    sizes = metrics.part_sizes(g, parts, k).astype(jnp.int32)
    cut = metrics.cutsize(g, parts).astype(jnp.int32)
    mat, edp = _e2(), _e1()
    nbr = wgt = nparts = _e2()
    if backend == "dense":
        mat = conn_matrix(g, parts, k)
    elif backend == "sorted":
        edp = jnp.where(g.edge_mask(), parts[g.adjncy], k).astype(jnp.int32)
    elif backend == "ell":
        from repro.kernels.jet_gain.ops import csr_to_ell, lookup_nbr_parts

        nbr, wgt = csr_to_ell(g, max_degree)
        nparts = lookup_nbr_parts(nbr, parts, k)
    else:
        raise ValueError(f"unknown connectivity backend {backend!r}")
    return ConnState(sizes, cut, mat, edp, nbr, wgt, nparts, jnp.int32(0))


def rebuild_state(
    g: Graph, state: ConnState, parts: jnp.ndarray, k: int, backend: str
) -> ConnState:
    """Full refresh from ``parts`` — the ``rebuild_every`` escape hatch.

    Reuses the static ELL adjacency (it never changes within a level).
    """
    from repro.core import metrics

    sizes = metrics.part_sizes(g, parts, k).astype(jnp.int32)
    cut = metrics.cutsize(g, parts).astype(jnp.int32)
    upd = {"sizes": sizes, "cut": cut, "moves_applied": jnp.int32(0)}
    if backend == "dense":
        upd["mat"] = conn_matrix(g, parts, k)
    elif backend == "sorted":
        upd["edge_dst_part"] = jnp.where(
            g.edge_mask(), parts[g.adjncy], k
        ).astype(jnp.int32)
    elif backend == "ell":
        from repro.kernels.jet_gain.ops import lookup_nbr_parts

        upd["ell_parts"] = lookup_nbr_parts(state.ell_nbr, parts, k)
    else:
        raise ValueError(f"unknown connectivity backend {backend!r}")
    return state._replace(**upd)


def apply_moves(
    g: Graph,
    state: ConnState,
    parts_old: jnp.ndarray,
    move: jnp.ndarray,
    dest: jnp.ndarray,
    k: int,
    backend: str,
) -> ConnState:
    """Advance the state past one move list (paper Alg 4.4, all backends).

    The dense matrix is re-derived from the post-move parts by
    :func:`conn_matrix`: one gather of M parts, which the cut's edge pass
    shares, and one scatter-add of M scalars.  Under static shapes a masked
    delta touches every edge too, and gathers ``move``, ``parts_old`` and
    ``dest`` at every edge; on a TPU v5e M-length gathers dominate, and the
    rebuild took about half the time of a one-hot prefix-sum delta and
    under half that of :func:`update_conn_matrix` at the benchmark's
    finest shapes (PERF.md §5).  The sorted / ELL structures get masked
    elementwise rewrites, part sizes a one-hot delta reduction, and the cut
    a one-pass edge reduction.  Bit-exact against :func:`rebuild_state`
    (integer arithmetic throughout).
    """
    from repro.core import metrics

    parts_new = jnp.where(move, dest, parts_old)
    sizes = metrics.delta_part_sizes(g, state.sizes, parts_old, move, dest, k)
    cut = metrics.delta_cutsize(g, state.cut, parts_old, parts_new)
    upd = {"sizes": sizes, "cut": cut,
           "moves_applied": state.moves_applied + 1}
    if backend == "dense":
        upd["mat"] = conn_matrix(g, parts_new, k)
    elif backend == "sorted":
        hit = g.edge_mask() & move[g.adjncy]
        upd["edge_dst_part"] = jnp.where(
            hit, dest[g.adjncy], state.edge_dst_part
        ).astype(jnp.int32)
    elif backend == "ell":
        from repro.kernels.jet_gain.ops import update_nbr_parts

        upd["ell_parts"] = update_nbr_parts(
            state.ell_nbr, state.ell_parts, move, dest, k
        )
    else:
        raise ValueError(f"unknown connectivity backend {backend!r}")
    return state._replace(**upd)


def state_queries(
    g: Graph, state: ConnState, parts: jnp.ndarray, k: int, backend: str
) -> ConnQueries:
    """Jetlp queries from the maintained state — no rebuild, no part gather."""
    if backend == "dense":
        return queries_from_matrix(state.mat, parts, k)
    if backend == "sorted":
        runs = runs_from_dst_part(g, state.edge_dst_part, k)
        return queries_from_runs(g, runs, parts, k)
    if backend == "ell":
        from repro.kernels.jet_gain.ops import jet_gain_from_parts

        cs, bp, bc = jet_gain_from_parts(
            state.ell_parts, state.ell_wgt, parts, k
        )
        return ConnQueries(conn_self=cs, best_part=bp, best_conn=bc)
    raise ValueError(f"unknown connectivity backend {backend!r}")


# -- valid-destination queries (Jetrw / Jetrs) from the maintained state ----

def _rw_from_matrix(mat: jnp.ndarray, valid_parts: jnp.ndarray, k: int):
    """Best valid-destination part per vertex: (best_conn, best_part, any)."""
    colmask = jnp.concatenate([valid_parts, jnp.zeros((1,), bool)])
    masked = jnp.where(colmask[None, :], mat, -1)
    best_conn = jnp.max(masked, axis=1)
    best_part = jnp.argmax(masked, axis=1).astype(jnp.int32)
    has = best_conn > 0
    return jnp.maximum(best_conn, 0), jnp.where(has, best_part, k), has


def _rw_from_runs(g: Graph, runs, valid_parts: jnp.ndarray, k: int):
    run_vertex, run_part, run_conn, valid = runs
    n_seg = g.n_max + 1
    vp = jnp.concatenate([valid_parts, jnp.zeros((1,), bool)])
    mask = valid & vp[jnp.clip(run_part, 0, k)]
    best_conn, best_part = _seg_argmax_part(
        run_conn, run_part, run_vertex, mask, n_seg, k
    )
    has = best_conn[: g.n_max] > 0
    return (
        jnp.maximum(best_conn[: g.n_max], 0),
        jnp.where(has, best_part[: g.n_max], k).astype(jnp.int32),
        has,
    )


def _rs_from_matrix(mat: jnp.ndarray, valid_parts: jnp.ndarray, k: int):
    """Sum and count of connectivity over *adjacent* valid parts per vertex."""
    colmask = jnp.concatenate([valid_parts, jnp.zeros((1,), bool)])
    sel = jnp.where(colmask[None, :], mat, 0)
    s = jnp.sum(sel, axis=1)
    cnt = jnp.sum((sel > 0).astype(jnp.int32), axis=1)
    return s, cnt


def _rs_from_runs(g: Graph, runs, valid_parts: jnp.ndarray, k: int):
    run_vertex, run_part, run_conn, valid = runs
    n_seg = g.n_max + 1
    vp = jnp.concatenate([valid_parts, jnp.zeros((1,), bool)])
    mask = valid & vp[jnp.clip(run_part, 0, k)]
    s = jax.ops.segment_sum(
        jnp.where(mask, run_conn, 0), run_vertex, num_segments=n_seg
    )[: g.n_max]
    cnt = jax.ops.segment_sum(
        jnp.where(mask & (run_conn > 0), 1, 0).astype(jnp.int32),
        run_vertex,
        num_segments=n_seg,
    )[: g.n_max]
    return s, cnt


def _state_matrix(g: Graph, state: ConnState, k: int, backend: str):
    """A dense (N, k+1) view of the state for matrix-shaped queries.

    ELL reconstructs it from the *maintained* neighbor parts — an O(N*D)
    scatter, used only on (rare) rebalance iterations.
    """
    if backend == "dense":
        return state.mat
    if backend == "ell":
        from repro.kernels.jet_gain.ops import ell_to_matrix

        return ell_to_matrix(state.ell_parts, state.ell_wgt, k)
    raise ValueError(f"unknown connectivity backend {backend!r}")


def rw_queries(
    g: Graph, state: ConnState, k: int, valid_parts: jnp.ndarray, backend: str
):
    """Jetrw: best valid-destination part from the maintained state."""
    if backend == "sorted":
        runs = runs_from_dst_part(g, state.edge_dst_part, k)
        return _rw_from_runs(g, runs, valid_parts, k)
    return _rw_from_matrix(_state_matrix(g, state, k, backend), valid_parts, k)


def rs_queries(
    g: Graph, state: ConnState, k: int, valid_parts: jnp.ndarray, backend: str
):
    """Jetrs: sum/count over valid destinations from the maintained state."""
    if backend == "sorted":
        runs = runs_from_dst_part(g, state.edge_dst_part, k)
        return _rs_from_runs(g, runs, valid_parts, k)
    return _rs_from_matrix(_state_matrix(g, state, k, backend), valid_parts, k)
