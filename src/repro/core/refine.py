"""Jet refinement — Jetlp (Alg 4.2) and the outer driver (Alg 4.1).

Everything here is one jittable ``lax.while_loop`` per level: the paper's
bulk-synchronous design maps 1:1 onto XLA.  The three iteration kinds
(Jetlp / weak rebalance / strong rebalance) are ``lax.cond`` branches chosen
by the balance state, exactly as Alg 4.1 alternates them.  They stay real
branches only unbatched: under a ``vmap`` that batches the predicates (T > 1
trials, fleet lanes) each ``cond`` lowers to a select, and every iteration
computes all three move kinds (DESIGN.md §9).

Stateful refinement (DESIGN.md §3): a :class:`~repro.core.
connectivity.ConnState` — connectivity structure, part sizes, and cutsize —
is built once per level, threaded through :class:`RefineState` inside the
loop, and advanced after every move list (Alg 4.4): part sizes by a delta,
the cut by one edge pass, the sorted / ELL structures by rewrites where a
neighbor moved, and the dense matrix by the one scatter-add that builds it,
from the post-move parts (on a TPU v5e that beat every delta form measured,
PERF.md §5).  ``rebuild_every`` is the periodic-full-rebuild escape hatch
(1 == the paper's always-rebuild fallback, 0 == never); on the dense
backend it rebuilds the matrix and the cut by the advance's own arithmetic
and differs only in recounting the part sizes.
All three iteration kinds consume the same ``ConnQueries`` computed once
per iteration from the threaded state.

Jetlp's afterburner reads each edge endpoint's (gain, destination, part)
with one gather, so an iteration holds two M-length gathers, not eight;
the ``X`` masks at both endpoints drop out exactly, and the ``segment_sum``
over ``esrc`` does not claim sorted indices (padding edges carry
``esrc = 0``); see ``jetlp_moves``.

Deviations from the paper are documented in DESIGN.md §6; the functional
behaviour (filters, afterburner ordering, locking, best-partition tracking
with the phi tolerance) follows the paper line by line.

Batch polymorphism (DESIGN.md §§9-10): ``_refine_loop`` (and everything it
calls — ``jetlp_moves``, the rebalance kernels, the ConnState interface) is
vmappable over a leading trial axis, and over a further graph axis for the
fleet path.  Traced stats stay traced; the loop condition is per-trial, and
JAX's ``while_loop`` batching rule freezes a trial's carry once its own
condition goes false, so a vmapped trial walks the exact trajectory of its
sequential run — the batch merely runs until the LAST trial's patience
expires.  The optional ``active`` flag extends the same mechanism to whole
lanes: a fleet lane whose own hierarchy ends above the current level enters
with ``active=False``, its condition is false at iteration 0, and its
(identity-projected) partition passes through bit-untouched.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import connectivity as cn
from repro.core import metrics
from repro.core import rebalance as rb
from repro.core.graph import Graph


VARIANTS = ("baseline", "locks", "weak_ab", "full_ab", "full")


def variant_flags(variant: str):
    """(use_ratio_filter, use_afterburner, use_locks) — Table 3 ablations."""
    return {
        "baseline": (False, False, False),
        "locks": (False, False, True),
        "weak_ab": (False, True, False),
        "full_ab": (True, True, False),
        "full": (True, True, True),
    }[variant]


def jetlp_moves(
    g: Graph,
    parts: jnp.ndarray,
    k: int,
    lock: jnp.ndarray,
    c: float,
    backend: str = "dense",
    variant: str = "full",
    queries: cn.ConnQueries | None = None,
):
    """One unconstrained LP pass (Alg 4.2). Returns (move_mask, dest).

    First filter: Eq 4.3 ``-F(v) < floor(c * conn(v, P_s))  or  F(v) >= 0``.
    Second filter (afterburner): recompute gain against the approximate next
    state merged under ``ord`` (Eq 4.1), keep non-negative.  ``variant``
    selects the paper's §7.1.4 ablations (see ``variant_flags``).

    ``queries`` is the shared per-iteration ConnQueries from the threaded
    state; standalone callers may omit it and pay for a one-off build.

    The afterburner reads each directed edge's head ``u = adjncy`` and tail
    ``v = esrc`` with one gather each, from one ``(N, 3)`` table of
    ``(F, Pd, parts)``: two M-length gathers where one per field and
    endpoint took eight (on a v5e a row gather of three int32s costs a
    fifth of one scalar gather of the same indices, PERF.md §5).  Two
    masks of the paper's form drop
    out exactly.  ``X[v]``: ``move = X & (F2 >= 0)`` discards every vertex
    outside X, so what the edges of such a ``v`` sum to is never read.
    ``X[u]``: outside X, ``Pd[u] == parts[u]``, so ``u`` moving "first"
    leaves its part where it is.  The ``segment_sum`` over ``esrc`` does
    not claim ``indices_are_sorted``: padding edges carry ``esrc = 0``
    after the real ones (``graph.build_csr_host``, ``csr_from_edge_runs``,
    ``Graph.with_capacity``), so ``esrc`` is not sorted.
    """
    use_ratio, use_ab, use_locks = variant_flags(variant)
    vmask = g.vertex_mask()
    q = queries if queries is not None else cn.queries(g, parts, k,
                                                       backend=backend)
    F = q.best_conn - q.conn_self  # gain of the best single move
    boundary = q.best_conn > 0

    if use_ratio:
        thr = jnp.floor(c * q.conn_self.astype(jnp.float32)).astype(jnp.int32)
        filter1 = (F >= 0) | (-F < thr)  # Eq 4.3 (strict <, floor rounding)
    else:
        filter1 = F >= 0
    X = vmask & boundary & filter1
    if use_locks:
        X = X & ~lock
    Pd = jnp.where(X, q.best_part, parts)
    if not use_ab:
        return X, Pd

    # Afterburner: per-edge approximate next state, each endpoint's
    # (gain, destination, part) read by one gather.
    u, v = g.adjncy, g.esrc
    state = jnp.stack([F, Pd, parts], axis=1)
    head, tail = state[u], state[v]
    Fu, Pdu, pu0 = head[:, 0], head[:, 1], head[:, 2]
    Fv, Pdv, pv = tail[:, 0], tail[:, 1], tail[:, 2]
    # ord(u) < ord(v): u moves "first" iff higher priority gain, tie -> smaller id
    u_first = (Fu > Fv) | ((Fu == Fv) & (u < v))
    pu = jnp.where(u_first, Pdu, pu0)
    contrib = g.adjwgt * (
        (pu == Pdv).astype(jnp.int32) - (pu == pv).astype(jnp.int32)
    )
    F2 = jax.ops.segment_sum(
        jnp.where(g.edge_mask(), contrib, 0), v, num_segments=g.n_max
    )
    move = X & (F2 >= 0)
    return move, Pd


class RefineState(NamedTuple):
    parts: jnp.ndarray
    conn: cn.ConnState           # threaded connectivity/sizes/cut state
    best_parts: jnp.ndarray
    best_cost: jnp.ndarray       # int32 cutsize of best
    best_maxsize: jnp.ndarray    # int32 max part weight of best
    best_balanced: jnp.ndarray   # bool
    lock: jnp.ndarray            # bool (N,) — last Jetlp move set
    since_best: jnp.ndarray      # int32 iterations since best improved
    weak_count: jnp.ndarray      # int32 consecutive weak rebalances
    it: jnp.ndarray              # int32 total iterations
    lp_iters: jnp.ndarray        # int32 (stats)
    rb_iters: jnp.ndarray        # int32 (stats)
    rs_iters: jnp.ndarray        # int32 (stats) strong rebalances


def jet_refine(
    g: Graph,
    parts0: jnp.ndarray,
    k: int,
    lam: float = 0.03,
    c: float = 0.75,
    phi: float = 0.999,
    backend: str = "dense",
    patience: int = 12,
    max_iter: int = 200,
    b_max: int = 2,
    variant: str = "full",
    rebuild_every: int = 0,
    conn0: cn.ConnState | None = None,
    max_degree: int | None = None,
):
    """Alg 4.1. Returns (best_parts, stats dict).

    Host-side wrapper: normalizes the input partition, builds the ConnState
    (unless the caller already owns one), resolves the static ELL width,
    then enters the jitted loop.  The multilevel driver does not come here:
    its levels fuse the same steps into ``partition.uncoarsen_level``.
    """
    if rebuild_every < 0:
        raise ValueError(f"rebuild_every must be >= 0, got {rebuild_every}")
    parts0 = jnp.where(
        g.vertex_mask(), jnp.asarray(parts0).astype(jnp.int32), k
    )
    if conn0 is None:
        if backend == "ell" and max_degree is None:
            max_degree = int(jax.device_get(jnp.max(g.degrees())))
        conn0 = cn.build_state(g, parts0, k, backend, max_degree=max_degree)
    return _refine_loop(
        g, parts0, conn0, phi,
        k=k, lam=lam, c=c, backend=backend, patience=patience,
        max_iter=max_iter, b_max=b_max, variant=variant,
        rebuild_every=rebuild_every,
    )


@partial(
    jax.jit,
    static_argnames=(
        "k", "lam", "c", "backend", "patience", "max_iter", "b_max",
        "variant", "rebuild_every",
    ),
)
def _refine_loop(
    g: Graph,
    parts0: jnp.ndarray,
    conn0: cn.ConnState,
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
    active=None,
):
    W = g.total_vweight()
    limit = metrics.size_limit(W, k, lam)

    cost0 = conn0.cut
    max0 = jnp.max(conn0.sizes).astype(jnp.int32)
    st = RefineState(
        parts=parts0,
        conn=conn0,
        best_parts=parts0,
        best_cost=cost0,
        best_maxsize=max0,
        best_balanced=max0 <= limit,
        lock=jnp.zeros((g.n_max,), bool),
        since_best=jnp.int32(0),
        weak_count=jnp.int32(0),
        it=jnp.int32(0),
        lp_iters=jnp.int32(0),
        rb_iters=jnp.int32(0),
        rs_iters=jnp.int32(0),
    )

    def cond(st: RefineState):
        ok = (st.since_best < patience) & (st.it < max_iter)
        if active is not None:
            # fleet lane masking (DESIGN.md §10): an inactive lane's loop
            # condition is false from iteration 0, so the while_loop batching
            # rule freezes its carry immediately and the lane's best_parts
            # pass the (projected) input partition through untouched
            ok = ok & active
        return ok

    def body(st: RefineState):
        balanced = jnp.max(st.conn.sizes) <= limit
        # one ConnQueries per iteration, shared by all three move kinds
        with jax.named_scope("jet.queries"):
            q = cn.state_queries(g, st.conn, st.parts, k, backend)

        def do_lp(_):
            with jax.named_scope("jet.lp"):
                move, dest = jetlp_moves(
                    g, st.parts, k, st.lock, c, backend, variant, queries=q
                )
            return (move, dest, move, jnp.int32(0), jnp.int32(1),
                    jnp.int32(0), jnp.int32(0))

        def do_rb(_):
            # The rebalance kernels gather from k-entry tables (sizes, caps,
            # per-part offsets).  Unbatched, XLA's TPU compiler expands each
            # such gather into a k-way compare/select chain, which at k=64
            # triples the level program's compile time; run them as a batch
            # of one, as they run under the trial vmap.
            def batch_of_one(kernel):
                def one(parts, conn, queries):
                    return kernel(g, parts, k, lam, backend, conn=conn,
                                  queries=queries)

                args = jax.tree_util.tree_map(lambda x: x[None],
                                              (st.parts, st.conn, q))
                return jax.tree_util.tree_map(lambda x: x[0],
                                              jax.vmap(one)(*args))

            def weak(_):
                with jax.named_scope("jet.rw"):
                    move, dest = batch_of_one(rb.jetrw_moves)
                return move, dest, jnp.int32(0)

            def strong(_):
                with jax.named_scope("jet.rs"):
                    move, dest = batch_of_one(rb.jetrs_moves)
                return move, dest, jnp.int32(1)

            move, dest, drs = jax.lax.cond(st.weak_count < b_max, weak,
                                           strong, None)
            # rebalancing does not touch lock state (paper §4.1.3)
            return (move, dest, st.lock, st.weak_count + 1, jnp.int32(0),
                    jnp.int32(1), drs)

        move, dest, lock2, weak2, dlp, drb, drs = jax.lax.cond(
            balanced, do_lp, do_rb, None
        )
        parts2 = jnp.where(move, dest, st.parts)

        # Alg 4.4 delta update; `rebuild_every` is the full-rebuild hatch.
        def incr(_):
            with jax.named_scope("jet.apply"):
                return cn.apply_moves(g, st.conn, st.parts, move, dest, k,
                                      backend)

        def full(_):
            with jax.named_scope("jet.apply"):
                return cn.rebuild_state(g, st.conn, parts2, k, backend)

        if rebuild_every == 1:
            conn2 = full(None)
        elif rebuild_every == 0:
            conn2 = incr(None)
        else:
            conn2 = jax.lax.cond(
                (st.it + 1) % rebuild_every == 0, full, incr, None
            )

        cost2 = conn2.cut
        max2 = jnp.max(conn2.sizes).astype(jnp.int32)
        bal2 = max2 <= limit

        # Best tracking (Alg 4.1 lines 16-23, fixed so a balanced partition
        # always supersedes an unbalanced best — see DESIGN.md §6).
        take_bal = bal2 & (~st.best_balanced | (cost2 < st.best_cost))
        significant = bal2 & (
            ~st.best_balanced
            | (cost2.astype(jnp.float32) < phi * st.best_cost.astype(jnp.float32))
        )
        take_imb = (~bal2) & (~st.best_balanced) & (max2 < st.best_maxsize)
        take = take_bal | take_imb
        reset = significant | take_imb

        return RefineState(
            parts=parts2,
            conn=conn2,
            best_parts=jnp.where(take, parts2, st.best_parts),
            best_cost=jnp.where(take, cost2, st.best_cost),
            best_maxsize=jnp.where(take, max2, st.best_maxsize),
            best_balanced=st.best_balanced | bal2,
            lock=lock2,
            since_best=jnp.where(reset, jnp.int32(0), st.since_best + 1),
            weak_count=jnp.where(bal2, jnp.int32(0), weak2),
            it=st.it + 1,
            lp_iters=st.lp_iters + dlp,
            rb_iters=st.rb_iters + drb,
            rs_iters=st.rs_iters + drs,
        )

    st = jax.lax.while_loop(cond, body, st)
    stats = {
        "iterations": st.it,
        "lp_iters": st.lp_iters,
        "rb_iters": st.rb_iters,
        "rs_iters": st.rs_iters,
        "best_cost": st.best_cost,
        "best_maxsize": st.best_maxsize,
        "best_balanced": st.best_balanced,
    }
    return st.best_parts, stats
