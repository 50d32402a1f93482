"""Stateful incremental refinement: ConnState.apply_moves must agree
bit-exactly with a from-scratch rebuild — connectivity structure, part
sizes, and cutsize — across many random move lists (paper Alg 4.4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import connectivity as cn
from repro.core import metrics, refine
from repro.core.graph import build_csr_host
from repro.data import graphs as gen


def _weighted_graph(seed=0, n=200, n_edges=700, pad=0):
    """``pad`` > 0 adds that many padding vertices and padding edges."""
    rng = np.random.default_rng(seed)
    path = np.stack([np.arange(n - 1), np.arange(1, n)], 1)
    extra = rng.integers(0, n, (n_edges, 2))
    edges = np.concatenate([path, extra])
    edges = edges[edges[:, 0] != edges[:, 1]]
    ew = rng.integers(1, 8, edges.shape[0])
    vw = rng.integers(1, 4, n)
    if not pad:
        return build_csr_host(n, edges, ew, vw)
    return build_csr_host(n, edges, ew, vw, n_max=n + pad,
                          m_max=2 * edges.shape[0] + pad)


def _rand_parts(g, k, rng):
    p = jnp.asarray(rng.integers(0, k, g.n_max).astype(np.int32))
    return jnp.where(g.vertex_mask(), p, k)


def _rand_moves(g, parts, k, rng, frac=0.15):
    move = jnp.asarray(rng.random(g.n_max) < frac) & g.vertex_mask()
    dest = jnp.asarray(rng.integers(0, k, g.n_max).astype(np.int32))
    return move, jnp.where(move, dest, parts)


def _assert_states_equal(st, ref, backend):
    np.testing.assert_array_equal(np.asarray(st.sizes), np.asarray(ref.sizes))
    np.testing.assert_array_equal(np.asarray(st.cut), np.asarray(ref.cut))
    if backend == "dense":
        np.testing.assert_array_equal(np.asarray(st.mat), np.asarray(ref.mat))
    elif backend == "sorted":
        np.testing.assert_array_equal(
            np.asarray(st.edge_dst_part), np.asarray(ref.edge_dst_part)
        )
    elif backend == "ell":
        np.testing.assert_array_equal(
            np.asarray(st.ell_parts), np.asarray(ref.ell_parts)
        )


@pytest.mark.parametrize("backend", ["dense", "sorted"])
@pytest.mark.parametrize("k", [2, 8, 33])
def test_apply_moves_matches_rebuild(backend, k):
    """10+ random move lists: incremental state == rebuilt state, bit-exact."""
    g = _weighted_graph(seed=k)
    rng = np.random.default_rng(100 + k)
    parts = _rand_parts(g, k, rng)
    st = cn.build_state(g, parts, k, backend)
    for step in range(12):
        move, dest = _rand_moves(g, parts, k, rng)
        st = cn.apply_moves(g, st, parts, move, dest, k, backend)
        parts = jnp.where(move, dest, parts)
        ref = cn.build_state(g, parts, k, backend)
        _assert_states_equal(st, ref, backend)
        # the maintained state answers queries identically to a rebuild
        qa = cn.state_queries(g, st, parts, k, backend)
        qb = cn.queries(g, parts, k, backend=backend)
        for a, b in zip(qa, qb):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(st.moves_applied) == 12


@pytest.mark.parametrize("k", [2, 7])
def test_apply_moves_matches_rebuild_ell(k):
    """The Pallas ELL backend participates in the stateful interface."""
    g = gen.grid2d(12, 12)
    md = int(np.max(np.asarray(g.degrees())))
    rng = np.random.default_rng(3)
    parts = _rand_parts(g, k, rng)
    st = cn.build_state(g, parts, k, "ell", max_degree=md)
    for step in range(4):
        move, dest = _rand_moves(g, parts, k, rng)
        st = cn.apply_moves(g, st, parts, move, dest, k, "ell")
        parts = jnp.where(move, dest, parts)
        ref = cn.build_state(g, parts, k, "ell", max_degree=md)
        _assert_states_equal(st, ref, "ell")
        n = int(g.n)
        qa = cn.state_queries(g, st, parts, k, "ell")
        qb = cn.queries(g, parts, k, backend="dense")
        for a, b in zip(qa, qb):
            np.testing.assert_array_equal(np.asarray(a)[:n], np.asarray(b)[:n])


@pytest.mark.parametrize("frac", [0.0, 0.05, 1.0])
@pytest.mark.parametrize("k", [2, 8, 33, 64])
def test_dense_apply_moves_equals_rebuild(k, frac):
    """The dense advance past one move list equals a rebuild from the
    post-move parts, bit for bit, with padding vertices and edges."""
    g = _weighted_graph(seed=k, n=150, n_edges=500, pad=21)
    assert g.n_max > int(g.n) and g.m_max > int(g.m)
    rng = np.random.default_rng(1000 * k + int(100 * frac))
    parts = _rand_parts(g, k, rng)
    st = cn.build_state(g, parts, k, "dense")
    move, dest = _rand_moves(g, parts, k, rng, frac)
    out = cn.apply_moves(g, st, parts, move, dest, k, "dense")
    ref = cn.rebuild_state(g, st, jnp.where(move, dest, parts), k, "dense")
    _assert_states_equal(out, ref, "dense")
    assert int(out.moves_applied) == 1


def test_dense_apply_moves_equals_rebuild_vmapped():
    """The same under ``vmap`` over a trial axis of 3, one move fraction
    (0, 5% and 100%) per trial, the graph unbatched."""
    k = 8
    g = _weighted_graph(seed=5, n=150, n_edges=500, pad=21)
    rng = np.random.default_rng(5)
    parts = jnp.stack([_rand_parts(g, k, rng) for _ in range(3)])
    md = [_rand_moves(g, parts[t], k, rng, f)
          for t, f in enumerate((0.0, 0.05, 1.0))]
    move = jnp.stack([m for m, _ in md])
    dest = jnp.stack([d for _, d in md])
    st = jax.vmap(lambda p: cn.build_state(g, p, k, "dense"))(parts)
    out = jax.vmap(lambda s, p, m, d: cn.apply_moves(
        g, s, p, m, d, k, "dense"))(st, parts, move, dest)
    ref = jax.vmap(lambda s, p: cn.rebuild_state(g, s, p, k, "dense"))(
        st, jnp.where(move, dest, parts))
    _assert_states_equal(out, ref, "dense")
    np.testing.assert_array_equal(np.asarray(out.moves_applied), [1, 1, 1])


def test_delta_metrics_match_recompute():
    g = _weighted_graph(seed=9)
    k = 6
    rng = np.random.default_rng(9)
    parts = _rand_parts(g, k, rng)
    sizes = metrics.part_sizes(g, parts, k)
    cut = metrics.cutsize(g, parts)
    for _ in range(10):
        move, dest = _rand_moves(g, parts, k, rng, frac=0.3)
        parts2 = jnp.where(move, dest, parts)
        sizes = metrics.delta_part_sizes(g, sizes, parts, move, dest, k)
        cut = metrics.delta_cutsize(g, cut, parts, parts2)
        parts = parts2
        np.testing.assert_array_equal(
            np.asarray(sizes), np.asarray(metrics.part_sizes(g, parts, k))
        )
        assert int(cut) == int(metrics.cutsize(g, parts))


@pytest.mark.parametrize("backend", ["dense", "sorted"])
def test_refine_incremental_equals_rebuild_every(backend):
    """rebuild_every=1 (legacy full rebuild per iteration) and the default
    incremental path must walk identical trajectories."""
    g = gen.grid2d(20, 20)
    k = 5
    rng = np.random.default_rng(11)
    parts0 = _rand_parts(g, k, rng)
    p_inc, s_inc = refine.jet_refine(g, parts0, k, lam=0.05, backend=backend,
                                     max_iter=60, rebuild_every=0)
    p_rbd, s_rbd = refine.jet_refine(g, parts0, k, lam=0.05, backend=backend,
                                     max_iter=60, rebuild_every=1)
    np.testing.assert_array_equal(np.asarray(p_inc), np.asarray(p_rbd))
    assert int(s_inc["iterations"]) == int(s_rbd["iterations"])
    assert int(s_inc["best_cost"]) == int(s_rbd["best_cost"])
    # periodic hatch lands on the same answer too
    p_per, s_per = refine.jet_refine(g, parts0, k, lam=0.05, backend=backend,
                                     max_iter=60, rebuild_every=7)
    np.testing.assert_array_equal(np.asarray(p_inc), np.asarray(p_per))
