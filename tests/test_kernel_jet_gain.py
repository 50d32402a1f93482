"""jet_gain Pallas kernel vs pure-jnp oracle — shape/dtype sweeps."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import connectivity as cn
from repro.data import graphs as gen
from repro.kernels.jet_gain.jet_gain import jet_gain_pallas
from repro.kernels.jet_gain.ops import csr_to_ell, jet_gain, jet_gain_from_parts
from repro.kernels.jet_gain.ref import jet_gain_ref


def _rand_inputs(n, d, k, seed=0, wmax=8):
    rng = np.random.default_rng(seed)
    nbr_parts = rng.integers(0, k + 1, (n, d)).astype(np.int32)
    nwgt = rng.integers(0, wmax, (n, d)).astype(np.int32)
    nwgt[nbr_parts == k] = 0  # padding slots carry no weight
    parts = rng.integers(0, k, n).astype(np.int32)
    return jnp.asarray(nbr_parts), jnp.asarray(nwgt), jnp.asarray(parts)


@pytest.mark.parametrize("n,d,k,block", [
    (256, 8, 4, 64),
    (512, 16, 7, 128),
    (1024, 4, 13, 256),
    (128, 32, 31, 128),
    (2048, 5, 3, 512),
])
def test_kernel_matches_ref_sweep(n, d, k, block):
    nbr_parts, nwgt, parts = _rand_inputs(n, d, k, seed=n + d + k)
    want = jet_gain_ref(nbr_parts, nwgt, parts, k)
    got = jet_gain_pallas(nbr_parts, nwgt, parts, k, block_n=block)
    for w, g_ in zip(want, got):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g_))


def test_kernel_tie_breaking_smallest_part():
    # two parts with equal connectivity -> smaller id must win, matching ref
    nbr_parts = jnp.asarray([[1, 2, 1, 2]], dtype=jnp.int32)
    nwgt = jnp.asarray([[3, 3, 2, 2]], dtype=jnp.int32)
    parts = jnp.asarray([0], dtype=jnp.int32)
    want = jet_gain_ref(nbr_parts, nwgt, parts, 4)
    got = jet_gain_pallas(
        jnp.tile(nbr_parts, (64, 1)), jnp.tile(nwgt, (64, 1)),
        jnp.tile(parts, 64), 4, block_n=64,
    )
    assert int(got[1][0]) == int(want[1][0]) == 1
    assert int(got[2][0]) == int(want[2][0]) == 5


def test_kernel_no_other_part():
    # vertex connected only to its own part -> best_part == k, best_conn == 0
    nbr_parts = jnp.zeros((64, 4), jnp.int32)
    nwgt = jnp.ones((64, 4), jnp.int32)
    parts = jnp.zeros((64,), jnp.int32)
    cs, bp, bc = jet_gain_pallas(nbr_parts, nwgt, parts, 3, block_n=64)
    assert int(cs[0]) == 4 and int(bp[0]) == 3 and int(bc[0]) == 0


@pytest.mark.parametrize("name", ["grid_64x32", "rmat_12"])
def test_ell_path_matches_csr_connectivity(name):
    """End-to-end: CSR->ELL + kernel == dense connectivity queries."""
    g = gen.suite_graph(name)
    k = 5
    rng = np.random.default_rng(3)
    parts = jnp.asarray(rng.integers(0, k, g.n_max).astype(np.int32))
    parts = jnp.where(g.vertex_mask(), parts, k)
    nbr, wgt = csr_to_ell(g)
    cs, bp, bc = jet_gain(nbr, wgt, parts, k, use_pallas=True)
    q = cn.dense_queries(g, parts, k)
    n = int(g.n)
    np.testing.assert_array_equal(np.asarray(cs)[:n], np.asarray(q.conn_self)[:n])
    np.testing.assert_array_equal(np.asarray(bc)[:n], np.asarray(q.best_conn)[:n])
    np.testing.assert_array_equal(np.asarray(bp)[:n], np.asarray(q.best_part)[:n])


def test_block_rows_fits_vmem():
    """Row tile shrinks with the ELL width; too-wide rows fail loudly."""
    from repro.kernels.jet_gain.ops import block_rows

    assert block_rows(8) == 256
    assert block_rows(1492) == 256
    assert block_rows(20000) == 32
    assert all(block_rows(d) % 8 == 0 for d in (1, 300, 5000, 80000))
    with pytest.raises(ValueError, match="too wide"):
        block_rows(100_000)


def test_derived_block_matches_ref_on_wide_rows():
    """A width whose derived tile is not 256, with n not a tile multiple."""
    nbr_parts, nwgt, parts = _rand_inputs(100, 5000, 6, seed=5)
    want = jet_gain_ref(nbr_parts, nwgt, parts, 6)
    got = jet_gain_from_parts(nbr_parts, nwgt, parts, 6, use_pallas=True)
    for w, g_ in zip(want, got):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g_))
