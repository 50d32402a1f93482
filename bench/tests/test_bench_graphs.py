"""The benchmark's own Delaunay generator and plain reference."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import graphs, reference  # noqa: E402


@pytest.mark.parametrize("seed", [0, 1, 2, 2 ** 31 + 11, 9_876_543_210])
@pytest.mark.parametrize("scale", [8, 11])
def test_delaunay_fits_its_padding(seed, scale):
    mesh = graphs.delaunay_mesh(np.random.default_rng(seed), scale)
    assert (mesh.n, mesh.n_max, mesh.m_max) == (1 << scale, 1 << scale,
                                                6 << scale)
    assert mesh.m <= 2 * (3 * mesh.n - 6) <= mesh.m_max
    assert np.all(mesh.edges[:, 0] < mesh.edges[:, 1])
    assert len(np.unique(mesh.edges, axis=0)) == len(mesh.edges)


def test_same_seed_same_mesh_other_seed_other_mesh():
    a, b, c = (graphs.delaunay_mesh(np.random.default_rng(s), 9)
               for s in (4, 4, 5))
    assert np.array_equal(a.edges, b.edges)
    assert not np.array_equal(a.points, c.points)


def test_csr_arrays_hold_each_edge_twice_in_order():
    mesh = graphs.delaunay_mesh(np.random.default_rng(3), 9)
    a = graphs.csr_arrays(mesh)
    n, m = int(a["n"]), int(a["m"])
    assert a["xadj"].shape == (mesh.n_max + 1,)
    assert a["adjncy"].shape == a["esrc"].shape == (mesh.m_max,)
    assert a["xadj"][0] == 0 and np.all(a["xadj"][n:] == m)
    src, dst = a["esrc"][:m], a["adjncy"][:m]
    assert np.array_equal(src, np.repeat(np.arange(n), np.diff(
        a["xadj"][: n + 1])))
    fwd = set(zip(src.tolist(), dst.tolist()))
    assert fwd == {(v, u) for u, v in fwd}
    assert not np.any(a["adjwgt"][m:]) and not np.any(a["adjncy"][m:])


@pytest.mark.parametrize("k", [2, 8, 64, 7])
def test_rcb_is_balanced_to_one_vertex(k):
    mesh = graphs.delaunay_mesh(np.random.default_rng(8), 10)
    parts = reference.rcb_parts(mesh.points, k)
    sizes = np.bincount(parts, minlength=k)
    assert sizes.max() - sizes.min() <= 1 and sizes.sum() == mesh.n


def test_check_numbers_catch_each_fault():
    mesh = graphs.delaunay_mesh(np.random.default_rng(6), 9)
    k = 8
    parts = reference.rcb_parts(mesh.points, k)
    cut = reference.cut_of(mesh.edges, parts)
    good = reference.check_numbers(mesh.edges, mesh.n, k, parts, cut, cut)
    assert good == {"bad_labels": 0, "cut_gap": 0, "imbalance": 0.0,
                    "cut_ratio": 1.0, "cut": cut}
    lim = reference.limits(0.03)
    assert reference.judge(good | {"failed": 0}, lim)[0]

    bad = parts.copy()
    bad[:3] = k
    assert reference.check_numbers(mesh.edges, mesh.n, k, bad, cut,
                                   cut)["bad_labels"] == 3
    heavy = np.where(parts == 1, 0, parts)
    r = reference.check_numbers(mesh.edges, mesh.n, k, heavy, cut, cut)
    assert r["imbalance"] == pytest.approx(1.0)
    assert r["cut_gap"] > 0
    ok, checks = reference.judge(r | {"failed": 0}, lim)
    assert not ok and checks["imbalance"]["limit"] == 0.03
    ok, checks = reference.judge(good | {"failed": 1}, lim)
    assert not ok
    assert not reference.judge({}, lim)[0], "a missing number fails"
