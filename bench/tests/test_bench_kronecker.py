"""The benchmark's Graph500 Kronecker generator, and its one-shot cell run
end to end on the CPU at a size a test can hold."""
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import graphs, kronecker, reference, run  # noqa: E402
from bench.registry import BENCH_DIR, Registry  # noqa: E402

CELL = "kron.oneshot.k64"


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 11, 9_876_543_210])
def test_same_seed_same_graph(seed):
    a, b = (kronecker.kronecker_graph(np.random.default_rng(seed), 10)
            for _ in range(2))
    assert a.n == b.n and np.array_equal(a.edges, b.edges)
    c = kronecker.kronecker_graph(np.random.default_rng(seed + 1), 10)
    assert not np.array_equal(a.edges[:100], c.edges[:100])


@pytest.mark.parametrize("scale", [8, 11])
def test_simple_compacted_and_within_the_edge_budget(scale):
    g = kronecker.kronecker_graph(np.random.default_rng(5), scale)
    e = g.edges
    assert np.all(e[:, 0] < e[:, 1]), "no self loops, u < v"
    assert len(np.unique(e, axis=0)) == len(e), "no duplicate edges"
    assert len(e) <= 16 << scale and g.m <= g.m_max == 32 << scale
    assert np.array_equal(np.unique(e), np.arange(g.n)), "ids compacted"
    assert g.n <= g.n_max == 1 << scale


def test_csr_is_symmetric():
    g = kronecker.kronecker_graph(np.random.default_rng(3), 9)
    a = graphs.csr_arrays(g)
    n, m = int(a["n"]), int(a["m"])
    src, dst = a["esrc"][:m], a["adjncy"][:m]
    assert np.array_equal(src, np.repeat(np.arange(n), np.diff(
        a["xadj"][: n + 1])))
    fwd = set(zip(src.tolist(), dst.tolist()))
    assert fwd == {(v, u) for u, v in fwd} and len(fwd) == m


@pytest.mark.parametrize("seed", [1, 2, 7])
def test_degrees_are_heavy_tailed_and_the_hub_is_relabelled(seed):
    g = kronecker.kronecker_graph(np.random.default_rng(seed), 12)
    deg = g.degrees()
    assert deg.min() >= 1
    assert deg.max() > 40 * deg.mean()
    assert int(np.argmax(deg)) != 0
    raw = kronecker.kronecker_edges(np.random.default_rng(seed), 12)
    assert np.bincount(raw.ravel()).argmax() != 0


def test_a_capacity_too_small_is_refused():
    with pytest.raises(ValueError, match="capacity"):
        kronecker.kronecker_graph(np.random.default_rng(1), 10, n_max=512)


def test_random_reference_is_balanced_to_one_vertex():
    drv = Registry().driver("oneshot_kron")
    parts = drv.random_parts(np.random.default_rng(4), 1001, 64)
    sizes = np.bincount(parts, minlength=64)
    assert sizes.max() - sizes.min() <= 1 and sizes.sum() == 1001


@pytest.fixture(scope="module")
def registry(tmp_path_factory):
    """The committed cell, its configuration cut to S=10 at the generator's
    own capacity, coarsened to 256 vertices so that S=10 has levels."""
    d = tmp_path_factory.mktemp("kron-overlay")
    for kind in ("configs", "traffic", "workloads"):
        (d / kind).mkdir()
    cfg = Registry().config("kron-g500")
    cfg["generator"] = {kk: vv for kk, vv in cfg["generator"].items()
                        if kk not in ("n_max", "m_max")} | {"scale": 10}
    cfg["partition"]["coarse_target"] = 256
    (d / "configs" / "kron-g500.json").write_text(json.dumps(cfg))
    wl = Registry().workload(CELL)
    (d / "workloads" / f"{CELL}.json").write_text(json.dumps(wl))
    tr = Registry().traffic(wl["traffic"]) | {"k": 8}
    (d / "traffic" / f"{wl['traffic']}.json").write_text(json.dumps(tr))
    return Registry(dirs=[d, BENCH_DIR])


def _measure(registry, trace=0, control=False):
    args = run.parse_args(["--workload", CELL, "--seed", "3000000033",
                           "--seconds", "1", "--trace", str(trace)])
    return run.measure(registry.cell(CELL), args, registry,
                       jax.devices()[:1], control=control)


def test_sound_run_is_correct_with_every_partition_checked(registry,
                                                           monkeypatch):
    checked = []
    real = reference.check_numbers

    def record(*a, **kw):
        checked.append(real(*a, **kw))
        return checked[-1]

    monkeypatch.setattr(reference, "check_numbers", record)
    line = _measure(registry)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"partition_s", "cut", "setup_s"}
    assert line["attempted"] >= 2 and len(checked) == line["attempted"]
    lims = reference.limits(0.03)
    assert all(reference.judge(c | {"failed": 0}, lims)[0] for c in checked)
    assert all(c["cut_ratio"] < 1 for c in checked), "beats random"


def test_control_readings_come_back_above_the_bound(registry):
    line = _measure(registry, control=True)
    assert line["checks"]["imbalance"]["limit"] == 0.03
    assert line["checks"]["imbalance"]["value"] > 0.03
    assert not line["correct"]


def test_traced_run_reads_the_coarsening_counters(registry):
    line = _measure(registry, trace=1)
    assert line["correct"], line["checks"]
    m = line["metrics"]
    assert 0 < m["hem_unmatched.oneshot"]["value"] <= 100
    assert m["twohop_levels.oneshot"]["value"] >= 1
    assert m["refine_iters.oneshot"]["value"] > 0
