"""A whole benchmark run on the CPU at a size a test can hold, past the
look for a chip: sound runs come out correct; the control, and each fault
the cells can have, planted under the timed path, come out not correct.

The cells' committed traffic and drivers run on their committed
configurations with only the scale cut down (and the server's ladder with
it); the served cell's files are committed, its entry in BENCHMARK.json
is not yet.  A cell on one chip has no exchange between chips to leave out.
"""
import json
import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import run  # noqa: E402
from bench.registry import BENCH_DIR, BENCHMARK_JSON, Registry  # noqa: E402

import repro.core.partition as core_partition  # noqa: E402
import repro.launch.partition_serve as serve  # noqa: E402

ONESHOT, SERVED = "delaunay.oneshot.k8", "serve.meshes.poisson"


@pytest.fixture(scope="module")
def registry(tmp_path_factory):
    """The committed cells, on configurations cut to test size."""
    d = tmp_path_factory.mktemp("bench-overlay")
    (d / "configs").mkdir()
    (d / "traffic").mkdir()
    fe = Registry().config("delaunay-fe")
    fe["generator"]["scale"] = 13
    (d / "configs" / "delaunay-fe.json").write_text(json.dumps(fe))
    sm = Registry().config("serve-meshes")
    sm["generator"] = {"kind": "delaunay", "scales": [10, 11],
                       "pool_per_scale": 2}
    sm["serve"] |= {"lanes": 2, "ladder_n": 2048, "ladder_m": 12288}
    (d / "configs" / "serve-meshes.json").write_text(json.dumps(sm))
    tr = Registry().traffic("poisson.meshes")
    tr |= {"rate_rps": 2.0, "scale_weights": [1.0, 0.5], "ks": [8],
           "k_weights": [1.0], "wait_after_close_s": 30}
    (d / "traffic" / "poisson.meshes.json").write_text(json.dumps(tr))
    # the served cell is not in BENCHMARK.json yet (PERF.md, Open
    # questions): its entries as a later PR would add them
    bench = json.loads(BENCHMARK_JSON.read_text())
    bench["workloads"].append({"name": SERVED, "config": "serve-meshes",
                               "traffic": "poisson.meshes", "chips": 1,
                               "why": "open loop"})
    bench["end_to_end"] = [m for m in bench["end_to_end"]
                           if m["name"] != "setup_s"] + [
        {"name": q, "unit": "ms", "better": "lower", "bound": 0.25,
         "source": "host_clock", "workloads": [SERVED]}
        for q in ("latency_p50_ms", "latency_p90_ms")] + [
        m for m in bench["end_to_end"] if m["name"] == "setup_s"]
    bench["per_layer"] += [
        {"name": name, "unit": "%", "better": better, "source": source,
         "layer": layer, "moves": "latency_p90_ms", "workloads": [SERVED]}
        for name, better, source, layer in (
            ("lane_occupancy.serve", "higher", "program_counter",
             "Server admission and bucketing"),
            ("device_idle.serve", "lower", "device_trace", "Device"))]
    (d / "BENCHMARK.json").write_text(json.dumps(bench))
    return Registry(dirs=[d, BENCH_DIR], benchmark=d / "BENCHMARK.json")


def _measure(registry, cell, seconds=1.0, trace=0, control=False):
    args = run.parse_args(["--workload", cell, "--seed", "3000000021",
                           "--seconds", str(seconds), "--trace",
                           str(trace)])
    return run.measure(registry.cell(cell), args, registry,
                       jax.devices()[:1], control=control)


def test_sound_one_shot_run_is_correct(registry):
    line = _measure(registry, ONESHOT)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"partition_s", "cut", "setup_s"}
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert line["checks"]["imbalance"]["limit"] == 0.03


def test_one_shot_control_is_not_correct(registry):
    line = _measure(registry, ONESHOT, control=True)
    assert not line["correct"]
    assert line["checks"]["imbalance"]["value"] > 0.03


def test_refinement_that_returns_its_state_is_not_correct(registry,
                                                          monkeypatch):
    real = core_partition.uncoarsen_level

    def unchanged(*a, **kw):
        return real(*a, **(kw | {"max_iter": 0}))

    monkeypatch.setattr(core_partition, "uncoarsen_level", unchanged)
    assert not _measure(registry, ONESHOT)["correct"]


def test_an_answer_altered_where_produced_is_not_correct(registry,
                                                         monkeypatch):
    real = core_partition.partition

    def altered(g, cfg):
        res = real(g, cfg)
        n = res.parts.shape[0] // 64
        res.parts = res.parts.at[:n].set((res.parts[:n] + 1) % cfg.k)
        return res

    monkeypatch.setattr(core_partition, "partition", altered)
    line = _measure(registry, ONESHOT)
    assert not line["correct"] and line["checks"]["cut_gap"]["value"] > 0


def test_sound_served_run_is_correct_and_traced(registry):
    line = _measure(registry, SERVED, seconds=3.0)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"latency_p50_ms", "latency_p90_ms",
                                    "setup_s"}
    assert line["attempted"] == 6
    traced = _measure(registry, SERVED, seconds=3.0, trace=1)
    assert traced["correct"]
    occ = traced["metrics"]["lane_occupancy.serve"]
    assert occ["unit"] == "%" and 0 < occ["value"] <= 100


def test_served_control_is_not_correct(registry):
    assert not _measure(registry, SERVED, seconds=3.0,
                        control=True)["correct"]


def test_half_the_batch_left_out_is_not_correct(registry, monkeypatch):
    real = serve.partition_fleet_stacked

    def half(buckets, cfg, schedule, *a, **kw):
        res = real(buckets, cfg, schedule, *a, **kw)
        for tag in sorted(res.results)[len(res.results) // 2:]:
            del res.results[tag]
        return res

    monkeypatch.setattr(serve, "partition_fleet_stacked", half)
    line = _measure(registry, SERVED, seconds=3.0)
    assert not line["correct"]
