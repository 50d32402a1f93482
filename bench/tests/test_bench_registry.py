"""Name discovery: every part of the benchmark is a file found by name, so
a cell or a metric is added with new files only."""
import json
import re
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import registry  # noqa: E402
from bench.registry import BENCH_DIR, BENCHMARK_JSON, Registry  # noqa: E402

BENCH = json.loads(BENCHMARK_JSON.read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_to_its_files(cell):
    c = Registry().cell(cell)
    assert c["chips"] == 1
    assert hasattr(c["driver"], "run")
    assert c["config"]["partition"]["lam"] == 0.03
    names = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert c["per_layer"], "every cell reports a per-layer metric"
    for m in c["per_layer"]:
        assert m["moves"] in names
        assert callable(Registry().metric(m["name"]).read)


def test_benchmark_json_keeps_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    root = BENCHMARK_JSON.parent
    for c in BENCH["configs"]:
        assert NAME.match(c["name"])
        assert (root / c["file"]).is_file()
        assert c["file"].startswith("bench/")
        assert set(c["reduced"]) <= set(json.loads(
            (root / c["file"]).read_text())["reduced"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    assert len(BENCHMARK_JSON.read_bytes()) <= 64 * 1024


def test_unknown_names_are_errors():
    r = Registry()
    for lookup in (r.config, r.workload, r.traffic, r.driver, r.metric,
                   r.cell):
        with pytest.raises(registry.UnknownName):
            lookup("no.such.name")
    with pytest.raises(registry.UnknownName):
        registry.peaks("TPU v0 imaginary")
    assert registry.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_a_new_cell_and_metric_are_picked_up_from_new_files(tmp_path):
    (tmp_path / "workloads").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "traffic" / "oneshot.k16.json").write_text(json.dumps(
        {"driver": "oneshot", "k": 16, "trace_partitions": 1}))
    (tmp_path / "workloads" / "delaunay.oneshot.k16.json").write_text(
        json.dumps({"config": "delaunay-fe", "traffic": "oneshot.k16"}))
    (tmp_path / "metrics" / "levels.oneshot.py").write_text(
        "def read(run):\n    return float(run['partitions'][0]['levels'])\n")
    bench = json.loads(BENCHMARK_JSON.read_text())
    bench["workloads"].append({"name": "delaunay.oneshot.k16",
                               "config": "delaunay-fe",
                               "traffic": "oneshot.k16", "chips": 1,
                               "why": "a test cell"})
    bench["per_layer"].append({"name": "levels.oneshot", "unit": "levels",
                               "better": "lower", "source": "program_counter",
                               "layer": "Coarsening", "moves": "partition_s",
                               "workloads": ["delaunay.oneshot.k16"]})
    for m in bench["end_to_end"]:
        if "partition_s" == m["name"]:
            m["workloads"].append("delaunay.oneshot.k16")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    before = sorted(p.read_bytes() for p in BENCH_DIR.rglob("*.json"))

    r = Registry(dirs=[tmp_path, BENCH_DIR],
                 benchmark=tmp_path / "BENCHMARK.json")
    c = r.cell("delaunay.oneshot.k16")
    assert c["traffic"]["k"] == 16
    assert c["config"] == Registry().config("delaunay-fe")
    assert [m["name"] for m in c["per_layer"]] == ["levels.oneshot"]
    assert r.metric("levels.oneshot").read(
        {"partitions": [{"levels": 5}]}) == 5.0
    assert {m["name"] for m in c["end_to_end"]} == {"partition_s",
                                                    "setup_s"}
    assert sorted(p.read_bytes() for p in BENCH_DIR.rglob("*.json")) == before


def test_a_cell_must_agree_with_its_workload_file(tmp_path):
    bench = json.loads(BENCHMARK_JSON.read_text())
    bench["workloads"][0]["traffic"] = "oneshot.k8"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    r = Registry(benchmark=tmp_path / "BENCHMARK.json")
    with pytest.raises(ValueError, match="BENCHMARK.json names"):
        r.cell(bench["workloads"][0]["name"])
