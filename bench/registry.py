"""Name lookup for the benchmark's parts: each part is a file of its own.

* ``configs/<config>.json``: a deployment (generator, capacities, the
  partitioner's settings, ``reduced`` and ``assumed``);
* ``workloads/<cell>.json``: a cell, naming its config and its traffic;
* ``traffic/<traffic>.json``: a traffic mix, data only, naming the driver
  that reads it;
* ``drivers/<driver>.py``: a general window driver with ``run(ctx)``;
* ``metrics/<metric>.py``: a per-layer metric reader with ``read(run)``.

A :class:`Registry` searches a list of directories in order, so a test
can lay new files over the committed ones without touching them.  An
unknown name is an error.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
BENCHMARK_JSON = BENCH_DIR.parent / "BENCHMARK.json"


class UnknownName(LookupError):
    """No file of that kind carries that name."""


class Registry:
    def __init__(self, dirs=(BENCH_DIR,), benchmark=BENCHMARK_JSON):
        self.dirs = [Path(d) for d in dirs]
        self.benchmark_path = Path(benchmark)

    def _find(self, kind: str, name: str, suffix: str) -> Path:
        for d in self.dirs:
            p = d / kind / f"{name}{suffix}"
            if p.is_file():
                return p
        raise UnknownName(f"no {kind} named {name!r} in "
                          f"{[str(d / kind) for d in self.dirs]}")

    def _json(self, kind: str, name: str) -> dict:
        with open(self._find(kind, name, ".json")) as f:
            return json.load(f)

    def _module(self, kind: str, name: str):
        path = self._find(kind, name, ".py")
        mod_name = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod  # dataclasses look their module up
        spec.loader.exec_module(mod)
        return mod

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def workload(self, name: str) -> dict:
        return self._json("workloads", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def driver(self, name: str):
        return self._module("drivers", name)

    def metric(self, name: str):
        return self._module("metrics", name)

    def benchmark(self) -> dict:
        with open(self.benchmark_path) as f:
            return json.load(f)

    def cell(self, name: str) -> dict:
        """Everything one cell runs from: its workload entry, config,
        traffic, driver, and the metrics ``BENCHMARK.json`` has it
        report."""
        wl = self.workload(name)
        traffic = self.traffic(wl["traffic"])
        bench = self.benchmark()
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise UnknownName(f"BENCHMARK.json has no workload {name!r}")
        if (entry["config"], entry["traffic"]) != (wl["config"],
                                                   wl["traffic"]):
            raise ValueError(f"workload {name!r}: BENCHMARK.json names "
                             f"{entry['config']}/{entry['traffic']}, its "
                             f"file {wl['config']}/{wl['traffic']}")

        e2e = [m for m in bench["end_to_end"]
               if name in m.get("workloads", [name])]
        reported = {m["name"] for m in e2e}
        # a per-layer metric without a "workloads" key is read in every
        # cell that reports the end-to-end metric it moves
        layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
        return {
            "name": name,
            "chips": entry["chips"],
            "config": self.config(wl["config"]),
            "traffic": traffic,
            "driver": self.driver(traffic["driver"]),
            "end_to_end": e2e,
            "per_layer": layer,
        }


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind`` (``peaks.json``);
    a kind that is not in the table is an error, not a default."""
    with open(BENCH_DIR / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownName(f"no published peaks for device kind "
                          f"{device_kind!r}")
    return table[device_kind]
