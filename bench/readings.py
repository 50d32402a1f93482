"""Read the numbers that decide ``correct``, for the program and its
control, over many seeds in one process.

    python3 bench/readings.py --workload delaunay.oneshot.k64 \\
        --seeds 11,12,13 --seconds 10

For each seed it runs the cell as ``bench/run.py`` does (with the
profiler off) and then its control: the same timed path with the
configuration's ``control`` overrides, which break a guarantee the
configuration states.  Each run prints one JSON line with ``correct`` and
every number beside its limit.  The program's runs give a limit its lower
reading, the control's its upper one (``PERF.md``).  Benchmark runs never
run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

if __name__ == "__main__":
    ROOT = Path(__file__).resolve().parents[1]
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

from bench import run  # noqa: E402
from bench.registry import Registry  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    registry = Registry()
    cell, devices = run.prepare(args.workload, registry)
    if devices is None:
        return 1
    for seed in (int(s) for s in args.seeds.split(",")):
        for control in (False, True):
            rargs = run.parse_args(["--workload", args.workload, "--seed",
                                    str(seed), "--seconds",
                                    str(args.seconds)])
            line = run.measure(cell, rargs, registry, devices,
                               control=control)
            print(json.dumps({"seed": seed, "control": control,
                              "correct": line["correct"],
                              "attempted": line["attempted"],
                              "metrics": line["metrics"],
                              "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
