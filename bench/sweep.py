"""Find an open-loop cell's knee: its traffic at several fixed rates.

    python3 bench/sweep.py --workload serve.meshes.poisson --seed 7 \\
        --seconds 30 --rates 0.5,1,2,4

One process, one set-up, then one window per rate, each printed as a JSON
line: the latency median and 90th percentile, the requests still open when
the window closed, and the median latency of the window's first and
second half.  At a rate the server sustains the halves agree; past the
knee the queue grows all through the window and the second half waits
longer.  The sweep stops after the first rate that leaves a quarter of
its requests open at the close.  The knee is found once, on the chip, when a cell is defined, and
its rate written into the cell's traffic file; benchmark runs never sweep.
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

from bench import run, window  # noqa: E402
from bench.registry import Registry  # noqa: E402


def halves(w) -> tuple[float, float]:
    """Median latency (ms) of the requests due in each half of the window."""
    lat = window.latencies([(rec[0], rec[2]) if rec else (0.0, None)
                            for rec in w["records"]])
    mid = len(lat) // 2
    return (1e3 * window.percentile(lat[:mid] or [0.0], 50),
            1e3 * window.percentile(lat[mid:] or [0.0], 50))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    registry = Registry()
    cell, devices = run.prepare(args.workload, registry)
    if devices is None:
        return 1
    driver = cell["driver"]
    ctx = run.Context(config=cell["config"], traffic=cell["traffic"],
                      seed=args.seed, seconds=args.seconds, trace=False)
    state = driver.setup(ctx)
    ctx.setup_done()
    run.log("setup_s", ctx.marks["setup_s"])
    for rate in (float(r) for r in args.rates.split(",")):
        before = ctx.compiles().get("compiles", 0)
        w = driver.measure_window(ctx, state, rate)
        compiles = ctx.compiles().get("compiles", 0) - before
        s = driver.summarize(ctx, state, w)
        first, second = halves(w)
        print(json.dumps({"rate_rps": rate, **s["end_to_end"],
                          "first_half_p50_ms": first,
                          "second_half_p50_ms": second,
                          "window_compiles": compiles,
                          "failed": s["failed"], **s["info"],
                          "checks": s["readings"]}), flush=True)
        if s["info"]["backlog_at_close"] > len(w["reqs"]) // 4:
            break  # well past the knee: higher rates only queue longer
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
