"""Chip benchmark of the Jet partitioner.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of standard output, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``:
each number compared with the plain reference beside its limit.  The same
checks are the last lines of standard error.  It exits nonzero, printing
no result, when JAX's first device is not a TPU or there are fewer chips
than the cell asks for.

Set-up (``setup_s``, from process start to the first timed call) builds
the cell's inputs from ``--seed`` and warms exactly the cell's shapes;
JAX's persistent compilation cache lives in ``<checkout>/.jax-compile-
cache``, so only a checkout's first run of a cell compiles.  The window
then runs for ``--seconds`` and must compile nothing: the compiles of
set-up and of the window are printed on standard error.

Everything that belongs to one cell, configuration, traffic mix or metric
is a file of its own under ``bench/``, found by name (``registry.py``):

* a cell: ``workloads/<cell>.json`` (``{"config": ..., "traffic": ...}``)
  and its entry in ``BENCHMARK.json``;
* a configuration: ``configs/<config>.json``, the deployment as it is run,
  with its source, ``reduced`` and ``assumed``;
* a traffic mix: ``traffic/<traffic>.json``, parameters only, naming the
  general driver in ``drivers/`` that reads it (``oneshot``: repeated
  ``partition()`` calls; ``open_loop``: Poisson arrivals at a fixed rate
  sent to ``PartitionServer``);
* a per-layer metric: ``metrics/<metric>.py`` with ``read(run)``, which
  returns a number or None where it finds nothing to read; list it under
  ``per_layer`` in ``BENCHMARK.json``.

So a new cell that reuses a driver is two JSON files and an entry in
``BENCHMARK.json``, and a new per-layer metric one reader and an entry.
``bench/control.py`` runs a cell's control and ``bench/sweep.py`` finds an
open-loop cell's knee; neither is part of a benchmark run.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path


def _process_start() -> float:
    """Wall time at which this process started (Linux), else now."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(ln.split()[1]) for ln in f
                         if ln.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return time.time()


T_PROCESS = _process_start()
ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    # bench/ itself must not be on the path: bench/trace.py would shadow
    # the standard library's module of that name
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

from bench import reference, trace  # noqa: E402
from bench.registry import Registry  # noqa: E402

CACHE_DIR = ROOT / ".jax-compile-cache"


def log(*parts) -> None:
    print("bench:", *parts, file=sys.stderr, flush=True)


class Tracer:
    """The profiler over the first ``steps`` steps of a window (or until
    :meth:`stop`), inside one host span ``bench.window``; inert when
    ``enabled`` is false.  The trace is reduced after the window."""

    def __init__(self, enabled: bool, steps: int | None, log_dir: str):
        self.enabled, self.limit, self.log_dir = enabled, steps, log_dir
        self.steps = 0
        self._span = None

    def __enter__(self):
        if self.enabled:
            import jax

            jax.profiler.start_trace(self.log_dir)
            self._span = jax.profiler.TraceAnnotation("bench.window")
            self._span.__enter__()
        return self

    @contextlib.contextmanager
    def step(self):
        yield
        if self._span is not None:
            self.steps += 1
            if self.limit is not None and self.steps >= self.limit:
                self.stop()

    def stop(self) -> None:
        if self._span is not None:
            import jax

            self._span.__exit__(None, None, None)
            self._span = None
            jax.profiler.stop_trace()

    def __exit__(self, *exc):
        self.stop()
        return False

    def summary(self) -> dict:
        if not self.enabled:
            return {}
        return trace.reduce_trace(self.log_dir, "bench.window")


@dataclass
class Context:
    """What a driver gets: the cell's files, the run's arguments, and the
    hooks that mark the end of set-up and of the window."""

    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    control: bool = False
    marks: dict = field(default_factory=dict)
    tracer: Tracer | None = None
    _tmp: tempfile.TemporaryDirectory | None = None

    def partition_settings(self) -> dict:
        """The configuration's ``PartitionConfig`` fields; for the control,
        with the configuration's ``control`` overrides laid over them."""
        out = dict(self.config["partition"])
        if self.control:
            out |= self.config["control"]["partition"]
        return out

    def compiles(self) -> dict:
        from repro.launch.compile_cache import cache_stats

        return cache_stats().snapshot()

    def setup_done(self) -> None:
        self.marks["setup_s"] = time.time() - T_PROCESS
        self.marks["setup_compiles"] = self.compiles()

    def traced(self, steps: int | None = None) -> Tracer:
        self._tmp = tempfile.TemporaryDirectory(prefix="bench-trace-")
        self.tracer = Tracer(self.trace, steps, self._tmp.name)
        return self.tracer

    def window_done(self) -> None:
        import jax

        self.marks["window_compiles"] = self.compiles()
        stats = jax.devices()[0].memory_stats() or {}
        self.marks["memory_peak_bytes"] = stats.get("peak_bytes_in_use")

    def trace_summary(self) -> dict:
        try:
            return self.tracer.summary() if self.tracer else {}
        finally:
            if self._tmp is not None:
                self._tmp.cleanup()


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_record(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def prepare(cell_name: str, registry: Registry):
    """The cell, and the devices, or None where the chips are missing."""
    cell = registry.cell(cell_name)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU: JAX's first device is {devices[0].platform}")
        return cell, None
    if len(devices) < cell["chips"]:
        log(f"the cell asks for {cell['chips']} chips, JAX has "
            f"{len(devices)}")
        return cell, None
    from repro.launch.compile_cache import enable_compile_cache

    # the cache's one place is inside the checkout, whatever the machine
    # sets, so that two checkouts on one machine share nothing
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    enable_compile_cache()
    return cell, devices[: cell["chips"]]


def measure(cell: dict, args, registry: Registry, devices,
            control: bool = False) -> dict:
    """Run the cell once and assemble its result line."""
    from repro.launch.compile_cache import cache_stats

    cache_stats()  # count compiles from the first one on
    ctx = Context(config=cell["config"], traffic=cell["traffic"],
                  seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), control=control)
    out = cell["driver"].run(ctx)
    summary = ctx.trace_summary()
    ctx_marks = ctx.marks
    setup_c = ctx_marks["setup_compiles"]
    window_c = ctx_marks["window_compiles"]
    log("compiles", json.dumps({
        "setup": setup_c.get("compiles", 0),
        "setup_cache_hits": setup_c.get("cache_hits", 0),
        "window": window_c.get("compiles", 0) - setup_c.get("compiles", 0)}))
    log("info", json.dumps(out["info"]))

    device = device_record(devices) | {
        "memory_peak_bytes": ctx_marks["memory_peak_bytes"]}
    if args.trace:
        run = {"trace": summary, **out["layers"]}
        values = {m["name"]: registry.metric(m["name"]).read(run)
                  for m in cell["per_layer"]}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell["per_layer"]
                   if values[m["name"]] is not None}
        device |= {kk: summary[kk] for kk in ("busy_s", "window_s")
                   if kk in summary}
    else:
        values = out["end_to_end"] | {"setup_s": ctx_marks["setup_s"]}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    # the limits are the configuration's, also when the control runs
    lam = cell["config"]["partition"]["lam"]
    correct, checks = reference.judge(out["readings"],
                                      reference.limits(lam))
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    if args.trace and summary:
        line["breakdown"] = {"device_ops": summary["device_ops"],
                             "idle_gaps": summary["idle_gaps"]}
    line["checks"] = checks
    return line


def emit(line: dict) -> None:
    for name, c in line["checks"].items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    args = parse_args(argv)
    registry = Registry()
    cell, devices = prepare(args.workload, registry)
    if devices is None:
        return 1
    emit(measure(cell, args, registry, devices))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
