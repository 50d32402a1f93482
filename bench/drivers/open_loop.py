"""Open-loop window driver: Poisson arrivals sent to ``PartitionServer``.

Configuration keys: ``generator`` (``scales``: the request meshes are
Delaunay meshes of 2^s points, each padded to (2^s, 6 * 2^s);
``pool_per_scale`` meshes of each size are made from the seed), ``serve``
(``ServeConfig`` fields) and ``partition`` (``PartitionConfig`` fields).

Traffic keys: ``rate_rps``; ``scale_weights`` (one per scale); ``ks`` and
``k_weights``; ``wait_after_close_s``, how long answers due in the window
are waited for after it closes; ``trace_seconds``, how much of a
``--trace 1`` window the profiler records.

Every seed sends the same mix: round(rate * seconds) requests, sizes and
k apportioned exactly by their weights, and one fixed set of exponential
gaps (``window.arrival_times``); the seed draws each request's mesh from
the pool and the order of everything.  A request is sent when it is due,
whether or not earlier ones were answered, and its latency runs from the
time it was due to its response; one that fails or never comes counts as
infinite.  Set-up warms the server for every pool mesh at every k of the
mix; the window must compile nothing.
"""
from __future__ import annotations

import asyncio
from dataclasses import dataclass

import numpy as np

from bench import graphs, reference, window


@dataclass
class Request:
    due: float          # offset from the window's start, s
    scale: int
    index: int          # mesh within the pool of its scale
    k: int


def schedule(traffic: dict, scales, pool: int, seconds: float,
             rng) -> list[Request]:
    n = int(round(traffic["rate_rps"] * seconds))
    sizes = [s for s, c in zip(scales, window.apportion(
        n, traffic["scale_weights"])) for _ in range(c)]
    ks = [k for k, c in zip(traffic["ks"], window.apportion(
        n, traffic["k_weights"])) for _ in range(c)]
    sizes = [sizes[i] for i in rng.permutation(n)]
    ks = [ks[i] for i in rng.permutation(n)]
    due = window.arrival_times(n, seconds, rng)
    return [Request(due=t, scale=s, index=int(rng.integers(pool)), k=k)
            for t, s, k in zip(due, sizes, ks)]


def setup(ctx) -> dict:
    """The pool of meshes, the server, and its warm-up."""
    from repro.core.partition import PartitionConfig
    from repro.launch.partition_serve import PartitionServer, ServeConfig

    cfg, traffic = ctx.config, ctx.traffic
    gen = cfg["generator"]
    rng = np.random.default_rng(ctx.seed)
    meshes = {s: [graphs.delaunay_mesh(rng, s)
                  for _ in range(gen["pool_per_scale"])]
              for s in gen["scales"]}
    pool = {s: [graphs.to_graph(m) for m in ms] for s, ms in meshes.items()}
    server = PartitionServer(ServeConfig(
        partition=PartitionConfig(**ctx.partition_settings()),
        **cfg["serve"]))
    used = [s for s, w in zip(gen["scales"], traffic["scale_weights"])
            if w > 0]
    server.warmup([g for s in used for g in pool[s]],
                  ks=tuple(traffic["ks"]), compositions="full")
    return {"meshes": meshes, "pool": pool, "server": server, "rng": rng}


async def _serve(server, pool, reqs, seconds, wait_s, tracer,
                 trace_seconds):
    loop = asyncio.get_running_loop()
    records: list = [None] * len(reqs)

    async def one(i, r):
        due = t0 + r.due
        await asyncio.sleep(max(0.0, due - loop.time()))
        sent = loop.time()
        try:
            res = await server.submit(pool[r.scale][r.index], k=r.k)
        except Exception as e:  # noqa: BLE001 - a failed request is a result
            records[i] = (due, sent, None, repr(e))
        else:
            records[i] = (due, sent, loop.time(), res)

    async with server:
        with tracer:
            t0 = loop.time() + 0.05
            if tracer.enabled:
                loop.call_at(t0 + trace_seconds, tracer.stop)
            tasks = [asyncio.create_task(one(i, r))
                     for i, r in enumerate(reqs)]
            await asyncio.wait(tasks, timeout=t0 + seconds + wait_s
                               - loop.time())
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
    return t0, records


def measure_window(ctx, state, rate: float | None = None) -> dict:
    """One window at the traffic's rate (or ``rate``): the raw records."""
    traffic = dict(ctx.traffic)
    if rate is not None:
        traffic["rate_rps"] = rate
    scales = ctx.config["generator"]["scales"]
    reqs = schedule(traffic, scales, ctx.config["generator"][
        "pool_per_scale"], ctx.seconds, state["rng"])
    server = state["server"]
    buckets, filler = server.stats["buckets"], server.stats["filler_lanes"]
    t0, records = asyncio.run(_serve(
        server, state["pool"], reqs, ctx.seconds,
        traffic["wait_after_close_s"], ctx.traced(),
        traffic["trace_seconds"]))
    return {"reqs": reqs, "records": records, "t0": t0,
            "buckets": server.stats["buckets"] - buckets,
            "filler_lanes": server.stats["filler_lanes"] - filler,
            "lanes": server.cfg.lanes}


def summarize(ctx, state, w) -> dict:
    """End-to-end numbers and the reference's readings of one window."""
    reqs, records = w["reqs"], w["records"]
    lat = window.latencies([
        (rec[0], rec[2]) if rec is not None else (0.0, None)
        for rec in records])
    close = w["t0"] + ctx.seconds
    late = [rec[1] - rec[0] for rec in records if rec is not None]
    backlog = sum(1 for rec in records
                  if rec is None or rec[2] is None or rec[2] > close)

    refs: dict = {}
    checked, failed = [], 0
    for r, rec in zip(reqs, records):
        if rec is None or rec[2] is None:
            failed += 1
            continue
        mesh = state["meshes"][r.scale][r.index]
        key = (r.scale, r.index, r.k)
        if key not in refs:
            refs[key] = reference.cut_of(
                mesh.edges, reference.rcb_parts(mesh.points, r.k))
        res = rec[3]
        checked.append(reference.check_numbers(
            mesh.edges, mesh.n, r.k, np.asarray(res.parts), res.cut,
            refs[key]))
    ms = [1e3 * x for x in lat]
    return {
        "attempted": len(reqs),
        "failed": failed,
        "readings": reference.worst(checked) | {"failed": failed},
        "end_to_end": {
            "latency_p50_ms": window.percentile(ms, 50),
            "latency_p90_ms": window.percentile(ms, 90),
        },
        "layers": {"lanes": {"buckets": w["buckets"],
                             "filler_lanes": w["filler_lanes"],
                             "lanes": w["lanes"]}},
        "info": {"requests": len(reqs),
                 "rate_rps": len(reqs) / ctx.seconds,
                 "backlog_at_close": backlog,
                 "generator_late_ms_max": 1e3 * max(late, default=0.0),
                 "generator_late_ms_p50": 1e3 * window.percentile(
                     late, 50) if late else 0.0,
                 "buckets": w["buckets"], "filler_lanes": w["filler_lanes"],
                 "cut_ratio_to_rcb": max((c["cut_ratio"] for c in checked),
                                         default=None)},
    }


def run(ctx) -> dict:
    state = setup(ctx)
    ctx.setup_done()
    w = measure_window(ctx, state)
    ctx.window_done()
    return summarize(ctx, state, w)
