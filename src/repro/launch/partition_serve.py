"""Partition-as-a-service: async micro-batching over fleet buckets (§11).

The §10 fleet machinery made the whole V-cycle batch-polymorphic, but
every caller still hands `partition_fleet` a pre-assembled fleet and
waits.  This module adds the missing traffic layer:

* :class:`PartitionServer` accepts concurrent partition requests (graph +
  k + trials + seed), coalesces them over a configurable window into
  shape-bucketed fleets on a FIXED §8 capacity ladder, dispatches each
  bucket through :func:`~repro.core.partition.partition_fleet_stacked`,
  and routes per-member results back to their callers.  Every response is
  bit-identical to a standalone ``partition()`` call with the same
  config — batching changes the schedule, never the values.

* Warm-start subsystem: :meth:`PartitionServer.warmup` is an explicit AOT
  pass that precompiles the (rung, k) signature grid from representative
  shapes; with JAX's persistent compilation cache on
  (:mod:`repro.launch.compile_cache`, which the entry points enable), a
  cold process re-reaches steady-state latency from disk instead of from
  XLA.

Batch width discipline: every dispatched bucket is padded (with filler
copies of its first member) or split to exactly ``ServeConfig.lanes``
lanes, so the batch axis never enters the compile-key degrees of freedom
— one executable per (rung, k) signature, whatever the arrival pattern.

    server = PartitionServer(ServeConfig(ladder_n=1024, ladder_m=8192))
    server.warmup([gen.grid2d(16, 16)], ks=(8,))
    async with server:
        res = await server.submit(g, k=8)
"""
from __future__ import annotations

import asyncio
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field, replace

from repro.core import graph as gr
from repro.core.coarsen import _round_up, shape_schedule
from repro.core.partition import (
    PartitionConfig, PartitionResult, partition_fleet_stacked,
    uncoarsen_level,
)
from repro.core.spans import span
from repro.launch.compile_cache import CompileCacheStats, cache_stats


# ---------------------------------------------------------------------------
# Server
# ---------------------------------------------------------------------------

@dataclass
class ServeConfig:
    """Serving knobs; ``partition`` holds the per-request defaults.

    ``ladder_n``/``ladder_m`` pin the top rung of the serve-wide capacity
    ladder — requests larger than the top rung are rejected at admission.
    ``window_s`` is the coalescing window: the batcher collects requests
    for this long after the first arrival before dispatching.  ``lanes``
    is the fixed batch width every dispatched bucket is padded/split to.
    """

    ladder_n: int = 4096
    ladder_m: int = 32768
    window_s: float = 0.002
    lanes: int = 4
    max_batch: int = 64            # requests per coalesce round, max
    partition: PartitionConfig = field(default_factory=PartitionConfig)


@dataclass
class _Request:
    graph: object
    cfg: PartitionConfig
    cfg_key: tuple       # grouping key: (k, trials, seed, trial_seeds)
    future: asyncio.Future
    t_enqueue: float
    rid: int             # the server's request sequence number


def _resolve_cfg(base: PartitionConfig, k, trials, seed, trial_seeds):
    cfg = base
    if k is not None:
        cfg = replace(cfg, k=int(k))
    if trials is not None:
        cfg = replace(cfg, trials=int(trials))
    if seed is not None:
        cfg = replace(cfg, seed=int(seed))
    if trial_seeds is not None:
        cfg = replace(cfg, trial_seeds=tuple(int(s) for s in trial_seeds))
    return cfg


class PartitionServer:
    """Async micro-batching front end over ``partition_fleet_stacked``.

    Lifecycle: construct, optionally :meth:`warmup`, then ``async with``
    (or :meth:`start` / :meth:`stop`).  :meth:`submit` is awaitable and
    safe to call concurrently from many tasks; requests sharing a
    coalescing window and a config signature (k, trials, seed) are batched
    into one fleet dispatch, shape-bucketed on the pinned ladder.

    Sync accounting per dispatch (DESIGN.md §11): one batched (n, m)
    admission fetch per flush, one (lanes, 3) stat fetch per coarsening
    level per bucket, and ONE blocking transfer for the whole dispatch's
    results — all amortized over every request in the batch.
    """

    def __init__(self, cfg: ServeConfig):
        if cfg.lanes < 1:
            raise ValueError(f"lanes must be >= 1, got {cfg.lanes}")
        self.cfg = cfg
        p = cfg.partition
        self.schedule = shape_schedule(
            _round_up(cfg.ladder_n, p.bucket_align),
            _round_up(cfg.ladder_m, p.bucket_align),
            ratio=p.bucket_ratio, safety=p.bucket_safety,
            stall_ratio=p.stall_ratio, align=p.bucket_align,
        )
        self._queue: asyncio.Queue | None = None
        self._task: asyncio.Task | None = None
        self._pool: ThreadPoolExecutor | None = None
        # per-item records are bounded so a long-lived server doesn't
        # accumulate memory with traffic; the counters are exact forever,
        # the latency percentiles and signature logs cover a recent window
        # (far larger than any bench run, which reads them whole)
        self.stats = {
            "requests": 0, "responses": 0, "rejected": 0, "dispatches": 0,
            "buckets": 0, "filler_lanes": 0,
            "occupancy_hist": {},      # real lanes per dispatched bucket
            "latency_s": deque(maxlen=8192),  # enqueue -> response
            "queue_wait_s": deque(maxlen=8192),  # enqueue -> dispatch
        }
        self.dispatch_log: deque = deque(maxlen=2048)  # signature records
        self.warmup_log: deque = deque(maxlen=2048)    # same, AOT grid

    # -- lifecycle ---------------------------------------------------------

    async def start(self) -> "PartitionServer":
        if self._task is not None:
            raise RuntimeError("server already started")
        self._queue = asyncio.Queue()
        # one worker: device dispatches serialize, the event loop keeps
        # coalescing the next window while the current batch computes
        self._pool = ThreadPoolExecutor(max_workers=1,
                                        thread_name_prefix="jet-serve")
        self._task = asyncio.create_task(self._batch_loop())
        return self

    async def stop(self) -> None:
        if self._task is None:
            return
        await self._queue.put(None)  # drain sentinel: flush, then exit
        await self._task
        # a submit racing stop() can enqueue behind the sentinel; fail
        # those futures instead of leaving their callers awaiting forever
        while not self._queue.empty():
            req = self._queue.get_nowait()
            if req is not None and not req.future.done():
                req.future.set_exception(
                    RuntimeError("server stopped before dispatch"))
        self._pool.shutdown(wait=True)  # all dispatches already gathered
        self._pool = None
        self._task = None
        self._queue = None

    async def __aenter__(self):
        return await self.start()

    async def __aexit__(self, *exc):
        await self.stop()

    # -- request path ------------------------------------------------------

    def _admissible(self, g) -> bool:
        """Host-only fast path; falls back to one (n, m) fetch only when
        the graph's own padding exceeds the ladder top."""
        n_top = max(nc for nc, _ in self.schedule)
        m_top = max(mc for _, mc in self.schedule)
        if g.n_max <= n_top and g.m_max <= m_top:
            return True
        return int(g.n) <= n_top and int(g.m) <= m_top

    async def submit(self, graph, *, k=None, trials=None, seed=None,
                     trial_seeds=None) -> PartitionResult:
        """Enqueue one partition request; resolves to the same
        :class:`PartitionResult` a standalone ``partition(graph, cfg)``
        call with the resolved config would return."""
        if self._queue is None:
            raise RuntimeError("server not started — use `async with server`")
        self.stats["requests"] += 1
        if not self._admissible(graph):
            self.stats["rejected"] += 1
            raise ValueError(
                "graph exceeds the serve ladder's top rung "
                f"({self.cfg.ladder_n}, {self.cfg.ladder_m}) — raise "
                "ServeConfig.ladder_n/ladder_m or partition it standalone"
            )
        cfg = _resolve_cfg(self.cfg.partition, k, trials, seed, trial_seeds)
        req = _Request(graph=graph, cfg=cfg,
                       cfg_key=(cfg.k, cfg.trials, cfg.seed,
                                cfg.trial_seeds),
                       future=asyncio.get_running_loop().create_future(),
                       t_enqueue=time.perf_counter(),
                       rid=self.stats["requests"])
        await self._queue.put(req)
        return await req.future

    async def _batch_loop(self) -> None:
        loop = asyncio.get_running_loop()
        inflight: set[asyncio.Task] = set()
        draining = False
        while not draining:
            first = await self._queue.get()
            if first is None:
                break
            batch = [first]
            deadline = loop.time() + self.cfg.window_s
            while len(batch) < self.cfg.max_batch:
                timeout = deadline - loop.time()
                if timeout <= 0:
                    break
                try:
                    nxt = await asyncio.wait_for(self._queue.get(), timeout)
                except asyncio.TimeoutError:
                    break
                if nxt is None:  # stop() mid-window: serve the batch, exit
                    draining = True
                    break
                batch.append(nxt)
            groups: dict[tuple, list[_Request]] = {}
            for r in batch:
                groups.setdefault(r.cfg_key, []).append(r)
            # dispatch WITHOUT awaiting: the single-worker executor
            # serializes device work while this loop keeps coalescing the
            # next window on top of it
            for reqs in groups.values():
                t = asyncio.create_task(
                    self._dispatch_group(reqs[0].cfg, reqs))
                inflight.add(t)
                t.add_done_callback(inflight.discard)
        if inflight:
            await asyncio.gather(*inflight)

    async def _dispatch_group(self, cfg: PartitionConfig,
                              reqs: list[_Request]) -> None:
        try:
            results, log, t_start = await (
                asyncio.get_running_loop().run_in_executor(
                    self._pool, self._dispatch, cfg, reqs))
        except Exception as e:  # noqa: BLE001 — routed to callers
            for r in reqs:
                if not r.future.done():
                    r.future.set_exception(
                        RuntimeError(f"dispatch failed: {e}"))
        else:
            # all stats/log mutation happens HERE, on the event-loop
            # thread — metrics() can iterate them concurrently without
            # racing the worker
            self.stats["dispatches"] += 1
            hist = self.stats["occupancy_hist"]
            for b in log["buckets"]:
                self.stats["buckets"] += 1
                self.stats["filler_lanes"] += b["lanes"] - b["real"]
                hist[b["real"]] = hist.get(b["real"], 0) + 1
            self.dispatch_log.append(log)
            t_done = time.perf_counter()
            for r, res in zip(reqs, results):
                if r.future.done():  # caller gave up (cancelled/timed out)
                    continue
                self.stats["responses"] += 1
                self.stats["latency_s"].append(t_done - r.t_enqueue)
                self.stats["queue_wait_s"].append(t_start - r.t_enqueue)
                r.future.set_result(res)

    def _dispatch(self, cfg: PartitionConfig, reqs: list[_Request]):
        """One coalesced fleet run (worker thread): assemble -> stacked
        fleet -> route.  Request order within the group is preserved.
        Returns (results, log record, the dispatch's start time); the
        caller applies them to the server's stats so this thread never
        touches shared state."""
        t_start = time.perf_counter()
        ids = " ".join(str(r.rid) for r in reqs)  # "," splits trace args
        with span("serve.assemble", requests=ids):
            asm = gr.BucketAssembler(self.schedule, lanes=self.cfg.lanes)
            for i, r in enumerate(reqs):
                asm.add(i, r.graph)
            buckets = asm.flush()
        with span("serve.dispatch", requests=ids):
            fres = partition_fleet_stacked(buckets, cfg, self.schedule)
        log = self._log_record(cfg, buckets, fres, len(reqs))
        return [fres.results[i] for i in range(len(reqs))], log, t_start

    @staticmethod
    def _log_record(cfg, buckets, fres, nreq) -> dict:
        """Signature-accounting record for one stacked-fleet run."""
        return {
            "k": cfg.k, "trials": cfg.trials, "backend": cfg.backend,
            "c_finest": cfg.c_finest, "c_coarse": cfg.c_coarse,
            "requests": nreq,
            "buckets": [
                {
                    "capacity": list(sb.capacity), "lanes": len(sb.tags),
                    "real": sum(t is not None for t in sb.tags),
                    # caller paddings of the real lanes: differing values
                    # prove the bucket mixed genuinely different graphs
                    "member_n_max": [nm for t, nm in zip(sb.tags,
                                                         sb.orig_n_max)
                                     if t is not None],
                    "levels": fb.levels,
                    # "masked": some lane is frozen at the level, so its
                    # program carries the lanes' refine flags
                    "level_stats": [
                        {kk: st[kk] for kk in ("level", "n_max", "m_max",
                                               "ell_width") if kk in st}
                        | {"masked": not bool(st["active"].all())}
                        for st in fb.level_stats
                    ],
                }
                for sb, fb in zip(buckets, fres.buckets)
            ],
        }

    # -- warm-start subsystem ---------------------------------------------

    def warmup(self, shapes, ks=None, trials=None, seed=None,
               compositions: str = "subsets") -> dict:
        """Explicit AOT pass: precompile the (rung, k) signature grid.

        ``shapes`` is a list of representative graphs spanning the
        workload's shape families; for each (k, T) in the grid, they are
        assembled into ``lanes``-wide buckets on the pinned ladder and
        run through the complete fleet path — compiling (and persisting,
        when the compile cache is enabled) every executable the same
        workload will hit at serve time.

        A bucket's coarse-level rung chain follows the per-level batch
        max over its lanes, so it depends on WHICH families share the
        bucket (though not on their multiplicity: duplicate lanes —
        filler included — never move the max).  The default
        ``compositions="subsets"`` therefore dispatches every size-<=
        ``lanes`` subset of each rung's families, covering every lane
        composition a replay of these shapes can produce: afterwards the
        same workload compiles ZERO new executables.  That grid is
        ``sum_s C(F, s)`` dispatches per (rung, k) — fine for the few
        families per rung real workloads have; ``compositions="full"``
        dispatches each rung's full member list once (cheapest, but a
        replay whose buckets mix differently may still compile).

        Call before :meth:`start`; returns executables/cache accounting.
        ``ks``/``trials``/``seed`` default to the server's own partition
        config — the signatures its plain ``submit()`` calls will hit
        (coarsening is seeded, so the rung chain follows the seed).
        """
        from itertools import combinations

        base = self.cfg.partition
        ks = (base.k,) if ks is None else ks
        trials = (base.trials,) if trials is None else trials
        seed = base.seed if seed is None else seed
        shapes = list(shapes)
        _, bucket_map = gr.bucket_graphs(shapes, schedule=self.schedule)
        jobs: list[tuple] = []
        for cap in sorted(bucket_map, reverse=True):
            idxs = bucket_map[cap]
            if compositions == "subsets":
                top = min(self.cfg.lanes, len(idxs))
                jobs += [c for s in range(1, top + 1)
                         for c in combinations(idxs, s)]
            elif compositions == "full":
                jobs.append(tuple(idxs))
            else:
                raise ValueError(
                    f"compositions must be 'subsets' or 'full', got "
                    f"{compositions!r}")

        stats = cache_stats()
        before_cache = stats.snapshot()
        before_exec = uncoarsen_level._cache_size()
        t0 = time.perf_counter()
        for k in ks:
            for t in trials:
                cfg = _resolve_cfg(self.cfg.partition, k, t, seed, None)
                for sub in jobs:
                    asm = gr.BucketAssembler(self.schedule,
                                             lanes=self.cfg.lanes)
                    for i in sub:
                        asm.add(i, shapes[i])
                    buckets = asm.flush()
                    fres = partition_fleet_stacked(buckets, cfg,
                                                   self.schedule)
                    self.warmup_log.append(
                        self._log_record(cfg, buckets, fres, len(sub)))
        return {
            "warmup_s": time.perf_counter() - t0,
            "signatures": [(k, t) for k in ks for t in trials],
            "new_executables": uncoarsen_level._cache_size()
            - before_exec,
            "cache_events": CompileCacheStats.delta(before_cache,
                                                    stats.snapshot()),
        }

    # -- reporting ---------------------------------------------------------

    def metrics(self) -> dict:
        """Service-side metrics snapshot (latency, occupancy, compiles)."""
        import numpy as np

        lat = sorted(self.stats["latency_s"])
        wait = sorted(self.stats["queue_wait_s"])
        occ = self.stats["occupancy_hist"]
        occ_total = sum(occ.values())
        return {
            "requests": self.stats["requests"],
            "responses": self.stats["responses"],
            "rejected": self.stats["rejected"],
            "dispatches": self.stats["dispatches"],
            "buckets": self.stats["buckets"],
            "filler_lanes": self.stats["filler_lanes"],
            "occupancy_hist": {str(kk): vv for kk, vv in sorted(occ.items())},
            "mean_occupancy": (
                sum(kk * vv for kk, vv in occ.items()) / occ_total
                if occ_total else 0.0
            ),
            "p50_latency_ms": 1e3 * float(np.percentile(lat, 50)) if lat
            else 0.0,
            "p95_latency_ms": 1e3 * float(np.percentile(lat, 95)) if lat
            else 0.0,
            "p50_queue_wait_ms": 1e3 * float(np.percentile(wait, 50))
            if wait else 0.0,
            "p90_queue_wait_ms": 1e3 * float(np.percentile(wait, 90))
            if wait else 0.0,
            "uncoarsen_executables": uncoarsen_level._cache_size(),
            "compile_cache": cache_stats().snapshot(),
        }


def serve_signatures(dispatch_log) -> set:
    """Distinct ``uncoarsen_level`` compile signatures a serve run must
    have hit — the §10 ``_fleet_signatures`` counting rule lifted to the
    dispatch log: (lanes, T, fine rung, coarse rung, c, ell width,
    masked, k, backend).  With the fixed-lanes discipline this collapses to one
    signature per (rung, k): lanes and T never vary within a server."""
    sigs = set()
    for d in dispatch_log:
        for b in d["buckets"]:
            sts = b["level_stats"]
            for j, st in enumerate(sts):
                nc = st["n_max"] if j == 0 else sts[j - 1]["n_max"]
                c = d["c_finest"] if st["level"] == 0 else d["c_coarse"]
                md = st.get("ell_width") if d["backend"] == "ell" else None
                sigs.add((b["lanes"], d["trials"], st["n_max"], st["m_max"],
                          nc, c, md, st["masked"], d["k"], d["backend"]))
    return sigs
