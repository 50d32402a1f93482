"""§11 serving: coalesced responses must be standalone-bit-identical.

The server's contract mirrors §9/§10: micro-batching changes the
SCHEDULE (who shares a dispatch, a bucket, a lane), never the VALUES —
every response equals the ``partition()`` result for the same config.
Plus: warmup covers the replay (zero post-warmup compiles), admission
rejects oversized graphs with the queue intact, and the CLI rejects
duplicate fleet member names.
"""
import asyncio
from dataclasses import replace

import numpy as np
import pytest

from repro.core.partition import PartitionConfig, partition
from repro.data import graphs as gen

pytest.importorskip("repro.launch.partition_serve")

from repro.launch.partition_serve import (  # noqa: E402
    PartitionServer, ServeConfig, serve_signatures,
)

# grid 6x6 and 6x5 round to one (64, 128) rung on the (64, 256) ladder
# (mixed-occupancy bucket); 4x4 lands in its own (64, 64) bucket
BASE = PartitionConfig(k=2, coarse_target=32, max_iter=30, patience=3)


def _server(**kw):
    return PartitionServer(ServeConfig(
        ladder_n=64, ladder_m=256, window_s=0.02, lanes=2,
        partition=BASE, **kw,
    ))


def test_serve_bit_identical_mixed_shape_mixed_k():
    server = _server()
    gs = [gen.grid2d(6, 6), gen.grid2d(6, 5), gen.grid2d(4, 4)]
    ks = [2, 2, 3]

    async def run():
        async with server:
            return await asyncio.gather(
                *(server.submit(g, k=k) for g, k in zip(gs, ks)))

    results = asyncio.run(run())
    for g, k, res in zip(gs, ks, results):
        solo = partition(g, replace(BASE, k=k))
        assert res.cut == solo.cut, k
        assert res.balanced == solo.balanced
        assert res.trial_cuts == solo.trial_cuts
        assert res.parts.shape == solo.parts.shape
        np.testing.assert_array_equal(np.asarray(res.parts),
                                      np.asarray(solo.parts))
    # the burst coalesced: the two near-sized grids shared one bucket
    occ = server.stats["occupancy_hist"]
    assert occ.get(2, 0) >= 1, occ
    # every dispatched bucket was pinned to the configured lane width
    assert server.dispatch_log
    for d in server.dispatch_log:
        assert all(b["lanes"] == 2 for b in d["buckets"])


def test_warmup_covers_replay():
    """After the AOT pass over the workload's shapes × k grid, replaying
    compiles zero new fleet executables."""
    from repro.core.partition import uncoarsen_level

    server = _server()
    shapes = [gen.grid2d(6, 6), gen.grid2d(6, 5), gen.grid2d(4, 4)]
    rep = server.warmup(shapes, ks=(2, 3))
    assert rep["new_executables"] >= 0
    assert len(serve_signatures(server.warmup_log)) > 0

    execs0 = uncoarsen_level._cache_size()

    async def run():
        async with server:
            return await asyncio.gather(
                server.submit(shapes[0], k=2),
                server.submit(shapes[1], k=2),
                server.submit(shapes[2], k=3),
            )

    results = asyncio.run(run())
    assert all(r.cut >= 0 for r in results)
    assert uncoarsen_level._cache_size() == execs0, \
        "replay after warmup must not compile new executables"
    assert serve_signatures(server.dispatch_log) <= \
        serve_signatures(server.warmup_log)


def test_oversized_request_rejected_queue_intact():
    server = _server()
    big = gen.grid2d(30, 30)  # n=900 over the 64-vertex ladder top

    async def run():
        async with server:
            with pytest.raises(ValueError, match="ladder"):
                await server.submit(big, k=2)
            # the server keeps serving after a rejection
            return await server.submit(gen.grid2d(4, 4), k=2)

    res = asyncio.run(run())
    solo = partition(gen.grid2d(4, 4), replace(BASE, k=2))
    assert res.cut == solo.cut
    assert server.stats["rejected"] == 1


def test_submit_requires_started_server():
    server = _server()

    async def run():
        with pytest.raises(RuntimeError, match="not started"):
            await server.submit(gen.grid2d(4, 4), k=2)

    asyncio.run(run())


def test_cli_fleet_rejects_duplicate_member_names(capsys):
    from repro.launch.partition_cli import main

    rc = main(["--fleet", "grid:8", "grid:8", "--k", "2"])
    captured = capsys.readouterr()
    assert rc == 2
    assert "duplicate" in captured.err
    assert "grid:8" in captured.err
    # distinct seeds make distinct members — accepted (parse-level check:
    # the specs differ, so no early exit on the duplicate path)
    from repro.launch.partition_cli import _parse_fleet_spec

    assert _parse_fleet_spec("grid:8:0", 16, 0) != \
        _parse_fleet_spec("grid:8:1", 16, 0)


def test_queue_wait_is_counted_and_dispatches_are_spanned(tmp_path):
    """Each response records its wait from enqueue to the start of its
    dispatch, never longer than its latency; the worker's assembly and
    dispatch are host spans carrying the request ids."""
    import jax
    from jax.profiler import ProfileData

    server = _server()
    gs = [gen.grid2d(6, 6), gen.grid2d(6, 5), gen.grid2d(4, 4)]

    async def run():
        async with server:
            return await asyncio.gather(
                *(server.submit(g, k=2) for g in gs))

    asyncio.run(run())  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        asyncio.run(run())
    waits = list(server.stats["queue_wait_s"])
    lats = list(server.stats["latency_s"])
    assert len(waits) == len(lats) == 2 * len(gs)
    assert all(0 <= w <= lat for w, lat in zip(waits, lats))
    m = server.metrics()
    assert 0 <= m["p50_queue_wait_ms"] <= m["p90_queue_wait_ms"]
    assert m["p90_queue_wait_ms"] <= 1e3 * max(waits)

    path = sorted(tmp_path.glob("**/*.xplane.pb"))[-1]
    spans = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    spans.setdefault(ev.name, []).append(dict(ev.stats))
    assert set(spans) == {"serve.assemble", "serve.dispatch"}
    ids = {int(i) for args in spans["serve.dispatch"]
           for i in str(args["requests"]).split()}
    assert ids == {4, 5, 6}  # the second burst's requests
    assert len(spans["serve.assemble"]) == len(spans["serve.dispatch"])
