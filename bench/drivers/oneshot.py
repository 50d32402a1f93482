"""One-shot window driver: ``partition()`` of a fixed set of meshes, back
to back.

Configuration keys: ``generator`` (``scale``: the meshes are Delaunay
meshes of 2^scale points, padded to (2^scale, 6 * 2^scale);
``mesh_seeds``: the generator seeds of the set) and ``partition``
(``PartitionConfig`` fields).  Traffic keys: ``k``; ``trace_partitions``,
how many partitions of a ``--trace 1`` run the profiler records.

Every seed partitions the same set of meshes, in another order: the time
of a partition changes with the mesh by 15% (iterations to converge), so
a mesh drawn from the seed would make the seed change the work.  The
window runs whole cycles over the set, each in an order drawn from
``--seed``, until ``--seconds`` have passed, so every run does the same
work per partition.  Set-up builds the meshes and partitions each once,
which compiles or loads every level program; the window compiles nothing.
Each partition ends with its labels fetched to the host, and every one is
checked against the reference once the window has closed.
"""
from __future__ import annotations

import time

import numpy as np

from bench import graphs, reference, window


def _partition_record(res, labels, mesh_index: int) -> dict:
    return {
        "mesh": mesh_index,
        "labels": labels,
        "cut": res.cut,
        "times": dict(res.times),
        "refine_iters": sum(int(ls.get("lp_iters", 0)) + int(ls.get(
            "rb_iters", 0)) for ls in res.level_stats),
        "levels": res.levels,
    }


def run(ctx) -> dict:
    import jax

    from repro.core.partition import PartitionConfig, partition

    cfg, traffic = ctx.config, ctx.traffic
    gen = cfg["generator"]
    meshes = [graphs.delaunay_mesh(np.random.default_rng(s), gen["scale"])
              for s in gen["mesh_seeds"]]
    gs = [graphs.to_graph(m) for m in meshes]
    pcfg = PartitionConfig(k=traffic["k"], **ctx.partition_settings())
    order = np.random.default_rng(ctx.seed)

    for g in gs:
        np.asarray(partition(g, pcfg).parts)
    ctx.setup_done()

    done = []
    t_begin = time.perf_counter()
    with ctx.traced(traffic["trace_partitions"]) as tracer:
        while time.perf_counter() - t_begin < ctx.seconds:
            for i in order.permutation(len(gs)):
                with tracer.step():
                    with jax.profiler.TraceAnnotation("bench.partition"):
                        res = partition(gs[i], pcfg)
                    with jax.profiler.TraceAnnotation("bench.fetch"):
                        labels = np.asarray(res.parts)
                done.append(_partition_record(res, labels, int(i)))
    window_s = time.perf_counter() - t_begin
    ctx.window_done()

    ref_cuts = [reference.cut_of(m.edges, reference.rcb_parts(m.points,
                                                              pcfg.k))
                for m in meshes]
    checked = [reference.check_numbers(
        meshes[p["mesh"]].edges, meshes[p["mesh"]].n, pcfg.k, p["labels"],
        p["cut"], ref_cuts[p["mesh"]]) for p in done]
    untraced = done[tracer.steps:] or done
    return {
        "attempted": len(done),
        "failed": 0,
        "readings": reference.worst(checked) | {"failed": 0},
        "end_to_end": {
            "partition_s": window.per_item(window_s, len(done)),
            "cut": float(np.median([c["cut"] for c in checked])),
        },
        "layers": {"partitions": untraced},
        "info": {"n": meshes[0].n, "m": [m.m for m in meshes],
                 "k": pcfg.k, "levels": sorted({p["levels"] for p in done}),
                 "partitions": len(done), "window_s": window_s,
                 "rcb_cut": ref_cuts,
                 "cut_ratio_to_rcb": max(c["cut_ratio"] for c in checked)},
    }
