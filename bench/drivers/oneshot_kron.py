"""One-shot window driver for Graph500 Kronecker graphs: ``partition()`` of
a fixed pair of graphs, back to back.

Configuration keys: ``generator`` (``scale``, ``edge_factor``,
``initiator``: the Graph500 generator's parameters; ``graph_seeds``: the
generator seeds of the set; ``n_max``/``m_max``: the capacity every graph
is padded to, or absent for the bound of any seed) and ``partition``
(``PartitionConfig`` fields).  Traffic keys: ``k``; ``trace_partitions``,
how many partitions of a ``--trace 1`` run the profiler records.

The window is that of ``oneshot.py``: set-up builds the graphs and
partitions each once, which compiles or loads every level program; the
window compiles nothing and runs whole cycles over the set, each in an
order drawn from ``--seed``.  Each partition ends with its labels fetched
to the host, and every one is checked against the reference once the
window has closed.  These graphs have no coordinates, so ``cut_ratio`` is
taken against a seeded random, exactly balanced assignment: recorded, not
compared.

Each partition's record carries, beside ``oneshot.py``'s, the per-level
``level_counts``: the coarsening counters (``hem_unmatched``, ``twohop``,
``twohop_pairs``; None where the program does not count them) and
``rs_iters``, coarsest level first.
"""
from __future__ import annotations

import time

import numpy as np

from bench import graphs, kronecker, reference, window

LEVEL_KEYS = ("level", "n", "hem_unmatched", "twohop", "twohop_pairs",
              "lp_iters", "rb_iters", "rs_iters")


def random_parts(rng: np.random.Generator, n: int, k: int) -> np.ndarray:
    """A random assignment of ``n`` vertices to ``k`` parts whose sizes
    differ by at most one."""
    return rng.permutation(n) % k


def _partition_record(res, labels, graph_index: int) -> dict:
    return {
        "graph": graph_index,
        "labels": labels,
        "cut": res.cut,
        "times": dict(res.times),
        "refine_iters": sum(int(ls.get("lp_iters", 0)) + int(ls.get(
            "rb_iters", 0)) for ls in res.level_stats),
        "levels": res.levels,
        "level_counts": [{kk: ls.get(kk) for kk in LEVEL_KEYS}
                         for ls in res.level_stats],
    }


def run(ctx) -> dict:
    import jax

    from repro.core.partition import PartitionConfig, partition

    cfg, traffic = ctx.config, ctx.traffic
    gen = cfg["generator"]
    kgs = [kronecker.kronecker_graph(
        np.random.default_rng(s), gen["scale"], gen.get("n_max"),
        gen.get("m_max"), gen["edge_factor"], tuple(gen["initiator"]))
        for s in gen["graph_seeds"]]
    gs = [graphs.to_graph(kg) for kg in kgs]
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

    # a graph's random reference depends on its generator seed alone
    ref_cuts = [reference.cut_of(kg.edges, random_parts(
        np.random.default_rng(s), kg.n, pcfg.k))
        for kg, s in zip(kgs, gen["graph_seeds"])]
    checked = [reference.check_numbers(
        kgs[p["graph"]].edges, kgs[p["graph"]].n, pcfg.k, p["labels"],
        p["cut"], ref_cuts[p["graph"]]) for p in done]
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
        "info": {"n": [kg.n for kg in kgs], "m": [kg.m for kg in kgs],
                 "max_degree": [int(kg.degrees().max()) for kg in kgs],
                 "k": pcfg.k, "levels": sorted({p["levels"] for p in done}),
                 "partitions": len(done), "window_s": window_s,
                 "random_cut": ref_cuts,
                 "cut_ratio_to_random": max(c["cut_ratio"]
                                            for c in checked)},
    }
