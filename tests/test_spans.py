"""Program spans, device scopes and set-up counters.

Under a profiler session, ``partition()`` and the fleet path write their
phases as nested host events (``core/spans.py``); the level programs name
the Jet loop's phases in their HLO metadata (``jax.named_scope``); and
``CompileCacheStats`` splits set-up into tracing, lowering and compiling.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.core import initial
from repro.core import partition as pt
from repro.core.graph import stack_graphs
from repro.core.spans import span
from repro.data import graphs as gen
from repro.launch.compile_cache import CompileCacheStats

CFG = pt.PartitionConfig(k=4, coarse_target=48, max_iter=20, patience=3)
PROGRAM_SPANS = ("partition", "partition_fleet", "partition.", "coarsen.",
                 "uncoarsen.")


def _host_spans(log_dir):
    """``(start, end, name, thread)`` of the program's spans in the
    newest trace under ``log_dir``."""
    paths = sorted(log_dir.glob("**/*.xplane.pb"))
    assert paths, "the profiler wrote no trace"
    out = []
    for plane in ProfileData.from_file(str(paths[-1])).planes:
        if plane.name != "/host:CPU":
            continue
        for t, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name in PROGRAM_SPANS[:2] or ev.name.startswith(
                        PROGRAM_SPANS[2:]):
                    out.append((ev.start_ns, ev.end_ns, ev.name, t))
    return out


def _inside(inner, outer) -> bool:
    return (inner[3] == outer[3] and outer[0] <= inner[0]
            and inner[1] <= outer[1])


def _nested(spans, outer: str, inner: str) -> bool:
    """Some ``inner`` span lies inside some ``outer`` span, and every one
    of them inside one."""
    outs = [s for s in spans if s[2] == outer]
    ins = [s for s in spans if s[2] == inner]
    return bool(ins) and all(any(_inside(i, o) for o in outs) for i in ins)


def test_partition_writes_nested_spans(tmp_path):
    g = gen.grid2d(16, 16)
    pt.partition(g, CFG)  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        res = pt.partition(g, CFG)
    spans = _host_spans(tmp_path)
    assert _nested(spans, "partition", "partition.coarsen")
    assert _nested(spans, "partition.coarsen", "coarsen.level")
    # each level's stat fetch, and the input graph's before the first
    levels = [s for s in spans if s[2] == "coarsen.level"]
    fetches = [s for s in spans if s[2] == "coarsen.fetch"]
    assert all(any(_inside(f, lv) for f in fetches) for lv in levels)
    assert len(fetches) == len(levels) + 1
    assert _nested(spans, "partition", "partition.initial")
    assert _nested(spans, "partition", "partition.uncoarsen")
    assert _nested(spans, "partition.uncoarsen", "uncoarsen.level")
    assert _nested(spans, "partition.uncoarsen", "partition.fetch")
    assert res.levels > 1
    assert sum(s[2] == "uncoarsen.level" for s in spans) == res.levels
    assert sum(s[2] == "partition" for s in spans) == 1


def test_times_keep_their_phases():
    res = pt.partition(gen.grid2d(12, 12), CFG)
    assert set(res.times) == {"coarsen_s", "uncoarsen_s", "total_s"}
    assert 0 < res.times["coarsen_s"] + res.times["uncoarsen_s"] <= (
        res.times["total_s"])


def test_fleet_writes_nested_spans(tmp_path):
    gs = [gen.grid2d(14, 14), gen.grid2d(13, 13)]
    pt.partition_fleet(gs, CFG)
    with jax.profiler.trace(str(tmp_path)):
        fres = pt.partition_fleet(gs, CFG)
    spans = _host_spans(tmp_path)
    assert _nested(spans, "partition_fleet", "partition.coarsen")
    assert _nested(spans, "partition_fleet", "partition.initial")
    assert _nested(spans, "partition.coarsen", "coarsen.level")
    assert _nested(spans, "partition_fleet", "partition.uncoarsen")
    assert _nested(spans, "partition.uncoarsen", "uncoarsen.level")
    assert _nested(spans, "partition_fleet", "partition.fetch")
    assert _nested(spans, "partition.uncoarsen", "partition.fetch")
    levels = sum(b.levels for b in fres.buckets)
    assert sum(s[2] == "uncoarsen.level" for s in spans) == levels
    assert set(fres.times) == {"bucket_s", "coarsen_s", "uncoarsen_s",
                               "total_s"}
    assert fres.times["total_s"] >= (fres.times["bucket_s"]
                                     + fres.times["coarsen_s"]
                                     + fres.times["uncoarsen_s"])


def test_span_adds_its_seconds_under_its_key():
    times = {"x": 1.0}
    with span("test.outer", times, "x", level=3):
        with span("test.inner"):
            pass
    with span("test.outer", times, "y"):
        pass
    assert times["x"] > 1.0 and times["y"] > 0.0
    assert set(times) == {"x", "y"}


@pytest.mark.parametrize("backend", ["dense", "ell"])
def test_level_program_names_the_jet_phases(backend):
    g = gen.grid2d(8, 8)
    text = pt.uncoarsen_level.lower(
        stack_graphs([g]), jnp.arange(g.n_max, dtype=jnp.int32)[None],
        jnp.zeros((1, 1, g.n_max), jnp.int32), None, 0.999, k=4, lam=0.03,
        c=0.25, backend=backend, patience=3, max_iter=10, b_max=2,
        variant="full",
        rebuild_every=0, max_degree=4 if backend == "ell" else None,
    ).as_text(debug_info=True)
    for scope in ("jet.lp", "jet.rw", "jet.rs", "jet.apply", "jet.queries",
                  "uncoarsen.project", "uncoarsen.build_state"):
        assert scope in text, scope


@pytest.mark.parametrize("method", ["voronoi", "random"])
def test_initial_program_names_its_scope(method):
    g = gen.grid2d(8, 8)
    text = initial._initial_batch.lower(
        stack_graphs([g]), jnp.arange(2, dtype=jnp.int32), k=4,
        method=method,
    ).as_text(debug_info=True)
    assert "initial" in text.replace("_initial_batch", "")


def test_compile_stats_count_tracing_and_lowering():
    stats = CompileCacheStats()
    jax.monitoring.register_event_duration_secs_listener(stats.on_duration)
    jax.monitoring.register_scalar_listener(stats.on_scalar)
    try:
        @jax.jit
        def inner(x):
            return jnp.sin(x) * 2

        @jax.jit
        def outer(x):
            return inner(x) + jnp.cos(x)

        np.asarray(outer(jnp.arange(7.0)))
        after = stats.snapshot()
    finally:
        jax.monitoring.unregister_event_duration_listener(stats.on_duration)
        jax.monitoring.unregister_scalar_listener(stats.on_scalar)
    assert after["trace_s"] > 0 and after["lower_s"] > 0
    assert after["compiles"] >= 1 and after["compile_s"] > 0


def test_nested_traces_count_once():
    stats = CompileCacheStats()
    name = "/jax/core/compile/jaxpr_trace_duration"
    stats.on_scalar(name, 0.0)      # outer trace starts
    stats.on_scalar(name, 0.0)      # inner jit traced inside it
    stats.on_duration(name, 0.25)   # inner ends
    stats.on_duration(name, 1.0)    # outer ends: its seconds hold the inner
    stats.on_duration(name, 0.5)    # a trace whose start was not seen
    assert stats.snapshot()["trace_s"] == pytest.approx(1.5)
    assert "compiles" not in stats.snapshot()
