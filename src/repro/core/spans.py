"""Program spans: the partitioner's host phases on the profiler's clock.

:func:`span` opens a ``jax.profiler.TraceAnnotation``, so while a profiler
session runs, every span lands in the same trace, and on the same clock,
as the device's op events; with no session it costs an annotation object.
Given ``times`` and ``key`` it also adds its ``perf_counter`` duration to
``times[key]``, which is how ``PartitionResult.times`` is kept.

Device phases are named inside the jitted programs with
``jax.named_scope`` (``jet.*``, ``uncoarsen.*``, ``initial``), which names
ops in the HLO metadata: it costs nothing at run time, and at set-up only
the lowering of a path component per op (the finest level program's
debug text grows by 3%).
"""
from __future__ import annotations

import time
from contextlib import contextmanager

import jax


@contextmanager
def span(name: str, times: dict | None = None, key: str | None = None,
         **args):
    """Host span ``name`` with trace arguments ``args``; adds its seconds to
    ``times[key]`` when ``key`` is given."""
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name, **args):
            yield
    finally:
        if key is not None:
            times[key] = times.get(key, 0.0) + time.perf_counter() - t0
