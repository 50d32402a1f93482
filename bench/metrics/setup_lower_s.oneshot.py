"""Set-up: seconds spent tracing functions to jaxprs and lowering them to
MLIR (``trace_s + lower_s`` of the process's
``launch/compile_cache.py:CompileCacheStats``), the work the persistent
compilation cache cannot skip.  Read after the window, which traces,
lowers and compiles nothing, so the sums are set-up's; None where the
program does not count them."""


def read(run):
    from repro.launch.compile_cache import cache_stats

    now = cache_stats().snapshot()
    if "trace_s" not in now or "lower_s" not in now:
        return None
    return now["trace_s"] + now["lower_s"]
