"""Set-up: seconds of backend compiles, persistent-cache loads included
(``compile_s`` of the process's
``launch/compile_cache.py:CompileCacheStats``).  Read after the window,
which compiles nothing, so the sum is set-up's."""


def read(run):
    from repro.launch.compile_cache import cache_stats

    return cache_stats().snapshot().get("compile_s")
