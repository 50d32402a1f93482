"""Shared test configuration.

* Makes the repo root importable so tests can exercise the ``benchmarks``
  package (the CI quality gate) without installing anything.
* ``JET_TEST_BACKEND`` env filter: when set to ``dense`` / ``sorted`` /
  ``ell``, every test parametrized over a connectivity ``backend`` keeps
  only the matching parametrization (unparametrized tests always run).
  CI matrixes its tier-1 job over this variable so the three backends run
  in parallel lanes instead of serially in one.
* Entry points turn on JAX's persistent compilation cache
  (``repro.launch.compile_cache``).  Inside a test it goes to a per-worker
  temp directory, and the cache settings are restored after the test, so
  no test writes into the checkout or changes what later tests compile.
* XLA's CPU backend maps each compiled executable's code in regions of its
  own, and a worker that has compiled a few thousand programs reaches the
  kernel's limit on mapped regions (``vm.max_map_count``, 65,530 by
  default): the next compile fails in LLVM ("Cannot allocate memory") and
  the worker dies with a segfault.  Once a test leaves the process past
  60% of the limit, JAX's caches are dropped, which unmaps them.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

_CACHE_OPTIONS = (
    "jax_compilation_cache_dir",
    "jax_enable_compilation_cache",
    "jax_persistent_cache_min_entry_size_bytes",
    "jax_persistent_cache_min_compile_time_secs",
)


@pytest.fixture(autouse=True)
def _scoped_compile_cache(monkeypatch, tmp_path_factory):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path_factory.getbasetemp() / "jax-cache"))
    before = {o: getattr(jax.config, o) for o in _CACHE_OPTIONS}
    yield
    if any(getattr(jax.config, o) != v for o, v in before.items()):
        from jax.experimental.compilation_cache import compilation_cache as cc

        for o, v in before.items():
            jax.config.update(o, v)
        cc.reset_cache()

def _map_counts() -> tuple[int, int] | None:
    """(mapped regions of this process, the kernel's limit), or None where
    ``/proc`` does not say."""
    try:
        with open("/proc/self/maps", "rb") as f:
            regions = sum(1 for _ in f)
        with open("/proc/sys/vm/max_map_count") as f:
            return regions, int(f.read())
    except (OSError, ValueError):
        return None


@pytest.fixture(autouse=True)
def _bounded_code_mappings():
    yield
    counts = _map_counts()
    if counts is not None and counts[0] > 0.6 * counts[1]:
        jax.clear_caches()


_BACKENDS = ("dense", "sorted", "ell")


def pytest_collection_modifyitems(config, items):
    backend = os.environ.get("JET_TEST_BACKEND")
    if not backend:
        return
    if backend not in _BACKENDS:
        raise ValueError(
            f"JET_TEST_BACKEND={backend!r} must be one of {_BACKENDS}"
        )
    kept, deselected = [], []
    for item in items:
        callspec = getattr(item, "callspec", None)
        param = callspec.params.get("backend") if callspec else None
        if param is not None and param != backend:
            deselected.append(item)
        else:
            kept.append(item)
    if deselected:
        config.hook.pytest_deselected(items=deselected)
        items[:] = kept
