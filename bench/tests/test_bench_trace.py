"""The reduction from trace events to device busy and idle time."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import trace  # noqa: E402


def test_union_merges_overlapping_and_touching_ops():
    ops = [(5, 8), (0, 3), (2, 4), (8, 9), (20, 25)]
    assert trace.union(ops) == [(0, 4), (5, 9), (20, 25)]


def test_busy_and_gaps_are_clipped_to_the_window():
    ops = [(0, 10), (15, 30), (28, 40), (90, 120)]
    assert trace.busy_ns(ops, 5, 100) == 5 + 25 + 10
    assert trace.gaps(ops, 5, 100) == [(10, 15), (40, 90)]
    assert trace.gaps([], 0, 7) == [(0, 7)]


def test_gaps_are_labelled_by_the_innermost_open_host_event():
    ms = 1_000_000
    host = [(0, 100 * ms, "bench.window"),
            (0, 50 * ms, "bench.partition"),
            (10 * ms, 20 * ms, "_fetch_stats"),
            (60 * ms, 70 * ms, "bench.fetch")]
    gaps = [(12 * ms, 14 * ms), (30 * ms, 40 * ms), (62 * ms, 64 * ms),
            (80 * ms, 90 * ms), (95 * ms, 95 * ms + 5)]
    labels = dict(trace.label_gaps(gaps, host))
    assert labels == pytest.approx({
        "_fetch_stats": 0.002, "bench.partition": 0.010,
        "bench.fetch": 0.002, "bench.window": 0.010,
        "(gaps under 10 us)": 5e-9})


def test_summarize_gives_idle_share_top_programs_and_gaps():
    ms = 1_000_000
    ops = {"/device:TPU:0": [(0, 40 * ms), (30 * ms, 60 * ms),
                             (80 * ms, 90 * ms)]}
    modules = [(0, 60 * ms, "jit_uncoarsen_level(7)"),
               (80 * ms, 90 * ms, "jit_coarsen_level(3)"),
               (200 * ms, 300 * ms, "outside")]
    host = [(0, 100 * ms, "bench.window"),
            (60 * ms, 80 * ms, "bench.fetch")]
    s = trace.summarize(ops, modules, host, 0, 100 * ms)
    assert s["busy_s"] == pytest.approx(0.070)
    assert s["window_s"] == pytest.approx(0.100)
    assert s["idle_share"] == pytest.approx(0.30)
    assert s["device_ops"] == [["jit_uncoarsen_level", pytest.approx(0.06)],
                               ["jit_coarsen_level", pytest.approx(0.01)]]
    assert s["idle_gaps"] == [["bench.fetch", pytest.approx(0.02)],
                              ["bench.window", pytest.approx(0.01)]]


def test_busy_is_averaged_over_the_devices_that_ran():
    ops = {"/device:TPU:0": [(0, 50)], "/device:TPU:1": [(0, 100)],
           "/device:TPU:2": [(500, 600)]}
    s = trace.summarize(ops, [], [], 0, 100)
    assert s["devices"] == 2
    assert s["idle_share"] == pytest.approx(0.25)


def test_nothing_to_read_gives_nothing(tmp_path):
    assert trace.summarize({}, [], [], 0, 100) == {}
    assert trace.reduce_trace(str(tmp_path), "bench.window") == {}
