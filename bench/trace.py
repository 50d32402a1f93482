"""Reduce a profiler trace to device busy and idle time.

``reduce_trace(path, span)`` reads one ``.xplane.pb`` with
``jax.profiler.ProfileData`` and returns, over the window that the host
span named ``span`` covers:

* ``busy_s``: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line, else ``XLA Modules``), averaged over the
  devices that ran any;
* ``window_s``: the span's length, and ``idle_share`` = 1 - busy / window;
* ``device_ops``: the device programs (the ``XLA Modules`` line) that
  took most time, ``[name, seconds]``, at most 10;
* ``idle_gaps``: the device's idle time within the window, summed by what
  the host was doing meanwhile: the innermost host event (a harness span or
  a Python frame) that covers the middle of each gap, at most 10.

The pure functions below take plain ``(start_ns, end_ns)`` intervals, so
the arithmetic is tested without a trace.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def union(intervals) -> list[tuple[float, float]]:
    """Merge overlapping or touching intervals; sorted, disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_ns(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(clip(intervals, lo, hi)))


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi] that no interval covers."""
    out, cur = [], lo
    for s, e in union(clip(intervals, lo, hi)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


SHORT_GAP_NS = 10_000


def label_at(t: float, host_events, starts) -> str:
    """The innermost host event covering time ``t``: the latest-starting
    one that is still open.  ``host_events`` holds ``(start, end, name)``
    sorted by start, and ``starts`` their starts."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0 and host_events[i][1] <= t:
        i -= 1
    return host_events[i][2] if i >= 0 else "(no host span)"


def label_gaps(gap_list, host_events, top: int = 10):
    """Idle time summed by host label, longest first.  Gaps shorter than
    10 us, the launch of the next op, are summed under one label."""
    # outer events first where two start together, so the walk back from
    # a time meets the inner one first
    host_events = sorted(host_events, key=lambda h: (h[0], h[0] - h[1]))
    starts = [h[0] for h in host_events]
    by = defaultdict(float)
    for s, e in gap_list:
        label = (label_at((s + e) / 2, host_events, starts)
                 if e - s >= SHORT_GAP_NS else "(gaps under 10 us)")
        by[label] += (e - s) / 1e9
    return sorted(([k, v] for k, v in by.items()), key=lambda kv: -kv[1])[
        :top]


def top_names(events, top: int = 10):
    """``(start, end, name)`` events summed by name, longest first."""
    by = defaultdict(float)
    for s, e, name in events:
        by[re.sub(r"\(\d+\)$", "", name)] += (e - s) / 1e9
    return sorted(([k, v] for k, v in by.items()), key=lambda kv: -kv[1])[
        :top]


def summarize(device_ops: dict, device_modules: list, host_events,
              lo: float, hi: float) -> dict:
    """The reduction over window [lo, hi] (ns) from plain events:
    ``device_ops`` maps each device to its op intervals."""
    window = hi - lo
    used = {d: iv for d, iv in device_ops.items() if clip(iv, lo, hi)}
    if not used or window <= 0:
        return {}
    busy = sum(busy_ns(iv, lo, hi) for iv in used.values()) / len(used)
    first = next(iter(sorted(used)))
    return {
        "busy_s": busy / 1e9,
        "window_s": window / 1e9,
        "idle_share": 1.0 - busy / window,
        "devices": len(used),
        "device_ops": top_names(
            [(s, e, n) for s, e, n in device_modules if e > lo and s < hi]),
        "idle_gaps": label_gaps(gaps(used[first], lo, hi), host_events),
    }


def read_xplane(path: str, span: str) -> dict:
    """Plain events of one trace file, and the window of host span
    ``span`` (its first occurrence)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops: dict[str, list] = {}
    modules: list = []
    host: list = []
    window = None
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            ops = lines.get(OPS_LINE, lines.get(MODULES_LINE))
            if ops is not None:
                device_ops[plane.name] = [(ev.start_ns, ev.end_ns)
                                          for ev in ops.events]
            mods = lines.get(MODULES_LINE)
            if mods is not None:
                modules += [(ev.start_ns, ev.end_ns, ev.name)
                            for ev in mods.events]
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                if ln.name.startswith("tf_"):  # runtime pools, not the host
                    continue                   # program's own threads
                for ev in ln.events:
                    host.append((ev.start_ns, ev.end_ns, ev.name))
                    if window is None and ev.name == span:
                        window = (ev.start_ns, ev.end_ns)
    return {"device_ops": device_ops, "modules": modules, "host": host,
            "window": window}


def find_xplane(log_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def reduce_trace(log_dir: str, span: str) -> dict:
    """The reduction of the newest trace under ``log_dir`` over host span
    ``span``; empty where there is no trace, no span or no device op."""
    path = find_xplane(log_dir)
    if path is None:
        return {}
    ev = read_xplane(path, span)
    if ev["window"] is None:
        return {}
    lo, hi = ev["window"]
    return summarize(ev["device_ops"], ev["modules"], ev["host"], lo, hi)
