"""The plain reference that decides ``correct``: numpy only, nothing of the
program.

For each partition the timed path produced, the benchmark recomputes from
its own edge list the numbers below and holds each to its limit:

* ``bad_labels``: real vertices whose part id lies outside [0, k).  Exact.
* ``cut_gap``: |cut the program reported - cut recomputed here|.  Exact.
* ``imbalance``: max part weight * k / W - 1, against the configuration's
  balance bound lambda (its stated guarantee).
* ``failed``: requests that failed, were refused or never came.  Exact.

Beside them it records, without a limit, ``cut_ratio``: the program's cut
over that of recursive coordinate bisection (``rcb_parts``) of the same
mesh at the same k, a plain geometric partitioner of exactly balanced
parts.  No control separates it from sound runs (see ``PERF.md``), so it
is reported and not compared; the ``cut`` end-to-end metric bounds quality.
"""
from __future__ import annotations

import numpy as np


def rcb_parts(points: np.ndarray, k: int) -> np.ndarray:
    """Recursive coordinate bisection into ``k`` parts whose sizes differ
    by at most one: each block splits across its longer side, with
    ``floor(parts/2)`` parts' share of the vertices on the low side."""
    n = points.shape[0]
    parts = np.empty(n, np.int64)
    stack = [(np.arange(n), 0, k)]
    while stack:
        idx, first, count = stack.pop()
        if count == 1:
            parts[idx] = first
            continue
        lo_parts = count // 2
        p = points[idx]
        axis = int(np.argmax(p.max(axis=0) - p.min(axis=0)))
        split = (idx.shape[0] * lo_parts) // count
        order = np.argsort(p[:, axis], kind="stable")
        stack.append((idx[order[:split]], first, lo_parts))
        stack.append((idx[order[split:]], first + lo_parts, count - lo_parts))
    return parts


def cut_of(edges: np.ndarray, parts: np.ndarray) -> int:
    """Undirected edges (unit weight) whose ends lie in different parts."""
    return int(np.count_nonzero(parts[edges[:, 0]] != parts[edges[:, 1]]))


def check_numbers(edges: np.ndarray, n: int, k: int, labels, reported_cut:
                  int, ref_cut: int) -> dict[str, float]:
    """The numbers compared for one partition of a mesh with unit vertex
    weights: ``labels`` holds at least the n real vertices' part ids."""
    p = np.asarray(labels)[:n].astype(np.int64)
    bad = int(np.count_nonzero((p < 0) | (p >= k)))
    sizes = np.bincount(np.clip(p, 0, k - 1), minlength=k)
    cut = cut_of(edges, p)
    return {
        "bad_labels": bad,
        "cut_gap": abs(int(reported_cut) - cut),
        "imbalance": float(sizes.max()) * k / n - 1.0,
        "cut_ratio": cut / max(ref_cut, 1),
        "cut": cut,
    }


def worst(records: list[dict[str, float]]) -> dict[str, float]:
    """Per number, the worst (largest) reading over ``records``; an empty
    list has no readings."""
    keys = ("bad_labels", "cut_gap", "imbalance", "cut_ratio", "cut")
    return {kk: max(r[kk] for r in records) for kk in keys} if records else {}


def limits(lam: float) -> dict[str, float]:
    """Each compared number's limit: 0 for the exact ones, and the
    configuration's own balance bound for ``imbalance``."""
    return {"bad_labels": 0, "cut_gap": 0, "imbalance": lam, "failed": 0}


def judge(readings: dict[str, float], lims: dict[str, float]) -> tuple[
        bool, dict[str, dict[str, float]]]:
    """``(correct, checks)``: each number beside its limit; correct when
    every number is at or under its limit and none is missing."""
    checks = {kk: {"value": readings.get(kk, float("nan")),
                   "limit": float(lim)} for kk, lim in lims.items()}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
