"""Host reference for device coarsening: a numpy repack of each level.

``coarsen_once`` builds one level from the same matching and contraction
kernels as ``coarsen.coarsen_level``, but reads every size back to the
host and repacks the coarse graph into tight arrays with numpy; the
device path must match it bit for bit (tests/test_coarsen_device.py).
``multilevel`` is the level loop over it with the device path's
termination rules.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.coarsen import (
    _round_up, coarse_map, contract_edges, heavy_edge_matching,
    twohop_matching,
)
from repro.core.graph import Graph


def coarsen_once(
    g: Graph,
    twohop_threshold: float = 0.25,
    mm_max_degree: int = 64,
    seed: int = 0,
) -> tuple[Graph, jnp.ndarray]:
    """One coarsening level, host-repack path.

    Returns (coarse graph (tight arrays), cmap).
    """
    match = heavy_edge_matching(g, seed=seed)
    n = int(g.n)
    unmatched = int(
        np.asarray(jnp.sum(((match < 0) & g.vertex_mask()).astype(jnp.int32)))
    )
    # float32 on purpose: bit-identical to coarsen_level's on-device trigger
    # (a float64 division here could disagree near the threshold for huge n)
    frac = np.float32(unmatched) / np.float32(max(n, 1))
    if frac > np.float32(twohop_threshold):
        match = twohop_matching(g, match, mm_max_degree, seed)
    cmap, nc_dev = coarse_map(g, match)
    cu_run, cv_run, w_run, run_valid, n_runs_dev, vwgt_c = contract_edges(g, cmap)
    nc = int(nc_dev)
    n_runs = int(n_runs_dev)
    # host repack into tight padded arrays
    cu = np.asarray(cu_run)[:n_runs]
    cv = np.asarray(cv_run)[:n_runs]
    w = np.asarray(w_run)[:n_runs]
    vw = np.asarray(vwgt_c)[:nc]
    n_max_c = _round_up(max(nc, 1))
    m_max_c = _round_up(max(n_runs, 1))
    xadj = np.zeros(n_max_c + 1, dtype=np.int64)
    np.add.at(xadj, cu + 1, 1)
    xadj = np.cumsum(xadj)
    xadj_p = np.full(n_max_c + 1, n_runs, dtype=np.int32)
    xadj_p[: nc + 1] = xadj[: nc + 1]
    adjncy_p = np.zeros(m_max_c, dtype=np.int32)
    adjncy_p[:n_runs] = cv
    adjwgt_p = np.zeros(m_max_c, dtype=np.int32)
    adjwgt_p[:n_runs] = w
    vwgt_p = np.zeros(n_max_c, dtype=np.int32)
    vwgt_p[:nc] = vw
    esrc_p = np.zeros(m_max_c, dtype=np.int32)
    esrc_p[:n_runs] = cu
    gc = Graph(
        xadj=jnp.asarray(xadj_p),
        adjncy=jnp.asarray(adjncy_p),
        adjwgt=jnp.asarray(adjwgt_p),
        vwgt=jnp.asarray(vwgt_p),
        esrc=jnp.asarray(esrc_p),
        n=jnp.asarray(nc, dtype=jnp.int32),
        m=jnp.asarray(n_runs, dtype=jnp.int32),
    )
    return gc, cmap


def multilevel(g: Graph, coarse_target: int = 4096, max_levels: int = 40,
               stall_ratio: float = 0.95, seed: int = 0) -> list:
    """``[(graph, cmap), ...]`` finest first, the coarsest's cmap None:
    ``coarsen_once`` per level, stopping where the device path stops."""
    levels = [(g, None)]
    for lvl in range(max_levels):
        cur = levels[-1][0]
        if int(cur.n) <= coarse_target:
            break
        gc, cmap = coarsen_once(cur, seed=seed + lvl)
        if int(gc.n) > stall_ratio * int(cur.n):
            break
        levels[-1] = (cur, cmap)
        levels.append((gc, None))
    return levels
