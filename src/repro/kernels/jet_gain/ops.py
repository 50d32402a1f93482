"""jit'd public wrapper for the jet_gain kernel.

Chooses the Pallas kernel (interpret=True on CPU, compiled on TPU) and
provides the CSR->ELL conversion used by the refinement layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.jet_gain.jet_gain import jet_gain_pallas
from repro.kernels.jet_gain.ref import jet_gain_ref


# v5e default scoped VMEM.  Per grid step the kernel holds its two int32
# (block_n, D) inputs double-buffered plus two (block_n, D) temporaries of
# the k-sweep (the part-compare mask and the masked weights), with D padded
# to the 128-lane tile.
_VMEM_BYTES = 16 * 1024 * 1024
_TILES_PER_ROW = 2 * 2 + 2


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def block_rows(d: int) -> int:
    """Rows per kernel grid step for ELL width ``d``: the largest multiple
    of 8, at most 256, whose VMEM tiles fit the scoped VMEM."""
    d_lanes = -(-d // 128) * 128
    rows = min(256, _VMEM_BYTES // (_TILES_PER_ROW * d_lanes * 4) // 8 * 8)
    if rows < 8:
        raise ValueError(
            f"ELL width {d} is too wide for the jet_gain kernel: even 8 rows "
            f"of its VMEM tiles exceed {_VMEM_BYTES >> 20} MiB"
        )
    return rows


def csr_to_ell(g, max_degree: int | None = None):
    """Pad CSR adjacency to (N, D). Returns (nbr (N,D), wgt (N,D)).

    Slots beyond a vertex's degree have nbr == N (ghost) and weight 0.
    """
    deg = jnp.asarray(g.degrees())
    d = int(max_degree) if max_degree else int(jnp.max(deg))
    n = g.n_max
    slots = jnp.arange(d, dtype=jnp.int32)
    eidx = g.xadj[:-1, None] + slots[None, :]
    valid = slots[None, :] < deg[:, None]
    eidx = jnp.clip(eidx, 0, g.m_max - 1)
    nbr = jnp.where(valid, g.adjncy[eidx], n)
    wgt = jnp.where(valid, g.adjwgt[eidx], 0)
    return nbr, wgt


def lookup_nbr_parts(nbr, parts, k: int):
    """(N, D) neighbor part ids from a parts vector; ghost slots map to k."""
    parts_ext = jnp.concatenate([parts, jnp.array([k], jnp.int32)])
    nbr_parts = parts_ext[jnp.clip(nbr, 0, parts.shape[0])].astype(jnp.int32)
    return jnp.where(nbr >= parts.shape[0], k, nbr_parts)


def update_nbr_parts(nbr, nbr_parts, move, dest, k: int):
    """Incrementally rewrite slots whose neighbor moved (paper Alg 4.4).

    Elementwise over the (N, D) ELL tile — no gather of the full parts
    vector, so the maintained state is the only connectivity read.
    """
    move_ext = jnp.concatenate([move, jnp.zeros((1,), bool)])
    dest_ext = jnp.concatenate(
        [dest.astype(jnp.int32), jnp.array([k], jnp.int32)]
    )
    idx = jnp.clip(nbr, 0, move.shape[0])
    return jnp.where(move_ext[idx], dest_ext[idx], nbr_parts)


def ell_to_matrix(nbr_parts, wgt, k: int):
    """(N, k+1) dense connectivity matrix from maintained ELL state.

    Used by the (rare) rebalance iterations, which need valid-destination
    queries the fused kernel does not answer.
    """
    n, d = nbr_parts.shape
    rows = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], (n, d))
    mat = jnp.zeros((n, k + 1), jnp.int32)
    return mat.at[rows, nbr_parts].add(wgt)


def jet_gain_from_parts(nbr_parts, wgt, parts, k: int, use_pallas=None):
    """Fused conn_self / best_part / best_conn from precomputed neighbor
    parts — the entry point for the stateful ELL backend.

    ``use_pallas=None`` auto-selects: the compiled kernel on TPU, the
    bit-identical pure-jnp k-sweep elsewhere (interpret-mode Pallas is for
    kernel validation, not production CPU runs).  The row tile comes from
    the ELL width (:func:`block_rows`).
    """
    n, d = nbr_parts.shape
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not use_pallas:
        return jet_gain_ref(nbr_parts, wgt, parts, k)
    block_n = block_rows(d)
    pad = (-n) % block_n
    if pad:
        nbr_parts = jnp.pad(nbr_parts, ((0, pad), (0, 0)), constant_values=k)
        wgt = jnp.pad(wgt, ((0, pad), (0, 0)))
        parts = jnp.pad(parts, (0, pad), constant_values=k)
    cs, bp, bc = jet_gain_pallas(
        nbr_parts, wgt, parts, k, block_n=block_n, interpret=not _on_tpu()
    )
    return cs[:n], bp[:n], bc[:n]


def jet_gain(nbr, wgt, parts, k: int, use_pallas=None):
    """Fused conn_self / best_part / best_conn (see jet_gain.py).

    ``nbr`` holds neighbor ids; part ids are looked up here (outside the
    kernel — TPU kernels avoid arbitrary gathers) and the padded ghost id N
    maps to ghost part k.
    """
    nbr_parts = lookup_nbr_parts(nbr, parts, k)
    return jet_gain_from_parts(nbr_parts, wgt, parts, k,
                               use_pallas=use_pallas)
