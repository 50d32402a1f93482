"""Compile-only checks of the jet_gain kernel for a described TPU v5e.

Nothing runs: each case compiles the Pallas kernel for one chip of a
described ``v5e:2x2`` topology, so the TPU compiler refuses here what it
would refuse on the chip (VMEM tiles, HBM, tiling), and asserts that the
compiled program holds the kernel (``tpu_custom_call``) rather than the
jnp reference or the interpreter.  The topology is described inside a
fixture, never at import, and the persistent compile cache is off around
these compiles: what they write could not be read back without a chip.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.jet_gain import ops
from repro.kernels.jet_gain.jet_gain import jet_gain_pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _pallas(d, k):
    return lambda a, w, p: jet_gain_pallas(a, w, p, k,
                                           block_n=ops.block_rows(d),
                                           interpret=False)


def _from_parts(d, k):
    return lambda a, w, p: ops.jet_gain_from_parts(a, w, p, k,
                                                   use_pallas=True)


@pytest.mark.parametrize("entry,n,d,k", [
    # the finest level of a 1024x1024 grid at k=64 (mesh degree <= 8)
    (_pallas, 1 << 20, 8, 64),
    # the suite's rmat graph: maximum degree 1,492
    (_pallas, 8192, 1492, 8),
    # a power-law hub row of width 20,000: only a derived block_n fits VMEM.
    # At n=2^16 this width exceeds HBM instead (two 4.9 GB inputs and their
    # copies: 19.6 of 15.75 GB), so the case keeps n at 8,192
    (_from_parts, 8192, 20000, 8),
], ids=["mesh_1M_k64", "rmat_d1492", "hub_d20000"])
def test_jet_gain_compiles_for_v5e(one_chip, monkeypatch, entry, n, d, k):
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    compiled = jax.jit(entry(d, k)).lower(
        arg((n, d)), arg((n, d)), arg((n,))).compile()
    assert "tpu_custom_call" in compiled.as_text()
