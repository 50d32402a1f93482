"""``chip_smoke.py`` phases A-D on the CPU at tiny sizes.

The script's phases run on the chip at full size; here the same functions
run on small graphs (with a small coarsening target, so every phase still
walks several levels), which catches wrong paths, arguments and checks
without chip time.  The TPU check lives only in ``main()``, which must
refuse this machine.
"""
import json
from dataclasses import replace

import numpy as np
import pytest

import chip_smoke as cs
from repro.core.partition import PartitionConfig
from repro.data import graphs as gen

BASE = PartitionConfig(coarse_target=64)


def _families():
    # one capacity bucket, 4 and 3 levels: as on the chip, fleet and server
    # lanes stop coarsening at different levels
    return {"grid:15": gen.grid2d(15, 15), "grid:14": gen.grid2d(14, 14)}


def test_one_shot(capsys):
    g = gen.grid2d(32, 32)
    dense = cs.phase_one_shot(g, 4, strip_cut=3 * 32, base=BASE)
    assert dense.levels > 2
    assert json.loads(capsys.readouterr().out)["cut"] == dense.cut


def test_ell_checks_all_but_the_kernel(capsys):
    # every check up to the kernel's presence passes; that one only a TPU
    # compile can pass, since the CPU runs the jnp reference
    g = gen.grid2d(24, 24)
    dense = cs.phase_one_shot(g, 4, strip_cut=3 * 24, base=BASE)
    with pytest.raises(cs.SmokeFailure, match="without the Pallas kernel"):
        cs.phase_ell(g, dense, cs.run_ell(g, 4, base=BASE))


def test_ell_must_equal_dense():
    g = gen.grid2d(24, 24)
    other = cs.phase_one_shot(g, 4, strip_cut=3 * 24,
                              base=replace(BASE, seed=1))
    with pytest.raises(cs.SmokeFailure, match="parts differ"):
        cs.phase_ell(g, other, cs.run_ell(g, 4, base=BASE))


def test_trials_and_fleet():
    res, fleet = cs.phase_trials_fleet(_families(), k=4, trials=2, base=BASE)
    assert res.trials == 2 and len(fleet.results) == 2
    assert [r.levels for r in fleet.results] == [4, 3]


def test_served():
    families = _families()
    warmed = cs.warm_server(families, ks=(2, 4), lanes=2, base=BASE)
    results = cs.phase_served(families, ks=(2, 4), copies=2, warmed=warmed)
    assert len(results) == 8


def test_run_all_side_by_side(capsys):
    # A, B and C/D's warm-up in threads, then D's replay and B's checks;
    # on the CPU only B's kernel check can fail, and it runs last
    with pytest.raises(cs.SmokeFailure, match="without the Pallas kernel"):
        cs.run_all(gen.grid2d(24, 24), 4, 3 * 24, _families(), trials=2,
                   ks=(2, 4), lanes=2, copies=1, base=BASE)
    phases = [json.loads(line)["phase"]
              for line in capsys.readouterr().out.splitlines()]
    assert sorted(phases) == ["A_one_shot_dense", "C_trials_fleet",
                              "D_served"]
    assert phases[-1] == "D_served"


def test_check_partition_catches_errors():
    g = gen.grid2d(8, 8)
    csr = cs._host_csr(g)
    parts = np.repeat(np.arange(2), 32)  # two 4x8 halves: cut 8
    cs.check_partition(csr, parts, 2, 0.03, 8)
    with pytest.raises(cs.SmokeFailure, match="numpy cut"):
        cs.check_partition(csr, parts, 2, 0.03, 7)
    with pytest.raises(cs.SmokeFailure, match="max part"):
        cs.check_partition(csr, np.repeat(np.arange(2), [40, 24]), 2, 0.03,
                           8)
    with pytest.raises(cs.SmokeFailure, match="part ids"):
        cs.check_partition(csr, np.full(64, 2), 2, 0.03, 0)


def test_main_refuses_without_tpu(capsys):
    assert cs.main() == 1
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "no TPU" in captured.err
