"""``correct`` on the CPU at tiny sizes: a sound run passes, and the
control and every planted fault that a cell can have make it false.

The runs go through the harness with its look for a chip skipped
(``bench_tiny.run_cell``); each fault breaks the program underneath it
(each driver's ``FAULTS``). Limits are the real cells' limits.
"""
from __future__ import annotations

import pytest

from bench_tiny import run_cell, tiny_root

from bench import harness, readings

CELLS = {"trainer": "train-tiny", "protocol": "protocol-tiny"}
FAULTS = {d: harness.driver(d).FAULTS for d in CELLS}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("driver", sorted(CELLS))
def test_sound_run_is_correct(root, driver):
    out = run_cell(root, CELLS[driver])
    assert out["correct"] is True, out["checks"]


@pytest.mark.parametrize("driver,fault", [
    (d, f) for d in sorted(FAULTS) for f in sorted(FAULTS[d])])
def test_planted_fault_is_not_correct(root, driver, fault):
    with FAULTS[driver][fault]():
        out = run_cell(root, CELLS[driver])
    assert out["correct"] is False, (fault, out["checks"])


def test_control_fails_the_training_cell(root):
    import jax

    wl = harness.workload("train-tiny", root / "bench")
    for rec in readings.readings("train-tiny", [5, 3000000007], "control",
                                 devices=jax.devices(),
                                 bench=root / "bench"):
        assert any(v > wl["limits"][k] for k, v in rec["numbers"].items()), \
            rec


def test_control_fails_the_protocol_cell(root):
    import jax

    wl = harness.workload("protocol-tiny", root / "bench")
    for rec in readings.readings("protocol-tiny", [5, 3000000007],
                                 "control", devices=jax.devices(),
                                 bench=root / "bench"):
        assert any(v > wl["limits"][k] for k, v in rec["numbers"].items()), \
            rec
