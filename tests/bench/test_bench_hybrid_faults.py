"""The hybrid train cell on the CPU at a tiny size: each of the cell's
faults comes out not correct."""
from __future__ import annotations

import io

import pytest
from hybrid_tiny import SEEDS, tiny_hybrid_cell

from bench import faults_hybrid, harness


@pytest.fixture
def tiny_hybrid():
    return tiny_hybrid_cell()


def _run(cell, cpu, seed=SEEDS[0]):
    return harness.run_cell(cell, seed, 0.5, False, cpu, log=io.StringIO())


@pytest.mark.parametrize("fault", sorted(faults_hybrid.FAULTS))
def test_each_fault_is_not_correct(tiny_hybrid, cpu, monkeypatch, fault):
    faults_hybrid.plant(fault, monkeypatch.setattr)
    res = _run(tiny_hybrid, cpu)
    assert not res["correct"], res["compared"]
