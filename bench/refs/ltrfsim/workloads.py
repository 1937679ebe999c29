"""Kernels of the suite, built from their synthesis specs.

The ``Workload`` record is copied from the program's ``workloads/suite.py``;
the specs themselves are data in the configuration's file.
"""
from __future__ import annotations

from dataclasses import dataclass

from .ir import Program
from .synth import SynthSpec, synthesize


@dataclass(frozen=True)
class Workload:
    name: str
    program: Program
    trips: dict[str, int]
    register_sensitive: bool
    regs_per_thread: int  # compiled (maxregcount) register demand
    suite: str
    l1_hit: float = 0.85  # data-cache hit rate


def build_workload(spec: dict) -> Workload:
    """One kernel from its entry in the configuration's ``kernels`` list."""
    kw = dict(spec)
    suite, sensitive = kw.pop("suite"), kw.pop("register_sensitive")
    if "trips" in kw:
        kw["trips"] = tuple(kw["trips"])
    s = SynthSpec(**kw)
    prog, trips = synthesize(s)
    return Workload(name=s.name, program=prog, trips=trips,
                    register_sensitive=sensitive,
                    regs_per_thread=s.regs_per_thread, suite=suite,
                    l1_hit=s.l1_hit)
