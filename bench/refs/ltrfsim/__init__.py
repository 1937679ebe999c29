"""Reference for the ``ltrf-dwm-sm`` configuration: the seed simulator and
the compiler passes it runs, copied from the program so that the check
imports nothing of the code under test.

``simulate(spec, cfg)`` builds the kernel from its synthesis spec and runs
the reference engine; ``counters(result)`` gives every counter and the
whole cycle breakdown as one flat dict, the form the check compares.
"""
from .golden import golden_simulate
from .model import SimBudgetExceeded, SimConfig, SimResult
from .workloads import Workload, build_workload

__all__ = ["SimBudgetExceeded", "SimConfig", "SimResult", "Workload",
           "build_workload", "golden_simulate"]
