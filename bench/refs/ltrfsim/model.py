"""The simulator's configuration and result types, as the reference uses them.

Copied from the program's ``sim/engine.py`` (SimConfig, SimResult,
SimBudgetExceeded, the warp record and its status codes) so that the
reference imports nothing of the program under test.
"""
from __future__ import annotations

from dataclasses import dataclass, field

from .ir import Instr


@dataclass(frozen=True)
class SimConfig:
    design: str = "BL"
    mrf_latency_mult: float = 1.0
    rf_size_kb: int = 256          # main register file capacity
    rfc_size_kb: int = 16          # register file cache capacity
    add_rfc_to_main: bool = False  # §6: BL gets the RFC's 16KB added to MRF
    num_warps: int = 64            # total warp contexts worth of work
    active_slots: int = 8
    issue_width: int = 3
    num_banks: int = 16
    interval_cap: int = 16         # registers allowed per register-interval
    base_rf_cycles: int = 4        # MRF bank access at 1x
    rfc_cycles: int = 1
    alu_cycles: int = 3
    mem_cycles: int = 380          # L1-miss latency (average)
    l1_cycles: int = 8             # L1-hit latency
    l1_hit_rate: float = 0.85
    num_collectors: int = 32       # operand collectors shared by the SM
    xbar_regs_per_cycle: int = 8   # prefetch crossbar bandwidth (1024-bit)
    max_inflight_prefetch: int = 12
    dram_interval: int = 4         # cycles between DRAM line services (bw/SM)
    seed: int = 0
    max_cycles: int = 0            # cycle-budget watchdog: a simulation that
                                   # passes this cycle raises SimBudgetExceeded
                                   # (0 = unlimited).  Never changes the
                                   # counters of a run that completes, so the
                                   # sweep cache (serving.sweep.sim_key)
                                   # deliberately excludes it.
    scheduler: str = "two_level"   # warp-scheduler policy (SCHEDULERS)
    num_sms: int = 1               # SMs on the chip; >1 via repro.sim.gpu
    mem_partitions: int = 0        # DRAM partitions feeding the SMs
                                   # (0 = one per SM, i.e. uncontended)
    bank_model: str = "none"       # RF bank arbitration (BANK_MODELS)
    renumber: str = "icg"          # renumbering ablation axis (RENUMBER_MODES)
    interval_strategy: str = "paper"  # interval formation (INTERVAL_STRATEGIES)
    trace: bool = False            # opt-in per-warp event tracer (repro.obs.
                                   # trace): records issue/stall/prefetch/swap
                                   # events on Simulator.trace for Chrome
                                   # trace-event export.  Pure observation —
                                   # never changes counters — so the sweep
                                   # cache (serving.sweep.sim_key) excludes it
                                   # like max_cycles.

    @property
    def mrf_cycles(self) -> float:
        return self.base_rf_cycles * self.mrf_latency_mult

    @property
    def rfc_entries(self) -> int:
        return self.rfc_size_kb * 1024 // 128  # 1024-bit warp registers

    @property
    def rfc_entries_per_warp(self) -> int:
        """Register-cache entries one active warp can claim — the bound the
        ``capacity`` interval strategy clamps working sets to."""
        return self.rfc_entries // max(self.active_slots, 1)


@dataclass
class SimResult:
    design: str
    workload: str
    cycles: int
    instructions: int
    resident_warps: int
    rfc_hits: int = 0
    rfc_accesses: int = 0
    mrf_accesses: int = 0
    prefetch_ops: int = 0
    prefetch_cycles: int = 0
    prefetch_stall_cycles: int = 0  # cycles warps spent blocked on an
                                    # in-flight interval prefetch (queueing
                                    # for a prefetch slot + the fetch itself)
    writeback_regs: int = 0
    activations: int = 0
    bank_conflicts: int = 0        # extra serialization rounds (arbitrated)
    bank_conflict_cycles: int = 0  # latency cycles those rounds added
    cycle_breakdown: dict[str, int] = field(default_factory=dict)
    # ^ where every cycle went: one entry per repro.obs.attribution category
    #   (issue/alu_dep/mem_stall/prefetch_stall/bank_conflict/scheduler_idle/
    #   drain); both engines enforce sum(cycle_breakdown.values()) == cycles.

    @property
    def ipc(self) -> float:
        return self.instructions / max(self.cycles, 1)

    @property
    def hit_rate(self) -> float:
        return self.rfc_hits / max(self.rfc_accesses, 1)

    @property
    def bank_conflict_rate(self) -> float:
        """Extra bank-serialization rounds per retired instruction."""
        return self.bank_conflicts / max(self.instructions, 1)


class SimBudgetExceeded(RuntimeError):
    """A simulation ran past its ``SimConfig.max_cycles`` budget.

    Structured (design/workload/budget/cycles attributes) and raised at the
    same simulated cycle by both the fast engine and the golden oracle (the
    watchdog sits at the identical point of both run loops), so the sweep
    service can classify runaway configs deterministically.  Args are passed
    positionally to ``RuntimeError`` so the exception survives pickling
    across process-pool workers."""

    def __init__(self, design: str, workload: str,
                 budget: int, cycles: int) -> None:
        super().__init__(design, workload, budget, cycles)
        self.design = design
        self.workload = workload
        self.budget = budget
        self.cycles = cycles

    def __str__(self) -> str:
        return (f"{self.workload}/{self.design}: simulation exceeded "
                f"max_cycles={self.budget} (reached cycle {self.cycles})")


ACTIVE, INACTIVE_READY, INACTIVE_WAIT, PREFETCH, DONE = range(5)


@dataclass
class _Warp:
    wid: int
    block: str
    idx: int = 0
    status: int = INACTIVE_READY
    ready_at: int = 0
    reg_ready: dict[int, float] = field(default_factory=dict)
    reg_from_mem: dict[int, bool] = field(default_factory=dict)
    pred_ready: dict[int, float] = field(default_factory=dict)
    loop_counters: dict[str, int] = field(default_factory=dict)
    diamond_visits: dict[tuple[str, int], int] = field(default_factory=dict)
    interval: int = -1
    issued: int = 0
    mem_ops: int = 0
    # Operand-readiness cache: a warp's register/predicate state only changes
    # when IT issues (or its prefetch lands), so the current instruction's
    # readiness is computed once per issue instead of once per scheduler scan.
    ver: int = 0                   # bumped whenever reg/pred state or PC moves
    c_ver: int = -1                # ver the cache below was computed at
    c_ins: Instr | None = None     # current instruction
    c_maxrdy: float = 0.0          # cycle at which all operands are ready
    c_times: tuple = ()            # pending operand-ready times (for events)
    c_mem: tuple = ()              # pending times of memory-produced operands

