"""Vectorized batch simulation engine: many independent sims in lockstep.

The scalar engines (`engine.py` event-heap, `golden.py` oracle) spend ~10us
of Python per retired instruction — the bottleneck for every sweep the
orchestrator runs.  This module restructures the *same* discrete-event tick
into a masked, functional step over arrays indexed ``(lane, warp)``: one
step advances a whole batch of independent simulations (per-SM shards,
sweep job lists) together, and the entire run loop executes as a single
jitted ``lax.while_loop`` — no Python in the hot path at all.

Correctness contract (same discipline as the event-heap engine, PR 1):
``golden.py`` stays frozen, and for every supported config the batch engine
produces **bit-identical** `SimResult`s — every counter and the full
`cycle_breakdown` — to the golden/event engines.  The differential fuzz
harness (`tests/test_sim_fuzz.py`) extends to batch-vs-golden, and the
Listing-1 pins go through the batch path too.

Supported domain (`batch_supported`): the paper's two-level scheduler,
``bank_model="none"``, untraced, single-SM configs — i.e. exactly the
tracked fast-path sweep.  Any design, any interval strategy, any renumber
mode (those are compile-side: the batch engine consumes the same
`CompiledPlan` the event engine does).  Unsupported configs transparently
fall back to the scalar event engine, job by job.

Numeric discipline: every float the scalar engines touch is a Python f64,
and the batch engine performs the *identical* operations in the *identical*
order (token-bucket refills, ``int()`` truncations, DRAM jitter hashes).  It
keeps each float as its IEEE-754 bit pattern in an int64 and adds, subtracts
and truncates with exact integer arithmetic (`f64bits`), so the results are
bit-equal to Python's on every backend — a TPU's emulated f64 is not.
Products and quotients of configuration constants (refill amounts, jitter
latencies, prefetch latencies, the L1-hit threshold) are tabulated on the
host in numpy f64.  The loop holds no floating-point value at all; it runs
under ``jax.enable_x64`` for its int64 state.

Why lockstep is exact: the scalar tick's sequential sub-loops collapse.
* The round-robin issue scan is rank arithmetic: the chosen warp is the
  minimum ``(pos - cycle % n) mod n`` among ready active slots, and golden's
  DONE-marking / mem-stall recording applies exactly to the ranks it
  scanned (``rank <= chosen_rank``).
* Deactivation order is irrelevant: the scalar loop's interleaved
  ``deactivate -> activate`` calls never change which warps activate (the
  READY pool only shrinks, admitted wids only increase), so one vectorized
  deactivate + one greedy lowest-wid-first activation phase is equivalent.
* The RFC's OrderedDict LRU is a (key, stamp) array pair: move-to-end and
  insert are monotonic stamps, eviction is argmin-stamp — multiset-equal to
  ``popitem(last=False)``.
* The collector / prefetch-slot min-heaps are argmin-replace on arrays
  (multiset equality with both the heap and golden's first-argmin scan).

BATCH_REV 2 (fused tick): on XLA CPU every scatter/gather dispatch costs
microseconds regardless of size, so REV 1's ~60 per-tick `.at[...]` updates
and four full `(lane, slot, src)` readiness scans dominated the wall clock.
REV 2 restructures the step around struct-of-arrays *families* and a
per-warp readiness cache (the scalar engines' `_refresh_ready` memo,
vectorized):

* ``wf``  (K, W, 6+loops+dias) — status/pc/iv/ready_at/issued/mem_ops plus
  the loop/diamond branch counters: one row gather + one row scatter per
  selected warp instead of one dispatch per field.
* ``rv``/``rm``  (K, W, regs+preds) — register/predicate ready-times and
  the from-mem flag, as two planes with the register axis minor, so every
  reader and writer wants one layout and the compiled loop never copies
  either plane into another (a trailing (time, flag) pair axis made a
  TPU relayout both ways on every issue slot).  The dst+pred writeback is
  one scatter into each plane with the same indices (out-of-bounds
  indices drop masked writes, no read-modify-write).
* ``cf``  (K, W, 2+S+PS) — cached max/mem-max/per-operand ready times of
  each warp's *current* instruction, refreshed only when that warp's state
  changes (its own issue or prefetch, exactly the scalar cache-invalidation
  sites).  Scheduler scans and the event-horizon search become elementwise
  reads of this plane — no per-slot 3D gathers.
* ``rc``  (K, E, 2) — RFC (key, stamp) rows; the LRU move-to-end phase is
  one scatter-max (stamps are monotone, so duplicate-key last-write ==
  max), only the insert/evict phase stays a short sequential loop.
* Active-list compaction is a cumsum + dropped-out-of-bounds scatter
  instead of a stable argsort.

Event-horizon time skipping (REV 1's ``delta`` jump) is unchanged: on a
zero-issue tick every lane advances straight to its next event — the min
over collector frees, warp wake-ups, and pending operand times, exactly
the scalar `_next_event` — with the skipped cycles charged to the same
`cycle_breakdown` category, so sum==cycles and bit-identity survive.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass
from typing import Sequence

import numpy as np

from repro.core.pipeline import parse_interval_strategy
from repro.core.plan_cache import compile_for_sim
from repro.obs.attribution import CYCLE_CATEGORIES, check_breakdown, new_breakdown
from repro.workloads.suite import Workload

from .engine import (
    ACTIVE, DONE, INACTIVE_READY, INACTIVE_WAIT, PREFETCH,
    _CACHED_DESIGNS, _EDGE_PREFETCH,
    SimBudgetExceeded, SimConfig, SimResult, simulate,
)

# Bump with ENGINE_REV-style discipline if batch-engine behavior ever
# intentionally diverges (it must not: bit-identity is the contract).
# REV 2: fused-family tick (struct-of-arrays state, cached readiness
# planes, one-scatter LRU hit phase, cumsum compaction) — bit-identical
# to REV 1 by construction, ~O(families) dispatches per tick.
BATCH_REV = 2

# Opcode kinds in the flat-PC instruction encoding.
_OP_OTHER, _OP_BRA, _OP_EXIT, _OP_SET, _OP_LD = range(5)

_BIG = np.int64(1) << 60          # sentinel "never" timestamp / rank
_GUARD = 8_000_000                # same wedge guard as the scalar engines

_CAT_INDEX = {c: i for i, c in enumerate(CYCLE_CATEGORIES)}

# warp-family (``wf``) fixed field columns; loop counters start at
# _F_LC, diamond counters at _F_LC + n_loop_slots + 1 (chunk-dependent).
F_ST, F_PC, F_IV, F_RA, F_IS, F_MO = range(6)
_F_LC = 6

# packed per-pc metadata (``meta``) fixed columns; the variable-width
# src/psrc/dst/acc column groups follow (see `_meta_cols`).
M_KIND, M_NACC, M_PDST, M_TGT, M_TRIPS, M_LSL, M_DSL, M_IVPC = range(8)


def _meta_cols(S: int, PS: int, DD: int):
    """Column offsets of the variable-width groups in the meta table."""
    m_s = 8
    m_ps = m_s + S
    m_d = m_ps + PS
    m_g = m_d + DD
    return m_s, m_ps, m_d, m_g


def _jax():
    """Import jax lazily so jax-free consumers never pay for it."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    return jax, jnp, lax


def batch_supported(cfg: SimConfig) -> bool:
    """Can this config run on the vectorized fast path?

    The batch engine implements the paper's two-level scheduler with no
    bank arbitration and no tracer — the golden-pinned domain, and exactly
    what the tracked sweep runs.  Everything compile-side (design, interval
    strategy, renumbering) is supported because the plan is shared.
    """
    return (cfg.scheduler == "two_level"
            and cfg.bank_model == "none"
            and not cfg.trace
            and cfg.num_sms == 1)


# --------------------------------------------------------------------------
# Static per-lane encoding: flat-PC program tables + interval tables.
# --------------------------------------------------------------------------

@dataclass
class _PlanCode:
    """Flat-PC encoding of one compiled plan (+ workload trip counts).

    All arrays are numpy; shared read-only across lanes and batches.
    ``P`` rows of instruction metadata plus one sentinel row at index P
    (the "past the end" position the clamped pc gather lands on).
    """
    n_pc: int                 # instruction count (flat program length)
    op_kind: np.ndarray       # (P+1,) int32
    srcs: np.ndarray          # (P+1, S) int32, sentinel = n_regs
    psrcs: np.ndarray         # (P+1, PS) int32, sentinel = n_preds
    dsts: np.ndarray          # (P+1, D) int32, sentinel = n_regs
    pdst: np.ndarray          # (P+1,) int32, sentinel = n_preds
    n_acc: np.ndarray         # (P+1,) int32
    acc_regs: np.ndarray      # (P+1, G) int32 srcs+dsts in order, -1 pad
    target: np.ndarray        # (P+1,) int32 flat target pc (bra)
    trips: np.ndarray         # (P+1,) int32 loop trip count (0 if not loop)
    loop_slot: np.ndarray     # (P+1,) int32, sentinel = n_loops
    dia_slot: np.ndarray      # (P+1,) int32, sentinel = n_dias
    interval_of_pc: np.ndarray  # (P+1,) int32, -1 = none
    n_regs: int
    n_preds: int
    n_loops: int
    n_dias: int
    # interval tables, indexed by interval id (row IV = "no interval")
    iv_rounds: np.ndarray     # (IV+1,) int32
    iv_nfetch: np.ndarray     # (IV+1,) int32 effective fetch count
    iv_nwb: np.ndarray        # (IV+1,) int32 writeback regs on deactivation
    iv_has_op: np.ndarray     # (IV+1,) bool  prefetch actually fires
    iv_regs: np.ndarray       # (IV+1, GV) int32 FULL bitvector, -1 pad
    n_ivs: int


_ENCODE_MEMO: dict = {}


def _encode_plan(workload: Workload, cfg: SimConfig) -> _PlanCode:
    plan = compile_for_sim(workload.program, cfg.design,
                           cfg.interval_cap, cfg.num_banks,
                           renumber=cfg.renumber,
                           interval_strategy=cfg.interval_strategy,
                           rfc_per_warp=cfg.rfc_entries_per_warp)
    trips_key = tuple(sorted(workload.trips.items()))
    key = (id(plan), cfg.design == "LTRF_plus", trips_key)
    hit = _ENCODE_MEMO.get(key)
    if hit is not None:
        return hit[0]

    prog = plan.prog
    is_plus = cfg.design == "LTRF_plus"
    flat: list[tuple[str, int, object]] = []     # (label, idx, ins)
    block_first: dict[str, int] = {}             # label -> flat pc of first
    for label in prog.order:
        bb = prog.blocks[label]
        block_first[label] = len(flat)           # even for empty blocks:
        for i, ins in enumerate(bb.instrs):      # first instr at-or-after
            flat.append((label, i, ins))
    P = len(flat)

    def target_pc(label: str) -> int:
        # flat pc of the first instruction in-or-after `label` (the scalar
        # engines' lazy block walk); past-the-end collapses to P.
        start = block_first.get(label)
        return P if start is None else start

    n_regs = 0
    n_preds = 0
    max_s = 1
    max_ps = 1
    max_d = 1
    for _, _, ins in flat:
        for r in tuple(ins.srcs) + tuple(ins.dsts):
            n_regs = max(n_regs, r + 1)
        for p in ins.psrcs:
            n_preds = max(n_preds, p + 1)
        if ins.pdst is not None:
            n_preds = max(n_preds, ins.pdst + 1)
        max_s = max(max_s, len(ins.srcs))
        max_ps = max(max_ps, len(ins.psrcs))
        max_d = max(max_d, len(ins.dsts))
    for op in plan.pf_ops.values():
        for r in op.bitvector:
            n_regs = max(n_regs, r + 1)

    # loop slots: one counter per trip-count label (shared across branch
    # sites, like the scalar `loop_counters[target]`); diamond slots: one
    # visit counter per conditional non-loop branch *site* (flat pc).
    loop_labels: dict[str, int] = {}
    n_dias = 0

    max_g = max(1, max_s + max_d)
    op_kind = np.zeros(P + 1, np.int32)
    srcs = np.full((P + 1, max_s), n_regs, np.int32)
    psrcs = np.full((P + 1, max_ps), n_preds, np.int32)
    dsts = np.full((P + 1, max_d), n_regs, np.int32)
    pdst = np.full(P + 1, n_preds, np.int32)
    n_acc = np.zeros(P + 1, np.int32)
    acc_regs = np.full((P + 1, max_g), -1, np.int32)
    target = np.zeros(P + 1, np.int32)
    trips = np.zeros(P + 1, np.int32)
    interval_of_pc = np.full(P + 1, -1, np.int32)

    loop_slot_rows = np.zeros(P + 1, np.int32)
    dia_slot_rows = np.zeros(P + 1, np.int32)
    kinds = {"bra": _OP_BRA, "exit": _OP_EXIT, "set": _OP_SET, "ld": _OP_LD}

    for pc, (label, idx, ins) in enumerate(flat):
        interval_of_pc[pc] = plan.block_interval.get(label, -1)
        op_kind[pc] = kinds.get(ins.op, _OP_OTHER)
        for j, r in enumerate(ins.srcs):
            srcs[pc, j] = r
        for j, p in enumerate(ins.psrcs):
            psrcs[pc, j] = p
        for j, r in enumerate(ins.dsts):
            dsts[pc, j] = r
        if ins.pdst is not None:
            pdst[pc] = ins.pdst
        regs = tuple(ins.srcs) + tuple(ins.dsts)
        n_acc[pc] = len(regs)
        for j, r in enumerate(regs):
            acc_regs[pc, j] = r
        if ins.op == "bra":
            target[pc] = target_pc(ins.target)
            t = workload.trips.get(ins.target)
            if ins.psrcs and t is not None:
                trips[pc] = t
                slot = loop_labels.setdefault(ins.target, len(loop_labels))
                loop_slot_rows[pc] = slot + 1  # 0 = "not a loop" below
            elif ins.psrcs:
                n_dias += 1
                dia_slot_rows[pc] = n_dias     # 0 = "not a diamond"
    # the lazy block walk parks a finished warp on the LAST block in order,
    # so the sentinel row's interval is that block's (activation prefetch
    # of an at-end warp — unreachable in practice, encoded for fidelity).
    interval_of_pc[P] = plan.block_interval.get(prog.order[-1], -1) \
        if prog.order else -1
    op_kind[P] = _OP_EXIT

    n_loops = len(loop_labels)
    loop_slot = np.where(loop_slot_rows > 0, loop_slot_rows - 1,
                         n_loops).astype(np.int32)
    dia_slot = np.where(dia_slot_rows > 0, dia_slot_rows - 1,
                        n_dias).astype(np.int32)

    # ------------------------------------------------------ interval tables
    n_ivs = 0
    for iid in plan.pf_ops:
        n_ivs = max(n_ivs, iid + 1)
    for iid in plan.block_interval.values():
        n_ivs = max(n_ivs, iid + 1)
    max_gv = 1
    for op in plan.pf_ops.values():
        max_gv = max(max_gv, len(op.bitvector))
    iv_rounds = np.zeros(n_ivs + 1, np.int32)
    iv_nfetch = np.zeros(n_ivs + 1, np.int32)
    iv_nwb = np.zeros(n_ivs + 1, np.int32)
    iv_has_op = np.zeros(n_ivs + 1, bool)
    iv_regs = np.full((n_ivs + 1, max_gv), -1, np.int32)
    for iid, op in plan.pf_ops.items():
        fetch = op.bitvector
        rounds = op.serial_rounds
        has = bool(fetch)
        if is_plus:
            ent = plan.plus_fetch.get(iid)
            if ent is not None:
                live, live_rounds = ent
                if fetch:                       # engine consults plus_fetch
                    fetch, rounds = live, live_rounds   # only past this guard
                    has = bool(live)
            nwb = len(plan.live_sets.get(iid, op.bitvector))
        else:
            nwb = len(op.bitvector)
        iv_rounds[iid] = rounds
        iv_nfetch[iid] = len(fetch)
        iv_nwb[iid] = nwb
        iv_has_op[iid] = has
        # reg_ready refresh uses the FULL bitvector even for LTRF+ (cache
        # slots are reserved for dead entries; only the data movement is
        # trimmed) — order irrelevant (independent per-register max).
        for j, r in enumerate(sorted(op.bitvector)):
            iv_regs[iid, j] = r

    code = _PlanCode(
        n_pc=P, op_kind=op_kind, srcs=srcs, psrcs=psrcs, dsts=dsts,
        pdst=pdst, n_acc=n_acc, acc_regs=acc_regs, target=target,
        trips=trips, loop_slot=loop_slot, dia_slot=dia_slot,
        interval_of_pc=interval_of_pc, n_regs=n_regs, n_preds=n_preds,
        n_loops=n_loops, n_dias=n_dias,
        iv_rounds=iv_rounds, iv_nfetch=iv_nfetch, iv_nwb=iv_nwb,
        iv_has_op=iv_has_op, iv_regs=iv_regs, n_ivs=n_ivs,
    )
    _ENCODE_MEMO[key] = (code, plan)  # keep `plan` alive: memo key uses id()
    return code


# --------------------------------------------------------------------------
# Batch assembly: pad lanes into shared (lane, ...) arrays.
# --------------------------------------------------------------------------

@dataclass
class _Lane:
    workload: Workload
    cfg: SimConfig
    code: _PlanCode
    occupancy: int


def _occupancy(workload: Workload, cfg: SimConfig) -> int:
    cap_kb = cfg.rf_size_kb + (cfg.rfc_size_kb if cfg.add_rfc_to_main else 0)
    per_warp = max(workload.regs_per_thread, 1)
    return max(1, min(cfg.num_warps, cap_kb * 1024 // 128 // per_warp))


def _acap(ln: "_Lane") -> int:
    """Active-slot cap for one lane (mirrors the scalar engines')."""
    if ln.cfg.design in _CACHED_DESIGNS:
        return min(ln.cfg.active_slots, ln.occupancy)
    return ln.occupancy


def _refill_rate(cfg: SimConfig) -> float:
    """MRF bank slots per cycle (the scalar engines' ``_mrf_rate``)."""
    return cfg.num_banks / max(cfg.mrf_cycles / 6.0, 1.0)


def _refill_steps(cfg: SimConfig) -> int:
    """Fewest cycles after which one refill fills an empty bucket."""
    rate, n = _refill_rate(cfg), 0
    while rate * n < cfg.num_banks:
        n += 1
    return n


def _bucket(n: int, floor: int) -> int:
    """Next power-of-two >= n (>= floor): shape buckets bound recompiles."""
    b = floor
    while b < n:
        b *= 2
    return b


def _build(lanes: Sequence[_Lane]):
    """Pad every lane's tables/config into batch arrays (numpy, 64-bit)."""
    from .f64bits import bits

    i32, i64 = np.int32, np.int64
    K = _bucket(len(lanes), 2)
    W = _bucket(max(ln.cfg.num_warps for ln in lanes), 4)
    # Active-list width: cached designs cap it at `active_slots` (8), the
    # uncached ones scan every resident warp.  Keeping this dimension tight
    # is the difference between (K, 8) and (K, 64) work in the per-slot
    # scheduler scans — `run_batch` groups lanes by it.
    A = _bucket(max(_acap(ln) for ln in lanes), 2)
    P = _bucket(max(ln.code.n_pc for ln in lanes), 16)
    S = max(ln.code.srcs.shape[1] for ln in lanes)
    PS = max(ln.code.psrcs.shape[1] for ln in lanes)
    DD = max(ln.code.dsts.shape[1] for ln in lanes)
    G = max(ln.code.acc_regs.shape[1] for ln in lanes)
    GV = _bucket(max(ln.code.iv_regs.shape[1] for ln in lanes), 4)
    R = _bucket(max(ln.code.n_regs for ln in lanes), 8)
    PR = _bucket(max(ln.code.n_preds for ln in lanes), 2)
    L = _bucket(max(ln.code.n_loops for ln in lanes), 2)
    DM = _bucket(max(ln.code.n_dias for ln in lanes), 2)
    IV = _bucket(max(ln.code.n_ivs for ln in lanes), 4)
    C = max(ln.cfg.num_collectors for ln in lanes)
    PF = max(ln.cfg.max_inflight_prefetch for ln in lanes)
    # E == 1 statically means "no RFC lane in this chunk": the jitted run
    # skips the whole cache-classification + LRU block (RFC chunks are
    # padded to >= 2 entries so the gate never misfires).
    _rfc_es = [ln.cfg.rfc_entries for ln in lanes if ln.cfg.design == "RFC"]
    E = max(2, *_rfc_es) if _rfc_es else 1
    IW = max(ln.cfg.issue_width for ln in lanes)
    # token-bucket refill table width: past a lane's last entry a refill
    # tops the bucket up to full whatever the gap
    DT = _bucket(max(_refill_steps(ln.cfg) for ln in lanes) + 1, 8)

    m_s, m_ps, m_d, m_g = _meta_cols(S, PS, DD)
    MW = m_g + G                      # packed meta row width
    NWF = _F_LC + (L + 1) + (DM + 1)  # warp-family row width
    RVW = (R + 1) + (PR + 1)          # register+predicate value rows

    meta = np.zeros((K, P + 1, MW), i32)
    meta[:, :, M_KIND] = _OP_EXIT
    meta[:, :, M_PDST] = PR
    meta[:, :, M_LSL] = L
    meta[:, :, M_DSL] = DM
    meta[:, :, M_IVPC] = -1
    meta[:, :, m_s: m_s + S] = R
    meta[:, :, m_ps: m_ps + PS] = PR
    meta[:, :, m_d: m_d + DD] = R
    meta[:, :, m_g: m_g + G] = -1

    co = {
        # packed per-pc instruction metadata (sentinel row at pc=P)
        "meta": meta,
        # per-interval table: [rounds, nfetch, nwb, has_op] (sentinel at IV)
        "ivt": np.zeros((K, IV + 1, 4), i32),
        "ivregs": np.full((K, IV + 1, GV), -1, i32),
        # per-interval prefetch latency: f64 bits and its int() truncation
        "ivlat": np.zeros((K, IV + 1), i64), "ivlati": np.zeros((K, IV + 1), i64),
        # per-lane scalars; f64 quantities travel as bits (see `f64bits`)
        "endpc": np.zeros(K, i32),
        "mrfc": np.zeros(K, i64), "rfcc": np.zeros(K, i64),
        "brf_f": np.zeros(K, i64), "wlat": np.zeros(K, i64),
        "aluf": np.zeros(K, i64), "aluw": np.zeros(K, i64),
        "banksf": np.zeros(K, i64),
        # bits of rate * dt, dt = 0..DT-1 cycles since the last refill
        "refill": np.zeros((K, DT), i64),
        # L1 hit <=> jitter hash h < hthr (h / 0xFFFF < l1_hit is monotone)
        "hthr": np.zeros(K, i64),
        # bits of mem_cycles * (1 + spread) for each (h >> 3) jitter value
        "mjit": np.zeros((K, 0x2000), i64),
        "brf_i": np.zeros(K, i64), "l1c": np.zeros(K, i64),
        # dram_interval is a float on gpu.per_sm_configs shards (the per-SM
        # effective interval is dram_interval*num_sms/partitions)
        "thr": np.zeros(K, i64), "drint": np.zeros(K, i64),
        "seed": np.zeros(K, i64), "maxc": np.zeros(K, i64),
        "iw": np.zeros(K, i32), "nw": np.zeros(K, i32),
        "rcap": np.zeros(K, i32), "acap": np.zeros(K, i32),
        "tcap": np.zeros(K, i32), "ecap": np.ones(K, i32),
        "cached": np.zeros(K, bool), "edge": np.zeros(K, bool),
        "bl": np.zeros(K, bool), "rfc": np.zeros(K, bool),
        "ideal": np.zeros(K, bool), "fam": np.zeros(K, bool),
        # wedge guard / tick cap: a traced scalar so profiling harnesses can
        # cap the fused loop without recompiling (production leaves _GUARD)
        "tmax": np.asarray(_GUARD, i64),
        # dummies whose SHAPES carry the static widths the traced step
        # needs (issue-slot unroll, meta column groups, value/counter rows)
        "slots": np.zeros(IW, np.int8),
        "mdims": np.zeros((S, PS, DD, G), np.int8),
        "rdims": np.zeros((R + 1, PR + 1), np.int8),
        "ldims": np.zeros((L + 1, DM + 1), np.int8),
    }

    def remap(a, sent_old, sent_new):
        return np.where(a == sent_old, sent_new, a).astype(np.int32)

    for k, ln in enumerate(lanes):
        c, cfg = ln.code, ln.cfg
        n = c.n_pc
        m = meta[k]
        m[: n + 1, M_KIND] = c.op_kind
        m[: n + 1, M_NACC] = c.n_acc
        m[: n + 1, M_PDST] = remap(c.pdst, c.n_preds, PR)
        m[: n + 1, M_TGT] = c.target
        m[: n + 1, M_TRIPS] = c.trips
        m[: n + 1, M_LSL] = remap(c.loop_slot, c.n_loops, L)
        m[: n + 1, M_DSL] = remap(c.dia_slot, c.n_dias, DM)
        m[: n + 1, M_IVPC] = c.interval_of_pc
        m[: n + 1, m_s: m_s + c.srcs.shape[1]] = remap(c.srcs, c.n_regs, R)
        m[: n + 1, m_ps: m_ps + c.psrcs.shape[1]] = \
            remap(c.psrcs, c.n_preds, PR)
        m[: n + 1, m_d: m_d + c.dsts.shape[1]] = remap(c.dsts, c.n_regs, R)
        m[: n + 1, m_g: m_g + c.acc_regs.shape[1]] = c.acc_regs
        nv = c.n_ivs
        co["ivt"][k, : nv + 1, 0] = c.iv_rounds
        co["ivt"][k, : nv + 1, 1] = c.iv_nfetch
        co["ivt"][k, : nv + 1, 2] = c.iv_nwb
        co["ivt"][k, : nv + 1, 3] = c.iv_has_op.astype(i32)
        co["ivregs"][k, : nv + 1, : c.iv_regs.shape[1]] = c.iv_regs
        # sentinel rows must stay inert even where lane rows ended early
        co["ivt"][k, nv, 3] = 0

        co["endpc"][k] = n
        design = cfg.design
        cached = design in _CACHED_DESIGNS
        rcap = ln.occupancy
        # every float below is computed as the scalar engines compute it
        # (numpy float64 rounds each operation as Python does)
        lat = c.iv_rounds * cfg.mrf_cycles \
            + c.iv_nfetch / cfg.xbar_regs_per_cycle
        co["ivlat"][k, : nv + 1] = bits(lat)
        co["ivlati"][k, : nv + 1] = lat.astype(i64)
        wlat = (cfg.base_rf_cycles if design == "Ideal"
                else cfg.mrf_cycles if design == "BL" else cfg.rfc_cycles)
        co["mrfc"][k] = bits(cfg.mrf_cycles)
        co["rfcc"][k] = bits(cfg.rfc_cycles)
        co["brf_f"][k] = bits(cfg.base_rf_cycles)
        co["wlat"][k] = bits(wlat)
        co["aluf"][k] = bits(cfg.alu_cycles)
        co["aluw"][k] = bits(cfg.alu_cycles + wlat)
        co["banksf"][k] = bits(cfg.num_banks)
        co["refill"][k] = bits(_refill_rate(cfg) * np.arange(DT))
        co["hthr"][k] = np.count_nonzero(
            np.arange(0x10000) / 0xFFFF < ln.workload.l1_hit)
        spread = (np.arange(0x2000) / 0x1FFF - 0.5) * 0.6
        co["mjit"][k] = bits(cfg.mem_cycles * (1.0 + spread))
        co["brf_i"][k] = cfg.base_rf_cycles
        co["l1c"][k] = cfg.l1_cycles
        co["thr"][k] = 2 * cfg.l1_cycles
        co["drint"][k] = bits(cfg.dram_interval)
        co["seed"][k] = cfg.seed
        co["maxc"][k] = cfg.max_cycles
        co["iw"][k] = cfg.issue_width
        co["nw"][k] = cfg.num_warps
        co["rcap"][k] = rcap
        co["acap"][k] = min(cfg.active_slots, rcap) if cached else rcap
        co["tcap"][k] = min(cfg.active_slots, rcap)
        co["ecap"][k] = max(1, min(cfg.rfc_entries, E))
        co["cached"][k] = cached
        co["edge"][k] = design in _EDGE_PREFETCH
        co["bl"][k] = design == "BL"
        co["rfc"][k] = design == "RFC"
        co["ideal"][k] = design == "Ideal"
        co["fam"][k] = cached

    wf = np.zeros((K, W, NWF), i64)
    wf[:, :, F_ST] = INACTIVE_READY
    wf[:, :, F_IV] = -1
    rc = np.full((K, E, 2), -1, i64)
    rc[:, :, 1] = _BIG
    st = {
        "cycle": np.zeros(K, i64),
        "guard": np.zeros((), i64),
        "alive": np.zeros(K, bool),
        "budget": np.zeros(K, bool),
        "wf": wf,
        "cf": np.zeros((K, W, 2 + S + PS), i64),
        "rv": np.zeros((K, W, RVW), i64),
        "rm": np.zeros((K, W, RVW), i32),
        "act": np.zeros((K, A), i32),
        "na": np.zeros(K, i32),
        "res": np.zeros((K, W), bool),
        "nr": np.zeros(K, i32),
        "ptr": np.zeros(K, i32),
        "pf": np.full((K, PF), _BIG, i64),
        "col": np.full((K, C), _BIG, i64),
        "tok": np.zeros(K, i64),
        "mlast": np.zeros(K, i64),
        "dnext": np.zeros(K, i64),
        "rc": rc,
        "rcnt": np.zeros(K, i32),
        "rstamp": np.zeros(K, i64),
        # cycles per attribution category, then one column counting the
        # ticks the lane entered alive (lockstep occupancy; no result
        # field reads it)
        "bd": np.zeros((K, len(CYCLE_CATEGORIES) + 1), i64),
        "ch": np.zeros(K, i64), "ca": np.zeros(K, i64),
        "cm": np.zeros(K, i64), "cpo": np.zeros(K, i64),
        "cpc": np.zeros(K, i64), "cps": np.zeros(K, i64),
        "cwb": np.zeros(K, i64), "cact": np.zeros(K, i64),
    }
    for k, ln in enumerate(lanes):
        cfg = ln.cfg
        st["alive"][k] = True
        # initial admit(): the first resident_cap warps, in wid order
        st["res"][k, : ln.occupancy] = True
        st["nr"][k] = ln.occupancy
        st["ptr"][k] = ln.occupancy
        st["pf"][k, : cfg.max_inflight_prefetch] = 0
        st["col"][k, : cfg.num_collectors] = 0
        st["tok"][k] = bits(cfg.num_banks)
    return co, st


# --------------------------------------------------------------------------
# The jitted lockstep run: one lax.while_loop over the whole batch.
# --------------------------------------------------------------------------

def _run_jax(co, st):
    """Advance every lane to completion.  Traced+jitted once per shape."""
    _, jnp, lax = _jax()
    from . import f64bits as fb

    i64 = jnp.int64
    K, W, NWF = st["wf"].shape
    A = st["act"].shape[1]
    E = st["rc"].shape[1]         # 1 <=> no RFC lane in this chunk (static)
    P = co["meta"].shape[1] - 1
    S, PS, DD, G = co["mdims"].shape
    R = co["rdims"].shape[0] - 1
    PRS = co["rdims"].shape[1] - 1
    RVW = st["rv"].shape[2]       # masked writes use index RVW: OOB-dropped
    LS = co["ldims"].shape[0] - 1
    DS = co["ldims"].shape[1] - 1
    IVS = co["ivt"].shape[1] - 1
    IW = co["slots"].shape[0]
    NCAT = len(CYCLE_CATEGORIES)
    M_S, M_PS, M_D, M_G = _meta_cols(S, PS, DD)
    F_DC = _F_LC + LS + 1
    READY, WAIT = INACTIVE_READY, INACTIVE_WAIT
    kk = jnp.arange(K)
    wI = jnp.arange(W)
    aI = jnp.arange(A)
    ctrI = jnp.arange(NWF - _F_LC)
    BIG = jnp.asarray(_BIG, i64)
    NOEV = jnp.asarray(np.iinfo(np.int64).max, i64)   # "no next event"

    def refresh_cf(s, wid, mask, md):
        """Recompute the readiness-cache row for one selected warp per lane
        (the scalar engines' `_refresh_ready`, at the identical sites: the
        warp's own issue or prefetch — the only events that can change its
        current instruction's operand times).  ``md`` is the warp's meta
        row at its (post-update) pc."""
        sidx = md[:, M_S: M_S + S]                          # (K, S)
        pidx = md[:, M_PS: M_PS + PS]                       # (K, PS)
        ts = s["rv"][kk[:, None], wid[:, None], sidx]       # (K, S)
        fm = s["rm"][kk[:, None], wid[:, None], sidx] > 0
        tp = s["rv"][kk[:, None], wid[:, None], R + 1 + pidx]
        cmax = jnp.maximum(ts.max(axis=1), tp.max(axis=1))
        cmem = jnp.where(fm, ts, 0).max(axis=1)
        newcf = jnp.concatenate([cmax[:, None], cmem[:, None], ts, tp],
                                axis=1)
        oldcf = s["cf"][kk, wid]
        s["cf"] = s["cf"].at[kk, wid].set(
            jnp.where(mask[:, None], newcf, oldcf))
        return s

    def prefetch_slot(s, body, lat):
        """Charge one prefetch op into the inflight-slot array, masked.
        Returns (state, done_time) — the caller folds status/ra/iv into
        its own warp-family row write."""
        slot = jnp.argmin(s["pf"], axis=1)
        freet = s["pf"][kk, slot]
        startt = jnp.maximum(s["cycle"], freet)
        done = fb.floor(fb.add(fb.from_int(startt), lat))  # int(start + lat)
        s["pf"] = s["pf"].at[kk, slot].set(jnp.where(body, done, freet))
        return s, done

    def prefetch_charge(s, wid, ii, body, done):
        """Max the fetched interval's registers up to the landing time (the
        from-mem flags stay as they are)."""
        regs = co["ivregs"][kk, ii]                     # (K, GV)
        vp = (regs >= 0) & body[:, None]
        ridx = jnp.where(vp, regs, RVW)                 # OOB: masked drop
        val = jnp.where(vp, fb.from_int(done)[:, None], 0)
        s["rv"] = s["rv"].at[kk[:, None], wid[:, None], ridx].max(val)
        return s

    def activation(s, act):
        """Greedy lowest-wid-ready activation until slots/candidates run out
        (the scalar engines' interleaved activate() calls collapse to this:
        admitted wids only increase and the READY pool never grows mid-loop,
        so batched ascending-wid activation charges identical prefetches)."""
        def more(s):
            cand = s["res"] & (s["wf"][:, :, F_ST] == READY)
            return jnp.any(act & (s["na"] < co["acap"])
                           & jnp.any(cand, axis=1))

        def one(s):
            cand = s["res"] & (s["wf"][:, :, F_ST] == READY)
            do = act & (s["na"] < co["acap"]) & jnp.any(cand, axis=1)
            wid = jnp.argmax(cand, axis=1)
            # _start_prefetch(force=True) for the activating warp
            row = s["wf"][kk, wid]                       # (K, NWF)
            pcc = jnp.minimum(row[:, F_PC], P)
            md = co["meta"][kk, pcc]
            iid = md[:, M_IVPC]
            go = do & co["cached"] & (iid >= 0)
            ii = jnp.where(go, iid, IVS)
            ivt = co["ivt"][kk, ii]                      # (K, 4)
            body = go & (ivt[:, 3] > 0)
            nf = ivt[:, 1].astype(i64)
            s, done = prefetch_slot(s, body, co["ivlat"][kk, ii])
            s["cpo"] += body.astype(i64)
            s["cpc"] += jnp.where(body, co["ivlati"][kk, ii], 0)
            s["cps"] += jnp.where(body, done - s["cycle"], 0)
            s["cm"] += jnp.where(body, nf, 0)
            s = prefetch_charge(s, wid, ii, body, done)
            # fold activation + prefetch into one warp-family row write
            newst = jnp.where(body, PREFETCH,
                              jnp.where(do, ACTIVE, row[:, F_ST]))
            newiv = jnp.where(go, iid.astype(i64), row[:, F_IV])
            newra = jnp.where(body, done, row[:, F_RA])
            newrow = jnp.concatenate(
                [newst[:, None], row[:, F_PC: F_PC + 1], newiv[:, None],
                 newra[:, None], row[:, F_RA + 1:]], axis=1)
            s["wf"] = s["wf"].at[kk, wid].set(newrow)
            s = refresh_cf(s, wid, body, md)
            s["cact"] += do.astype(i64)
            pos = jnp.minimum(s["na"], A - 1)
            oldv = s["act"][kk, pos]
            s["act"] = s["act"].at[kk, pos].set(
                jnp.where(do, wid.astype(s["act"].dtype), oldv))
            s["na"] = s["na"] + do.astype(s["na"].dtype)
            return s

        return lax.while_loop(more, one, s)

    def issue_one(s, picked, wsel, cycf):
        """The _issue body for one selected warp per lane, masked.
        Returns (state, instruction-issued, structural-stall)."""
        row = s["wf"][kk, wsel]                         # (K, NWF)
        pcs = row[:, F_PC]
        pcc = jnp.minimum(pcs, P)
        md = co["meta"][kk, pcc]                        # (K, MW)
        kind = md[:, M_KIND]
        bra = picked & (kind == _OP_BRA)
        ext = picked & (kind == _OP_EXIT)
        opnd = picked & (kind != _OP_BRA) & (kind != _OP_EXIT)
        nacc = md[:, M_NACC].astype(i64)
        # RFC classification against the PRE-issue cache state (statically
        # skipped in chunks with no RFC lane: co["rfc"] is all-False there,
        # so every consumer of n_miss/n_hit reduces to the zero branch)
        regs = md[:, M_G: M_G + G]                      # (K, G)
        if E > 1:
            onr = (regs >= 0) & opnd[:, None] & co["rfc"][:, None]
            keyv = jnp.where(onr,
                             wsel.astype(i64)[:, None] * (R + 1) + regs, -2)
            memb = (s["rc"][:, None, :, 0] == keyv[:, :, None]).any(axis=2)
            n_miss = (onr & ~memb).sum(axis=1).astype(i64)
            n_hit = memb.sum(axis=1).astype(i64)
        else:
            n_miss = jnp.zeros((K,), i64)
            n_hit = jnp.zeros((K,), i64)
        # MRF bandwidth token bucket (refill only on a non-zero request)
        n_bw = jnp.where(co["bl"], jnp.where(opnd, nacc, 0),
                         jnp.where(co["rfc"], n_miss, 0))
        do_bw = opnd & (n_bw > 0)
        refill = do_bw & (s["cycle"] > s["mlast"])
        gap = jnp.clip(s["cycle"] - s["mlast"], 0, co["refill"].shape[1] - 1)
        newtok = jnp.minimum(co["banksf"],
                             fb.add(s["tok"], co["refill"][kk, gap]))
        tok = jnp.where(refill, newtok, s["tok"])
        s["mlast"] = jnp.where(refill, s["cycle"], s["mlast"])
        n_bwf = fb.from_int(n_bw)
        bw_ok = ~do_bw | (tok >= n_bwf)
        # tokens are consumed before the collector attempt (and leak if the
        # collector then fails — the scalar engines' exact semantics)
        s["tok"] = jnp.where(do_bw & bw_ok, fb.sub(tok, n_bwf), tok)
        cslot = jnp.argmin(s["col"], axis=1)
        cfree = s["col"][kk, cslot]
        ok = opnd & bw_ok & (cfree <= s["cycle"])
        s["col"] = s["col"].at[kk, cslot].set(
            jnp.where(ok, s["cycle"] + co["brf_i"], cfree))
        sfail = opnd & ~ok
        read_lat = jnp.where(
            co["ideal"], co["brf_f"],
            jnp.where(co["bl"], co["mrfc"],
                      jnp.where(co["rfc"],
                                jnp.where(n_miss > 0, co["mrfc"], co["rfcc"]),
                                co["rfcc"])))
        s["cm"] += jnp.where(ok, jnp.where(co["bl"], nacc,
                                           jnp.where(co["rfc"], n_miss, 0)), 0)
        s["ca"] += jnp.where(ok & (co["rfc"] | co["fam"]), nacc, 0)
        s["ch"] += jnp.where(ok, jnp.where(co["rfc"], n_hit,
                                           jnp.where(co["fam"], nacc, 0)), 0)
        # RFC LRU mutation: move-to-end every pre-state hit in operand order,
        # then insert misses with oldest-stamp eviction (OrderedDict-equal).
        # The hit phase is ONE scatter-max: stamps are globally monotone, so
        # a duplicate key's last move-to-end is exactly the max stamp, and
        # every fresh stamp exceeds the entry's old one.
        if E > 1:
            lru = ok & co["rfc"]
            hvs = lru[:, None] & memb                   # (K, G)
            hvi = hvs.astype(i64)
            stamps = s["rstamp"][:, None] + jnp.cumsum(hvi, axis=1) - hvi
            pos = jnp.argmax(s["rc"][:, None, :, 0] == keyv[:, :, None],
                             axis=2)
            posm = jnp.where(hvs, pos, E)               # OOB: masked drop
            s["rc"] = s["rc"].at[kk[:, None], posm, 1].max(stamps)
            s["rstamp"] += hvi.sum(axis=1)
            for i in range(G):                          # insert/evict phase
                ki = keyv[:, i]
                membL = (s["rc"][:, :, 0] == ki[:, None]).any(axis=1)
                ins = lru & (ki >= 0) & ~membL          # vs LIVE state
                full = s["rcnt"] >= co["ecap"]
                slot = jnp.where(full,
                                 jnp.argmin(s["rc"][:, :, 1], axis=1)
                                 .astype(s["rcnt"].dtype),
                                 s["rcnt"])
                slot = jnp.minimum(slot, E - 1)
                oldrow = s["rc"][kk, slot]
                newr = jnp.stack([ki, s["rstamp"]], axis=1)
                s["rc"] = s["rc"].at[kk, slot].set(
                    jnp.where(ins[:, None], newr, oldrow))
                s["rstamp"] += ins.astype(i64)
                s["rcnt"] += (ins & ~full).astype(s["rcnt"].dtype)
        # memory latency: deterministic jitter hash + single-server DRAM queue
        is_ld = kind == _OP_LD
        ldo = ok & is_ld
        mops = row[:, F_MO]
        h = (wsel.astype(i64) * 2654435761 + mops * 40503
             + co["seed"] * 97) & 0xFFFF
        hit = h < co["hthr"]
        dstart = jnp.maximum(cycf, s["dnext"])
        s["dnext"] = jnp.where(ldo & ~hit, fb.add(dstart, co["drint"]),
                               s["dnext"])
        mlat = jnp.where(hit, co["l1c"],
                         fb.floor(fb.add(fb.sub(dstart, cycf),
                                         co["mjit"][kk, h >> 3])))
        # writeback chain: done_at accumulates exactly like the scalar code
        is_set = kind == _OP_SET
        da = fb.add(fb.add(cycf, read_lat),
                    jnp.where(is_set, co["aluf"],
                              jnp.where(is_ld,
                                        fb.add(fb.from_int(mlat), co["wlat"]),
                                        co["aluw"])))
        # dst-register + dst-predicate writeback: one scatter into each
        # (reg | pred) plane at the same indices, masked rows dropped via OOB
        pd = md[:, M_PDST]
        onp = ok & is_set & (pd < PRS)
        dsts = md[:, M_D: M_D + DD]                     # (K, DD)
        ond = (ok & ~is_set)[:, None] & (dsts < R)
        didx = jnp.where(ond, dsts, RVW)
        pcol = jnp.where(onp, R + 1 + pd, RVW)[:, None]
        wix = jnp.concatenate([didx, pcol], axis=1)     # (K, DD+1)
        vt = jnp.concatenate(
            [jnp.broadcast_to(da[:, None], ond.shape), da[:, None]], axis=1)
        vm = jnp.concatenate(
            [ond & is_ld[:, None], jnp.zeros((K, 1), bool)], axis=1)
        s["rv"] = s["rv"].at[kk[:, None], wsel[:, None], wix].set(vt)
        s["rm"] = s["rm"].at[kk[:, None], wsel[:, None], wix].set(
            vm.astype(s["rm"].dtype))
        happened = bra | ext | ok
        # branch resolution (loop trip counters / diamond visit hashes);
        # the counters live in the warp-family row — updated in place via
        # one-hot column selects, folded into the single row write below
        tgt = md[:, M_TGT]
        trips = md[:, M_TRIPS]
        lsl = md[:, M_LSL]
        dsl = md[:, M_DSL]
        uncond = md[:, M_PS] >= PRS
        isl = bra & (lsl < LS)
        lidx = jnp.where(isl, lsl, LS)
        oldl = jnp.take_along_axis(row, (_F_LC + lidx)[:, None], axis=1)[:, 0]
        c = oldl + 1
        tkl = c < trips
        newl = jnp.where(tkl, c, 0)
        isd = bra & ~uncond & (lsl >= LS)
        didx2 = jnp.where(isd, dsl, DS)
        v = jnp.take_along_axis(row, (F_DC + didx2)[:, None], axis=1)[:, 0]
        hh = (wsel.astype(i64) * 31 + v * 17 + co["seed"]) & 0xFF
        taken = jnp.where(uncond, True,
                          jnp.where(isl, tkl, (hh & 1) == 1))
        npc = jnp.where(bra, jnp.where(taken, tgt.astype(i64), pcs + 1),
                        jnp.where(ok, pcs + 1, pcs))
        npce = jnp.where(picked & ~ext, npc, pcs)
        # edge prefetch: issued warp crossed into a new interval's block
        # (_start_prefetch with force=False, at the post-update pc)
        ep = co["edge"] & (bra | ok) & (npc < co["endpc"])
        pccp = jnp.minimum(npce, P)
        md2 = co["meta"][kk, pccp]          # shared with the cache refresh
        iid = md2[:, M_IVPC]
        go = ep & (iid >= 0) & (iid != row[:, F_IV])
        ii = jnp.where(go, iid, IVS)
        ivt = co["ivt"][kk, ii]
        body = go & (ivt[:, 3] > 0)
        nf = ivt[:, 1].astype(i64)
        s, done = prefetch_slot(s, body, co["ivlat"][kk, ii])
        s["cpo"] += body.astype(i64)
        s["cpc"] += jnp.where(body, co["ivlati"][kk, ii], 0)
        s["cps"] += jnp.where(body, done - s["cycle"], 0)
        s["cm"] += jnp.where(body, nf, 0)
        s = prefetch_charge(s, wsel, ii, body, done)
        # ONE warp-family row write covers pc/status/iv/ra/issued/mops and
        # both branch counters (ext and edge-prefetch are disjoint: ep
        # requires bra|ok, which excludes exit instructions)
        newst = jnp.where(ext, DONE,
                          jnp.where(body, PREFETCH, row[:, F_ST]))
        newiv = jnp.where(go, iid.astype(i64), row[:, F_IV])
        newra = jnp.where(body, done, row[:, F_RA])
        newis = row[:, F_IS] + happened.astype(i64)
        newmo = mops + ldo.astype(i64)
        ctr = row[:, _F_LC:]
        ctr = jnp.where(isl[:, None] & (ctrI[None, :] == lidx[:, None]),
                        newl[:, None], ctr)
        ctr = jnp.where(isd[:, None]
                        & (ctrI[None, :] == (LS + 1 + didx2)[:, None]),
                        (v + 1)[:, None], ctr)
        newrow = jnp.concatenate(
            [newst[:, None], npce[:, None], newiv[:, None], newra[:, None],
             newis[:, None], newmo[:, None], ctr], axis=1)
        s["wf"] = s["wf"].at[kk, wsel].set(newrow)
        s = refresh_cf(s, wsel, happened, md2)
        return s, happened, sfail

    def tick(s):
        s["guard"] = s["guard"] + 1
        entered = s["alive"]
        # cycle-budget watchdog: freeze the lane at the identical cycle the
        # scalar engines raise SimBudgetExceeded
        exceed = s["alive"] & (co["maxc"] > 0) & (s["cycle"] > co["maxc"])
        s["budget"] = s["budget"] | exceed
        s["alive"] = s["alive"] & ~exceed
        act = s["alive"]
        # wake: WAIT->READY, PREFETCH->ACTIVE once ready_at arrives
        stp = s["wf"][:, :, F_ST]
        wake = s["res"] & act[:, None] \
            & (s["wf"][:, :, F_RA] <= s["cycle"][:, None])
        ns = jnp.where(wake & (stp == WAIT), READY,
                       jnp.where(wake & (stp == PREFETCH), ACTIVE, stp))
        s["wf"] = s["wf"].at[:, :, F_ST].set(ns)
        s = activation(s, act)
        # issue slots (round-robin rank arithmetic == the golden scan).
        # The active list is frozen across the unrolled slots (compaction
        # runs after), so slot position / rank / DONE-mark bookkeeping is
        # accumulated per slot and applied in two scatters at the end —
        # deferring the DONE status write is exact because an at-end warp
        # is never ready (atend gates every consumer the status would).
        posv = aI[None, :] < s["na"][:, None]
        wida = jnp.where(posv, s["act"], 0)
        nz = jnp.maximum(s["na"], 1).astype(i64)
        rank = jnp.where(posv,
                         (aI[None, :] - (s["cycle"] % nz)[:, None])
                         % nz[:, None], BIG)
        ndacc = jnp.zeros((K, A), bool)
        msacc = jnp.zeros((K, A), i64)
        cycf = fb.from_int(s["cycle"])
        thrf = fb.from_int(s["cycle"] + co["thr"])
        issue_any = jnp.zeros((K,), bool)
        struct = jnp.zeros((K,), bool)
        for j in range(IW):
            slot_on = act & (j < co["iw"])
            wfa = s["wf"][kk[:, None], wida]            # (K, A, NWF)
            cfa = s["cf"][kk[:, None], wida]            # (K, A, CW)
            stat = wfa[:, :, F_ST]
            isact = posv & (stat == ACTIVE)
            pca = wfa[:, :, F_PC]
            atend = pca >= co["endpc"][:, None]
            # readiness/blockedness from the cached per-warp planes — no
            # per-slot operand gathers (scalar `_refresh_ready` semantics:
            # a warp's operand times only change when IT issues/prefetches)
            ready = isact & ~atend & (cfa[:, :, 0] <= cycf[:, None])
            blocked = jnp.where(cfa[:, :, 1] > thrf[:, None], cfa[:, :, 1], 0)
            rrk = jnp.where(ready & slot_on[:, None], rank, BIG)
            crank = rrk.min(axis=1)
            picked = (crank < BIG) & slot_on
            visited = posv & slot_on[:, None] & (rank <= crank[:, None])
            # scanned warps at program end retire (applied after the slots)
            ndacc = ndacc | (visited & isact & atend)
            # scanned warps blocked on long memory: deactivation candidates
            ms = visited & isact & ~atend & ~ready & (blocked > 0)
            msacc = jnp.maximum(msacc, jnp.where(ms, blocked, 0))
            wsel = s["act"][kk, jnp.argmin(rrk, axis=1)]
            s, happened, sfail = issue_one(s, picked, wsel, cycf)
            issue_any = issue_any | happened
            struct = struct | sfail
        s["wf"] = s["wf"].at[kk[:, None], wida, F_ST].max(
            jnp.where(ndacc, DONE, 0))
        stall_until = jnp.zeros((K, W), i64).at[kk[:, None], wida].max(msacc)
        # two-level deactivation (cached designs swap stalled warps out)
        stp2 = s["wf"][:, :, F_ST]
        de = (stall_until > 0) & (stp2 == ACTIVE) \
            & co["cached"][:, None] & act[:, None]
        ivv = s["wf"][:, :, F_IV]
        ii = jnp.where(de & (ivv >= 0), ivv, IVS)
        nwb = jnp.where(de, co["ivt"][kk[:, None], ii, 2].astype(i64), 0) \
            .sum(axis=1)
        s["cwb"] += nwb
        s["cm"] += nwb
        s["wf"] = s["wf"].at[:, :, F_ST].set(jnp.where(de, WAIT, stp2))
        s["wf"] = s["wf"].at[:, :, F_RA].set(
            jnp.where(de, fb.floor(stall_until), s["wf"][:, :, F_RA]))
        s["wf"] = s["wf"].at[:, :, F_IV].set(jnp.where(de, -1, ivv))
        # compact the active list: drop deactivated (WAIT) + retired (DONE).
        # Stable compaction = cumsum of keepers + dropped-OOB scatter (the
        # argsort this replaces cost more than every other tick op).
        stw = s["wf"][kk[:, None], wida, F_ST]
        gone = posv & act[:, None] & ((stw == WAIT) | (stw == DONE))
        keep = posv & ~gone
        cpos = jnp.where(keep, jnp.cumsum(keep.astype(jnp.int32), axis=1) - 1,
                         A)
        s["act"] = jnp.zeros_like(s["act"]).at[kk[:, None], cpos].set(
            wida.astype(s["act"].dtype))
        s["na"] = keep.sum(axis=1).astype(s["na"].dtype)
        # retire DONE warps from residency, admit pending warps
        donep = posv & act[:, None] & (stw == DONE)
        s["res"] = s["res"].at[kk[:, None], wida].min(~donep)
        s["nr"] = s["nr"] - donep.sum(axis=1).astype(s["nr"].dtype)
        nadm = jnp.maximum(
            jnp.minimum(co["nw"] - s["ptr"], co["rcap"] - s["nr"]), 0)
        nadm = jnp.where(act, nadm, 0)
        newres = (wI[None, :] >= s["ptr"][:, None]) \
            & (wI[None, :] < (s["ptr"] + nadm)[:, None])
        s["res"] = s["res"] | newres
        s["nr"] = s["nr"] + nadm
        s["ptr"] = s["ptr"] + nadm
        # one activation pass covers the scalar engines' interleaved
        # deactivate()/cleanup activate() calls (admitted wids exceed every
        # resident wid, so ascending-wid order is the same either way)
        s = activation(s, act)
        # terminate lanes with nothing resident and nothing pending
        fin = act & (s["nr"] == 0) & (s["ptr"] >= co["nw"])
        s["alive"] = s["alive"] & ~fin
        adv = act & ~fin
        # classify the zero-issue cycle + find the next event horizon —
        # all elementwise reads of the status/pc/readiness planes (status
        # ACTIVE/PREFETCH <=> active-list membership, so no slot gathers)
        stc = s["wf"][:, :, F_ST]
        pcw = s["wf"][:, :, F_PC]
        livew = (stc == ACTIVE) & (pcw < co["endpc"][:, None])
        cmaxw = s["cf"][:, :, 0]
        cmemw = s["cf"][:, :, 1]
        saw_pf = (stc == PREFETCH).any(axis=1)
        saw_mem = (livew & (cmemw > cycf[:, None])).any(axis=1)
        saw_dep = (livew & (cmaxw > cycf[:, None])).any(axis=1)
        drain = (s["ptr"] >= co["nw"]) & (s["nr"] < co["tcap"])
        cat = jnp.where(drain, _CAT_INDEX["drain"],
              jnp.where(struct, _CAT_INDEX["bank_conflict"],
              jnp.where(saw_pf, _CAT_INDEX["prefetch_stall"],
              jnp.where(saw_mem, _CAT_INDEX["mem_stall"],
              jnp.where(saw_dep, _CAT_INDEX["alu_dep"],
                        _CAT_INDEX["scheduler_idle"])))))
        cyc = s["cycle"]
        colf = s["col"].min(axis=1)
        c1 = jnp.where(colf > cyc, colf, NOEV)
        wnp = s["res"] & ((stc == WAIT) | (stc == PREFETCH))
        c2 = jnp.where(wnp, s["wf"][:, :, F_RA], NOEV).min(axis=1)
        # operand times: the earliest pending one, floored (floor and min
        # commute)
        tv = s["cf"][:, :, 2:]
        tmin = jnp.where(livew[:, :, None] & (tv > cycf[:, None, None]),
                         tv, fb.INF).min(axis=(1, 2))
        c3 = jnp.where(tmin == fb.INF, NOEV, fb.floor(tmin))
        best = jnp.minimum(jnp.minimum(c1, c2), c3)
        nxt = jnp.where(best == NOEV, cyc + 1, jnp.maximum(best, cyc + 1))
        delta = jnp.where(issue_any, 1, nxt - cyc)
        cati = jnp.where(issue_any, 0, cat)
        oh = (jnp.arange(NCAT)[None, :] == cati[:, None]) & adv[:, None]
        # the alive-tick counter rides in the breakdown's update: a carried
        # vector of its own cost the loop 3 us a tick on a v5e (0.45%)
        s["bd"] = s["bd"] + jnp.concatenate(
            [jnp.where(oh, delta[:, None], 0), entered[:, None].astype(i64)],
            axis=1)
        s["cycle"] = cyc + jnp.where(adv, delta, 0)
        return s

    def running(s):
        return jnp.any(s["alive"]) & (s["guard"] <= co["tmax"])

    return lax.while_loop(running, tick, st)


# Launch accounting for the perf ledger: XLA compile wall vs steady-state
# simulation wall, plus the fused-loop tick count (how hard the
# event-horizon skip is working); the benchmark's sim driver counts its
# change over the measured window (`bench/drivers/sim.py`).  The
# host phases around the launches (plan encoding, packing, extraction) have
# their own seconds, and ``lane_ticks`` / ``lane_slots`` count the ticks
# real lanes entered alive against the ticks they were carried (lockstep
# occupancy).  Each phase is also a profiler span (`_phase`).
RUN_STATS = {"compile_s": 0.0, "run_s": 0.0,
             "compiles": 0, "launches": 0, "ticks": 0,
             "encode_s": 0.0, "build_s": 0.0, "extract_s": 0.0,
             "lane_ticks": 0, "lane_slots": 0}


def reset_run_stats() -> dict:
    """Zero the compile/run accounting (returns the live dict)."""
    for k, v in RUN_STATS.items():
        RUN_STATS[k] = type(v)(0)
    return RUN_STATS


@contextlib.contextmanager
def _phase(span: str, stat: str):
    """One host phase: a profiler span named ``span`` (on the profiler's
    clock, beside the device's operations) whose wall seconds add to
    ``RUN_STATS[stat]``."""
    jax, _, _ = _jax()
    with jax.profiler.TraceAnnotation(span):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            RUN_STATS[stat] += time.perf_counter() - t0


_COMPILED: dict = {}


def _aot_compile(co, st):
    """Compile (or fetch) the executable for this chunk's shape bucket.

    Ahead-of-time ``lower().compile()`` instead of a bare ``jax.jit`` call
    so compilation wall is attributed to ``RUN_STATS["compile_s"]`` and the
    launch wall to ``RUN_STATS["run_s"]`` — the honest throughput split the
    ledger reports (the persistent compile cache still applies)."""
    sig = (tuple(sorted((k, v.shape, str(v.dtype)) for k, v in co.items())),
           tuple(sorted((k, v.shape, str(v.dtype)) for k, v in st.items())))
    fn = _COMPILED.get(sig)
    if fn is None:
        jax, _, _ = _jax()
        t0 = time.perf_counter()
        fn = jax.jit(_run_jax).lower(co, st).compile()
        RUN_STATS["compile_s"] += time.perf_counter() - t0
        RUN_STATS["compiles"] += 1
        _COMPILED[sig] = fn
    return fn


def _run_lanes(lanes: Sequence[_Lane]) -> list:
    jax, _, _ = _jax()
    with _phase("repro.sim.build", "build_s"):
        co, st = _build(lanes)
    with jax.enable_x64(True):  # int64 state: cycles, stamps, f64 bits
        fn = _aot_compile(co, st)
        with _phase("repro.sim.launch", "run_s"):
            out = fn(co, st)
            out = {k: np.asarray(v) for k, v in out.items()}
        RUN_STATS["launches"] += 1
        RUN_STATS["ticks"] += int(out["guard"])
        # the `_bucket` padding lanes past len(lanes) are never alive
        RUN_STATS["lane_ticks"] += int(out["bd"][:len(lanes), -1].sum())
        RUN_STATS["lane_slots"] += len(lanes) * int(out["guard"])
    if out["alive"].any():
        raise RuntimeError("batch simulator wedged")
    with _phase("repro.sim.extract", "extract_s"):
        return [_extract(ln, i, out) for i, ln in enumerate(lanes)]


def _extract(lane: _Lane, i: int, out: dict):
    cfg = lane.cfg
    if out["budget"][i]:
        return SimBudgetExceeded(cfg.design, lane.workload.name,
                                 cfg.max_cycles, int(out["cycle"][i]))
    bd = new_breakdown()
    for j, c in enumerate(CYCLE_CATEGORIES):
        bd[c] = int(out["bd"][i, j])
    res = SimResult(design=cfg.design, workload=lane.workload.name,
                    cycles=int(out["cycle"][i]),
                    instructions=int(out["wf"][i, :, F_IS].sum()),
                    resident_warps=lane.occupancy,
                    rfc_hits=int(out["ch"][i]),
                    rfc_accesses=int(out["ca"][i]),
                    mrf_accesses=int(out["cm"][i]),
                    prefetch_ops=int(out["cpo"][i]),
                    prefetch_cycles=int(out["cpc"][i]),
                    prefetch_stall_cycles=int(out["cps"][i]),
                    writeback_regs=int(out["cwb"][i]),
                    activations=int(out["cact"][i]),
                    cycle_breakdown=bd)
    check_breakdown(bd, res.cycles, cfg.design, lane.workload.name)
    return res


# --------------------------------------------------------------------------
# Public API
# --------------------------------------------------------------------------

# Lanes per compiled run: bounds peak memory on huge sweeps while keeping
# each launch big enough to amortize dispatch.
_MAX_LANES = 512

# Lanes per sub-chunk within a shape group (see `_chunk_lanes`): small
# enough that a length-sorted group retires its short lanes early instead
# of carrying them to the group's slowest straggler, big enough that the
# lane-independent while-loop overhead stays a few percent of the launch.
_SUB_LANES = 8


def run_batch(jobs: Sequence[tuple[Workload, SimConfig]], *,
              fallback: bool = True) -> list:
    """Simulate many (workload, config) jobs; vectorized where supported.

    Returns one outcome per job, in order: a `SimResult`, or a
    `SimBudgetExceeded` *instance* (not raised) for lanes that blew their
    ``max_cycles`` watchdog — the sweep service records those as outcomes.
    Unsupported configs (see `batch_supported`) fall back to the scalar
    event-heap engine per job; pass ``fallback=False`` to get a
    `ValueError` instead.
    """
    outcomes: list = [None] * len(jobs)
    lanes: list[_Lane] = []
    idxs: list[int] = []
    scalar: list[int] = []
    with _phase("repro.sim.encode", "encode_s"):
        for i, (w, cfg) in enumerate(jobs):
            if batch_supported(cfg):
                parse_interval_strategy(cfg.interval_strategy)  # as engine
                code = _encode_plan(w, cfg)
                lanes.append(_Lane(w, cfg, code, _occupancy(w, cfg)))
                idxs.append(i)
            elif fallback:
                scalar.append(i)
            else:
                raise ValueError(
                    f"config not batch-supported (scheduler={cfg.scheduler!r}"
                    f", bank_model={cfg.bank_model!r}, trace={cfg.trace}, "
                    f"num_sms={cfg.num_sms})")
    for i in scalar:
        w, cfg = jobs[i]
        try:
            outcomes[i] = simulate(w, cfg)
        except SimBudgetExceeded as e:
            outcomes[i] = e
    for chunk, chunk_idxs in _chunk_lanes(lanes, idxs):
        for i, r in zip(chunk_idxs, _run_lanes(chunk)):
            outcomes[i] = r
    return outcomes


def _chunk_lanes(lanes: list[_Lane], idxs: list[int]):
    """Partition lanes into compile-friendly, utilization-friendly chunks.

    Lanes are grouped by the shape dimensions that dominate per-tick cost —
    active-list width (8 for the cached designs vs. all-resident for
    BL/RFC/Ideal), warp count, and the shared-RFC entry table — so a chunk
    of LTRF lanes pays (K, 8) scheduler scans instead of inheriting (K, 64)
    from one BL bystander.  Within a group, lanes are ordered by a crude
    run-length estimate: the lockstep while-loop runs until the *slowest*
    lane finishes, so co-scheduling similar-length lanes keeps the rest of
    the chunk from idling (and finished lanes from being dead weight).

    Groups are then cut into sub-chunks of at most `_SUB_LANES` lanes.
    Per-tick cost is nearly linear in the lane count (the K-independent
    loop overhead is small), so a finished lane that stays resident until
    the chunk's slowest lane retires costs almost as much as a live one —
    on the tracked sweep the longest lane runs ~5x the mean, and one big
    chunk burns that whole imbalance as dead weight.  Length-sorted
    sub-chunks retire short lanes in cheap early launches and leave the
    stragglers in small tail chunks, at the price of a few extra XLA
    shapes (compiled once, persistently cached)."""
    groups: dict[tuple, list[int]] = {}
    for j, ln in enumerate(lanes):
        cfg = ln.cfg
        sig = (_bucket(cfg.num_warps, 4), _bucket(_acap(ln), 2),
               cfg.rfc_entries if cfg.design == "RFC" else 0)
        groups.setdefault(sig, []).append(j)
    for sig, members in groups.items():
        members.sort(key=lambda j: _length_hint(lanes[j]))
        for lo in range(0, len(members), _SUB_LANES):
            part = members[lo: lo + _SUB_LANES]
            yield [lanes[j] for j in part], [idxs[j] for j in part]


def _length_hint(ln: _Lane) -> float:
    """Rough relative cycle count (ordering heuristic only)."""
    cfg = ln.cfg
    return (ln.code.n_pc * ln.occupancy
            * (cfg.mrf_cycles + cfg.mem_cycles * (1.0 - cfg.l1_hit_rate)))


def simulate_batch(jobs: Sequence[tuple[Workload, SimConfig]], *,
                   fallback: bool = True) -> list[SimResult]:
    """Like `run_batch` but raises the first `SimBudgetExceeded` (matching
    the scalar `simulate` contract)."""
    outcomes = run_batch(jobs, fallback=fallback)
    for r in outcomes:
        if isinstance(r, SimBudgetExceeded):
            raise r
    return outcomes


def simulate_one(workload: Workload, cfg: SimConfig) -> SimResult:
    """Single-job convenience wrapper over the batch path."""
    return simulate_batch([(workload, cfg)])[0]
