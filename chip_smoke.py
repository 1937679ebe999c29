"""Bring-up check: the system's main paths, end to end, on one TPU chip.

    python chip_smoke.py               # one chip: sim, train, serve, kernels
    python chip_smoke.py --four-chips  # four chips: sharded train vs one chip

One process drives the chip; nothing here starts another.  Every phase
prints one JSON line, and the last line of stdout is the verdict::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Phases (one chip):

* ``sim``: the jitted lockstep batch engine (`repro.sim.batch.run_batch`,
  no host fallback) on 20 jobs (srad and kmeans x BL, RFC, LTRF,
  LTRF_plus and Ideal x 8 and 16 warps), two traced-kernel jobs and a
  200-cycle watchdog job.  Every outcome must equal the host event-heap
  engine's, every counter and the whole cycle breakdown, with no tolerance.
* ``train``: three AdamW steps of qwen3-0.6b at full width and depth
  (batch 4 x seq 512) through `repro.launch.train.train`.  Losses are
  finite, and the first is within 0.5 of ln(vocab) + 1/2, the loss that
  the random init implies (`expected_first_loss`).
* ``serve``: eight requests through `repro.launch.serve.serve` at full
  width; all complete and no KV page leaks.
* ``kernels``: the three Pallas kernels, compiled for the chip, against
  their float32 oracles under ``highest`` matmul precision (flash
  attention's gradients too).

``--four-chips`` runs only the three training steps on a (data=2, model=2)
mesh and the same steps on a 1x1 mesh on the first chip, and compares the
losses step by step.

Exits non-zero when JAX finds no TPU, or when any phase fails.  Weights and
data are random, made from fixed seeds.  Results are computed, never read
from a cache of results; JAX's compilation cache follows
`repro.launch.compile_cache`.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import tempfile
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
ARCH = "qwen3-0.6b"
TRAIN = dict(steps=3, batch=4, seq=512)

# (name, shapes) at the widths of the model configurations
KERNEL_SHAPES = {
    "ltrf_matmul": [(4096, 1024, 3072), (4096, 14336, 4096)],   # (M, K, N)
    "flash_attention": [(1, 16, 8, 2048, 128)],                 # (B, H, KV, S, d)
    "ssd_scan": [(1, 2048, 64, 64, 128, 256)],                  # (B, S, H, P, N, Q)
}
# float32 tolerances of tests/test_kernels.py
KERNEL_TOL = {"ltrf_matmul": (2e-4, 1e-4), "flash_attention": (2e-4, 1e-4),
              "ssd_scan": (3e-3, 3e-3)}


class CompileLog:
    """Counts XLA backend compiles (and their seconds) as JAX reports them."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n, self.s = 0, 0.0

        def on_event(event, seconds, **_):
            if event == self.EVENT:
                self.n += 1
                self.s += seconds

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def snapshot(self):
        return self.n, self.s


def phase_sim() -> dict:
    from dataclasses import replace

    from repro.sim import SimBudgetExceeded, design_config, simulate
    from repro.sim.batch import reset_run_stats, run_batch
    from repro.workloads import get_workload

    jobs = [(get_workload(n), design_config(d, table2_config=7, num_warps=nw))
            for n in ("srad", "kmeans")
            for d in ("BL", "RFC", "LTRF", "LTRF_plus", "Ideal")
            for nw in (8, 16)]
    jobs += [(get_workload("traced_matmul"),
              design_config(d, table2_config=7, num_warps=16))
             for d in ("LTRF", "LTRF_conf")]
    jobs.append((jobs[0][0], replace(jobs[0][1], max_cycles=200)))
    stats = reset_run_stats()
    got = run_batch(jobs, fallback=False)
    mismatched = []
    for (w, cfg), out in zip(jobs, got):
        try:
            want = simulate(w, cfg)
        except SimBudgetExceeded as e:
            want = e
        same = (out.args == want.args
                if isinstance(want, SimBudgetExceeded)
                and isinstance(out, SimBudgetExceeded) else out == want)
        if not same:
            mismatched.append(f"{w.name}/{cfg.design}/w{cfg.num_warps}")
    return {"jobs": len(jobs), "mismatched": mismatched,
            "watchdog_budget": isinstance(got[-1], SimBudgetExceeded),
            "batch_compiles": stats["compiles"],
            "batch_compile_s": stats["compile_s"],
            "batch_run_s": stats["run_s"], "ticks": stats["ticks"],
            "ok": not mismatched and isinstance(got[-1], SimBudgetExceeded)}


def _train(smoke: bool, mesh, **kw) -> dict:
    from repro.launch.train import train

    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as d:
        return train(ARCH, smoke=smoke, ckpt_dir=d, mesh=mesh, **TRAIN, **kw)


def expected_first_loss(vocab: int) -> float:
    """Cross-entropy of the untrained model: the head's 1/sqrt(d_model)
    init over unit-RMS features gives unit-variance logits, and
    E[logsumexp] of V unit-normal logits is ln(V) + 1/2."""
    return math.log(vocab) + 0.5


def _first_loss_ok(losses, vocab) -> bool:
    return (all(math.isfinite(v) for v in losses)
            and abs(losses[0] - expected_first_loss(vocab)) < 0.5)


def phase_train(smoke: bool = False) -> dict:
    import jax

    from repro.configs import get_arch, get_smoke

    vocab = (get_smoke(ARCH) if smoke else get_arch(ARCH)).vocab
    out = _train(smoke, None)
    stats = jax.devices()[0].memory_stats() or {}
    return {"losses": out["losses"], "ln_vocab": math.log(vocab),
            "expected_first_loss": expected_first_loss(vocab),
            "restarts": out["restarts"], "wall_s": out["wall_s"],
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "ok": (out["final_step"] == TRAIN["steps"]
                   and out["restarts"] == 0
                   and _first_loss_ok(out["losses"], vocab))}


def phase_serve(smoke: bool = False) -> dict:
    from repro.launch.serve import serve

    out = serve(ARCH, smoke=smoke, n_requests=8)
    return {**out, "ok": (out["completed"] == out["requests"] == 8
                          and out["pages_leaked"] == 0)}


def phase_kernels(shapes=KERNEL_SHAPES, interpret: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.ltrf_matmul.ops import ltrf_matmul
    from repro.kernels.ltrf_matmul.ref import matmul_ref
    from repro.kernels.ssd_scan.ops import ssd_scan
    from repro.kernels.ssd_scan.ref import ssd_ref

    def rnd(i, shape, scale=1.0):
        return jax.random.normal(jax.random.PRNGKey(i), shape) * scale

    def err(got, want, tol):
        got, want = np.asarray(got), np.asarray(want)
        rtol, atol = tol
        excess = np.abs(got - want) - (atol + rtol * np.abs(want))
        return {"max_abs_err": float(np.abs(got - want).max()),
                "ok": bool(np.isfinite(got).all() and (excess <= 0).all())}

    res = []
    with jax.default_matmul_precision("highest"):
        for M, K, N in shapes["ltrf_matmul"]:
            # weights at the fan-in init scale, so outputs are O(1) as the
            # tests' absolute tolerance assumes (unit weights at K=14336
            # give outputs near 120, whose f32 rounding alone exceeds it)
            x, w = rnd(0, (M, K)), rnd(1, (K, N), K ** -0.5)
            got = ltrf_matmul(x, w, interpret=interpret)
            res.append({"kernel": "ltrf_matmul", "shape": [M, K, N],
                        **err(got, matmul_ref(x, w),
                              KERNEL_TOL["ltrf_matmul"])})
        for B, H, KV, S, d in shapes["flash_attention"]:
            q, k, v = (rnd(2, (B, H, S, d)), rnd(3, (B, KV, S, d)),
                       rnd(4, (B, KV, S, d)))
            got = flash_attention(q, k, v, interpret=interpret)
            res.append({"kernel": "flash_attention", "shape": [B, H, KV, S, d],
                        **err(got, attention_ref(q, k, v),
                              KERNEL_TOL["flash_attention"])})
            # the backward kernels: (dq, dk, dv) against the oracle's
            do = rnd(9, (B, H, S, d))
            grads = [jax.vjp(f, q, k, v)[1](do) for f in (
                lambda *a: flash_attention(*a, interpret=interpret),
                attention_ref)]
            errs = [err(g, w, KERNEL_TOL["flash_attention"])
                    for g, w in zip(*grads)]
            res.append({"kernel": "flash_attention_grad",
                        "shape": [B, H, KV, S, d],
                        "max_abs_err": max(e["max_abs_err"] for e in errs),
                        "ok": all(e["ok"] for e in errs)})
        for B, S, H, P, N, Q in shapes["ssd_scan"]:
            x = rnd(5, (B, S, H, P), 0.5)
            dt = jax.nn.softplus(rnd(6, (B, S, H)))
            A = -jnp.exp(jnp.linspace(0.0, 1.5, H))
            Bm, Cm = rnd(7, (B, S, N), 0.3), rnd(8, (B, S, N), 0.3)
            y, fin = ssd_scan(x, dt, A, Bm, Cm, chunk=Q, interpret=interpret)
            yr, finr = ssd_ref(x, dt, A, Bm, Cm)
            ey = err(y, yr, KERNEL_TOL["ssd_scan"])
            ef = err(fin, finr, KERNEL_TOL["ssd_scan"])
            res.append({"kernel": "ssd_scan", "shape": [B, S, H, P, N, Q],
                        "max_abs_err": max(ey["max_abs_err"],
                                           ef["max_abs_err"]),
                        "ok": ey["ok"] and ef["ok"]})
    return {"results": res, "ok": all(r["ok"] for r in res)}


def phase_four_chips(smoke: bool = False) -> dict:
    """The same steps on a 1x1 mesh (first chip) and a 2x2 mesh."""
    import jax

    from repro.launch.mesh import make_host_mesh

    devs = jax.devices()
    if len(devs) != 4:
        raise RuntimeError(f"--four-chips needs 4 devices, found {len(devs)}")
    one = _train(smoke, make_host_mesh(devices=devs[:1]))["losses"]
    out = _train(smoke, make_host_mesh(model=2, devices=devs))
    leaves = jax.tree.leaves(out.pop("state"))
    on_all = all(len(x.sharding.device_set) == 4 for x in leaves)
    split = sum(x.sharding.shard_shape(x.shape) != x.shape for x in leaves)
    tokens = out["batch_shardings"]["tokens"]
    batch_split = (len(tokens.device_set) == 4
                   and tokens.shard_shape((TRAIN["batch"], TRAIN["seq"]))[0]
                   == TRAIN["batch"] // 2)
    four = out["losses"]
    # bf16 parameters and a different reduction order: agree to 1%
    agree = [abs(a - b) <= 1e-2 * abs(b) for a, b in zip(four, one)]
    return {"losses_1x1": one, "losses_2x2": four,
            "state_leaves": len(leaves), "state_leaves_split": split,
            "state_on_4_chips": on_all, "batch_split_over_data": batch_split,
            "ok": (len(one) == len(four) == TRAIN["steps"] and all(agree)
                   and on_all and split > 0 and batch_split
                   and out["restarts"] == 0)}


def run_phases(phases, log: CompileLog) -> bool:
    ok = True
    for name, fn in phases:
        n0, s0 = log.snapshot()
        t0 = time.perf_counter()
        try:
            rec = fn()
        except Exception as e:  # noqa: BLE001 - reported, and fails the run
            traceback.print_exc()
            rec = {"ok": False, "error": f"{type(e).__name__}: {e}"}
        n1, s1 = log.snapshot()
        rec = {"phase": name, "ok": rec.pop("ok"), "seconds":
               time.perf_counter() - t0, "compiles": n1 - n0,
               "compile_s": s1 - s0, **rec}
        print(json.dumps(rec, default=str), flush=True)
        ok = ok and rec["ok"]
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="sharded training on a 2x2 mesh vs one chip only")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"ok": False, "error": f"no TPU: JAX found "
                          f"{dev.platform!r}"}))
        return 2
    sys.path[:0] = [str(ROOT / "src")]
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(json.dumps({"ok": False, "error": f"repo not found: {e}"}))
        return 2
    enable_compile_cache()
    log = CompileLog()
    if args.four_chips:
        phases = [("four_chips", phase_four_chips)]
    else:
        phases = [("sim", phase_sim), ("train", phase_train),
                  ("serve", phase_serve), ("kernels", phase_kernels)]
    ok = run_phases(phases, log)
    print(json.dumps({"ok": ok, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
