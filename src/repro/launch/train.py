"""Training driver: end-to-end fault-tolerant trainer on the local mesh.

``python -m repro.launch.train --arch tinyllama-1.1b --smoke --steps 50``

Production posture on a real fleet: the same builders compile against
``make_production_mesh()`` (see dryrun.py); here we train the reduced config
on the host devices so the full loop (data -> sharded step -> checkpoint ->
restore -> elastic reshard) is exercised for real.
"""
from __future__ import annotations

import argparse
import logging
import os
import tempfile
import time

import jax

from repro.checkpoint import Checkpointer
from repro.configs import get_arch, get_smoke
from repro.launch.compile_cache import enable_compile_cache
from repro.configs.base import ShapeConfig
from repro.data import DataConfig, PrefetchingLoader
from repro.distributed.fault import FaultConfig, FaultTolerantTrainer
from repro.distributed.sharding import default_rules, shardings_for
from repro.launch.mesh import make_host_mesh
from repro.optim.adamw import AdamWConfig
from repro.optim.compression import CompressionConfig
from repro.runtime.train_step import (
    batch_axes_for, batch_shardings, build_train_step, make_train_state,
)

log = logging.getLogger("repro.train")


def train(arch_id: str, smoke: bool = True, steps: int = 50,
          batch: int = 8, seq: int = 64, ckpt_dir: str | None = None,
          ckpt_every: int = 20, compress: bool = False,
          inject_failures: dict[int, int] | None = None,
          n_micro: int = 1, seed: int = 0, mesh=None):
    """Train ``steps`` steps on ``mesh`` (default: every local device).

    State is placed by the logical-axis rules, and every batch is put on
    the mesh (batch dimension over ``data``) before the step sees it."""
    cfg = get_smoke(arch_id) if smoke else get_arch(arch_id)
    shape = ShapeConfig("driver", seq, batch, "train")
    mesh = mesh if mesh is not None else make_host_mesh()
    rules = default_rules(mesh)

    state, state_axes = make_train_state(cfg, jax.random.PRNGKey(seed))
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    st_sh = shardings_for(rules, state_axes, shapes)
    state = jax.tree.map(lambda x, s: jax.device_put(x, s), state, st_sh)

    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=max(steps, 1))
    comp = CompressionConfig(enabled=True) if compress else None
    b_sh = batch_shardings(rules, batch_axes_for(cfg, "train"))
    jitted = jax.jit(
        build_train_step(cfg, rules, opt_cfg, comp, n_micro=n_micro),
        donate_argnums=(0,))

    def step_fn(state, batch):
        return jitted(state, jax.device_put(batch, b_sh))

    loader = PrefetchingLoader(cfg, shape, DataConfig(seed=seed + 1))
    ckpt = Checkpointer(
        ckpt_dir or os.path.join(tempfile.gettempdir(), f"repro_ckpt_{arch_id}"),
        keep=2)
    trainer = FaultTolerantTrainer(
        step_fn=step_fn, checkpointer=ckpt, loader=loader,
        cfg=FaultConfig(ckpt_every=ckpt_every,
                        inject_failures=inject_failures or {}))
    t0 = time.time()
    state, final_step, metrics = trainer.run(state, steps)
    dt = time.time() - t0
    losses = [float(m["loss"]) for m in metrics]
    loader.close()
    return {
        "final_step": final_step,
        "losses": losses,
        "restarts": trainer.restarts,
        "straggler_fallbacks": loader.straggler_fallbacks,
        "wall_s": dt,
        "state": state,
        "batch_shardings": b_sh,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--full", action="store_true", help="full (non-smoke) config")
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--n-micro", type=int, default=1)
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    enable_compile_cache()
    out = train(args.arch, smoke=not args.full, steps=args.steps,
                batch=args.batch, seq=args.seq, compress=args.compress,
                n_micro=args.n_micro)
    print(f"steps={out['final_step']} loss[0]={out['losses'][0]:.4f} "
          f"loss[-1]={out['losses'][-1]:.4f} wall={out['wall_s']:.1f}s "
          f"restarts={out['restarts']}")


if __name__ == "__main__":
    main()
