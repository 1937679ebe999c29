"""Ahead-of-time compiles for a described TPU v5e, at real widths.

Nothing runs on a chip here: the TPU compiler, which is installed with jax,
compiles for a 2x2 v5e topology that is described, not attached.  That
catches what Pallas interpret mode never checks (block alignment to the
(8, 128) tiling, scoped-VMEM limits, kernels that cannot lower) and what a
CPU run never sees (a step that does not fit 16 GB of HBM).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and under pytest-xdist
every worker imports this file.  Keep all such compiles in this one file.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

GiB = 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_hlo(fn, *args) -> str:
    txt = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in txt  # the Pallas kernel, not a fallback
    return txt


@pytest.mark.parametrize("M,K,N,dtype", [
    (4096, 1024, 3072, jnp.bfloat16),     # qwen3-0.6b MLP up-projection
    (4096, 14336, 4096, jnp.float32),     # 14336-wide K in f32
])
def test_ltrf_matmul_compiles(one_chip, M, K, N, dtype):
    from repro.kernels.ltrf_matmul.ops import ltrf_matmul

    _kernel_hlo(ltrf_matmul, _sds((M, K), dtype, one_chip),
                _sds((K, N), dtype, one_chip))


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention.ops import flash_attention

    q = _sds((1, 16, 2048, 128), jnp.bfloat16, one_chip)   # qwen3 GQA 16:8
    kv = _sds((1, 8, 2048, 128), jnp.bfloat16, one_chip)
    _kernel_hlo(flash_attention, q, kv, kv)


def test_ssd_scan_compiles(one_chip):
    from repro.configs import get_arch
    from repro.kernels.ssd_scan.ops import ssd_scan

    cfg = get_arch("mamba2-1.3b")
    H = cfg.ssm_expand * cfg.d_model // cfg.ssm_headdim
    B, S, P, N = 1, 2048, cfg.ssm_headdim, cfg.ssm_state
    assert (H, P, N, cfg.ssm_chunk) == (64, 64, 128, 256)
    _kernel_hlo(lambda *a: ssd_scan(*a, chunk=cfg.ssm_chunk),
                _sds((B, S, H, P), jnp.bfloat16, one_chip),
                _sds((B, S, H), jnp.float32, one_chip),
                _sds((H,), jnp.float32, one_chip),
                _sds((B, S, N), jnp.bfloat16, one_chip),
                _sds((B, S, N), jnp.bfloat16, one_chip))


def _batch_chunk(one_chip, kernels, designs, num_warps: int):
    """The batch engine's lockstep loop for one chunk, a lane for every
    (kernel, design) at Table-2 #7, compiled for one described v5e: (its
    initial state arrays, the lowered text, the compiled HLO text)."""
    from repro.sim import design_config
    from repro.sim.batch import _Lane, _build, _encode_plan, _occupancy, _run_jax
    from repro.workloads import get_workload

    lanes = []
    for name in kernels:
        for design in designs:
            w = get_workload(name)
            cfg = design_config(design, table2_config=7, num_warps=num_warps)
            lanes.append(_Lane(w, cfg, _encode_plan(w, cfg), _occupancy(w, cfg)))
    co, st = _build(lanes)
    with jax.enable_x64(True):
        args = [{k: _sds(v.shape, v.dtype, one_chip) for k, v in d.items()}
                for d in (co, st)]
        lowered = jax.jit(_run_jax).lower(*args)
        return st, lowered.as_text(), lowered.compile().as_text()


def test_batch_engine_chunk_compiles(one_chip):
    """One 4-lane chunk of the jitted lockstep simulator, with int64 state
    and no floating point (a TPU's f64 is emulated and not IEEE-exact)."""
    _, lowered, compiled = _batch_chunk(one_chip, ("srad", "kmeans"),
                                        ("BL", "RFC"),  # token-bucket designs
                                        num_warps=16)
    assert compiled
    assert "f64" not in lowered


def _while_bodies(hlo: str) -> dict[str, list[str]]:
    """The instruction lines of every while-loop body in compiled HLO
    text, by computation name."""
    comps, name = {}, None
    for ln in hlo.splitlines():
        if ln and not ln[0].isspace() and ln.endswith("{"):
            name = ln.split()[1] if ln.startswith("ENTRY") else ln.split()[0]
            comps[name.lstrip("%")] = []
        elif ln.startswith("}"):
            name = None
        elif name:
            comps[name.lstrip("%")].append(ln)
    bodies = re.findall(r"\swhile\(.*?body=%?([\w.\-]+)", hlo)
    return {b: comps[b] for b in bodies}


@pytest.mark.parametrize("design", ["BL", "LTRF"])
def test_batch_engine_loop_keeps_value_planes_in_place(one_chip, design):
    """The sim-fig14 bucket (8 lanes over its 8 kernels, Table-2 #7, 64
    warps) compiled for a v5e: neither (K, W, regs+preds) value plane is
    copied inside the tick loop or either activation loop.  A copy there is
    a relayout of the whole plane on every tick; a trailing (time, flag)
    pair axis made 14 in the tick body and 2 in each activation body,
    each padded 64x to the TPU's 128-lane tiles."""
    st, _, hlo = _batch_chunk(
        one_chip, ("pathfinder", "kmeans", "bfs", "btree", "nw", "dct8x8",
                   "mri-q", "gaussian"), (design,), num_warps=64)
    # (K, W, RVW) of any element type (an s64 plane lowers to u32 halves on
    # the TPU), with or without trailing axes
    plane = ",".join(map(str, st["rv"].shape[:3]))
    copy = re.compile(r"= \(?\w+\[%s[\],][^=]*\scopy(-start)?\(" % plane)
    bodies = _while_bodies(hlo)
    assert len(bodies) == 3, list(bodies)     # tick + two activation loops
    found = {b: [ln.strip()[:120] for ln in lines if copy.search(ln)]
             for b, lines in bodies.items()}
    assert not any(found.values()), found
    assert st["rm"].shape == st["rv"].shape


def _qwen3_train_step(topo, batch_rows: int, seq: int):
    """The full-width, full-depth qwen3-0.6b AdamW step on one described
    v5e, compiled: (compiled executable, its total memory in bytes)."""
    from repro.configs import get_arch

    return _train_step(topo, get_arch("qwen3-0.6b"), batch_rows, seq)


def _train_step(topo, cfg, batch_rows: int, seq: int):
    """``cfg``'s AdamW step on one described v5e, compiled: (compiled
    executable, its total memory in bytes)."""
    from repro.distributed.sharding import default_rules, shardings_for
    from repro.launch.mesh import make_host_mesh
    from repro.optim.adamw import AdamWConfig
    from repro.runtime.train_step import (
        batch_axes_for, batch_shardings, build_train_step, make_train_state,
    )

    rules = default_rules(make_host_mesh(devices=topo.devices[:1]))
    axes = {}

    def init(key):
        state, axes["state"] = make_train_state(cfg, key)
        return state

    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    st_sh = shardings_for(rules, axes["state"], shapes)
    state = jax.tree.map(lambda s, sh: _sds(s.shape, s.dtype, sh),
                         shapes, st_sh)
    b_sh = batch_shardings(rules, batch_axes_for(cfg, "train"))
    batch = {k: _sds((batch_rows, seq), jnp.int32, sh)
             for k, sh in b_sh.items()}
    assert all(isinstance(s, NamedSharding) for s in b_sh.values())
    step = build_train_step(cfg, rules, AdamWConfig(total_steps=3))
    compiled = jax.jit(step, donate_argnums=(0,)).lower(state, batch).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return compiled, total


def test_qwen3_train_step_fits_one_chip(topo):
    """The full-width, full-depth qwen3-0.6b step (batch 4 x seq 512, AdamW)
    that `chip_smoke.py` trains fits one v5e's 16 GB with room to spare."""
    _, total = _qwen3_train_step(topo, 4, 512)
    assert total < 14 * GiB, total / GiB


def test_qwen3_train_step_uses_flash_kernel(topo):
    """At the train cell's shape (2 x 4096) the step's attention is the
    fused flash kernel: its Mosaic calls are in the HLO, no (B, H, q-block,
    S) float32 score tile is, and the step fits the chip.

    The compiler's total here (about 14.87 GiB, the same as the XLA path's)
    is set by the 152k-wide head's float32 logits (2 x 4095 x 151936 x 4 B,
    4.96 GB), not by attention; a chip reads 8.41 GiB in use at the peak."""
    from repro.models import layers

    layers.reset_attn_stats()
    compiled, total = _qwen3_train_step(topo, 2, 4096)
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= 3       # forward, dq, dk/dv
    assert "f32[2,16,512,4096]" not in hlo
    assert layers.ATTN_STATS["kernel_calls"] >= 1
    assert layers.ATTN_STATS["xla_calls"] == 0
    assert total < 16 * GiB, total / GiB


def test_granite_train_step_uses_flash_and_ssd_kernels(topo):
    """The granite-h-train-8k step (Granite-4.0-H-Micro's first 10 layers at
    published widths, 1 x 8192, AdamW) on one described v5e: the NoPE
    attention layer (head width 64) takes the flash kernel and every
    Mamba-2 layer the SSD chunk kernel, no (B, chunks, Q, Q, H) float32
    tensor is in the step, and the compiler's total fits 16 GiB (about
    15.6 GiB: the state is 8.9 GiB)."""
    import dataclasses

    from repro.configs import get_arch
    from repro.models import layers, mamba2

    full = get_arch("granite-4.0-h-micro")
    cfg = dataclasses.replace(full, n_layers=10,
                              layer_types=full.layer_types[:10])
    assert cfg.runs() == [("mamba", 5), ("attention", 1), ("mamba", 4)]
    layers.reset_attn_stats()
    mamba2.reset_ssd_stats()
    compiled, total = _train_step(topo, cfg, 1, 8192)
    hlo = compiled.as_text()
    calls = [ln.split(" = ")[0].strip() for ln in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    # flash: forward, its remat recompute, dq, dk/dv; SSD: the forward and
    # its recompute in each of the two Mamba-2 runs
    assert sum("flash_attention" in c for c in calls) == 4, calls
    assert sum(c.startswith("%ssd") for c in calls) == 4, calls
    assert (layers.ATTN_STATS["xla_calls"], mamba2.SSD_STATS["xla_calls"]) \
        == (0, 0)
    assert "f32[1,32,256,256,64]" not in hlo
    assert total < 16 * GiB, total / GiB
