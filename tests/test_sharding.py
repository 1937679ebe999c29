"""Unit tests for logical sharding rules, shape-aware shardings and the
dry-run's HLO collective parser."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, cell_is_runnable, get_arch, input_specs
from repro.distributed.sharding import (
    constrain, default_rules, shardings_for, use_rules,
)
from repro.launch.hlo_stats import _shape_bytes, collective_stats
from repro.launch.mesh import make_host_mesh


def rules():
    return default_rules(make_host_mesh())


def test_shape_safe_drops_nondivisible():
    r = rules()  # mesh (1,1) on one device: sizes 1, everything divides
    sh = shardings_for(r, {"w": ("embed", "ffn")},
                       {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)})
    assert sh["w"].spec == P("data", "model")


def test_shape_safe_dedups_mesh_axes():
    r = rules()
    # experts and ffn both map to 'model': only the first may take it
    sh = shardings_for(
        r, {"w": ("experts", "embed", "ffn")},
        {"w": jax.ShapeDtypeStruct((4, 8, 8), jnp.float32)})
    spec = sh["w"].spec
    flat = [s for s in spec if s == "model"]
    assert len(flat) == 1
    assert spec[0] == "model"  # first dim wins


def test_kv_fallback_to_head_dim():
    import numpy as np
    from jax.sharding import Mesh
    # fake 4-wide model axis via an abstract mesh
    devs = np.array(jax.devices() * 4).reshape(1, 4) if len(jax.devices()) == 1 \
        else None
    if devs is None:
        pytest.skip("multi-device host")
    mesh = Mesh(devs, ("data", "model"))
    r = default_rules(mesh)
    sh = shardings_for(
        r, {"k": ("layers", "act_batch", None, "act_kv", "act_hd")},
        {"k": jax.ShapeDtypeStruct((2, 8, 16, 2, 8), jnp.bfloat16)})
    spec = sh["k"].spec
    assert spec[3] is None          # kv=2 can't take model=4
    assert spec[4] == "model"       # head_dim=8 takes it instead


def test_constrain_noop_without_rules():
    x = jnp.ones((4, 4))
    assert constrain(x, ("act_batch", None)) is x


def test_constrain_applies_with_rules():
    with use_rules(rules()):
        y = constrain(jnp.ones((4, 4)), ("act_batch", "act_embed"))
        assert y.shape == (4, 4)


def test_layouts_exist():
    m = make_host_mesh()
    for layout in ("2d", "fsdp_pure", "ep_only", "ep_dp"):
        r = default_rules(m, layout=layout)
        assert r.axis("batch") is not None or layout == "2d"


# ---------------------------------------------------------------------------
# dry-run parsing helpers
# ---------------------------------------------------------------------------

def test_shape_bytes():
    assert _shape_bytes("f32[4,4]") == 64
    assert _shape_bytes("bf16[2,3]") == 12
    assert _shape_bytes("(f32[2], s8[4])") == 12
    assert _shape_bytes("pred[8]") == 8


def test_collective_stats_parsing():
    hlo = """
      %ag = bf16[16,128]{1,0} all-gather(%x), dimensions={0}
      %ar = (f32[4,4]{1,0}, f32[4,4]{1,0}) all-reduce(%a, %b), to_apply=%sum
      %cp = f32[8]{0} collective-permute(%y), source_target_pairs={{0,1}}
      %notacoll = f32[8]{0} add(%y, %y)
    """
    st = collective_stats(hlo)
    assert st["all-gather"]["count"] == 1
    assert st["all-gather"]["bytes"] == 16 * 128 * 2
    assert st["all-reduce"]["count"] == 1
    assert st["all-reduce"]["bytes"] == 2 * 16 * 4
    assert st["collective-permute"]["count"] == 1
    assert st["total_count"] == 3


# ---------------------------------------------------------------------------
# cell definitions
# ---------------------------------------------------------------------------

def test_40_cells_defined():
    from repro.configs import ARCH_IDS, all_cells
    cells = all_cells()
    assert len(cells) == 44
    skips = [c for c in cells if not c[2]]
    assert len(skips) == 9  # 9 archs with full attention skip long_500k
    assert all(s[1] == "long_500k" for s in skips)
    runnable = [c for c in cells if c[2]]
    assert len(runnable) == 35


@pytest.mark.parametrize("arch_id", ["phi3-medium-14b", "musicgen-large",
                                     "llava-next-34b", "mamba2-1.3b"])
@pytest.mark.parametrize("shape", ["train_4k", "decode_32k"])
def test_input_specs_shapes(arch_id, shape):
    cfg = get_arch(arch_id)
    specs = input_specs(cfg, SHAPES[shape])
    B = SHAPES[shape].global_batch
    if SHAPES[shape].is_decode:
        if cfg.family == "audio":
            assert specs["tokens"].shape == (B, cfg.n_codebooks, 1)
        else:
            assert specs["tokens"].shape == (B, 1)
    else:
        if cfg.family == "vlm":
            total = specs["tokens"].shape[1] + specs["patches"].shape[1]
            assert total == SHAPES[shape].seq_len
        elif cfg.family == "audio":
            assert specs["codes"].shape == (B, cfg.n_codebooks,
                                            SHAPES[shape].seq_len)
        else:
            assert specs["tokens"].shape == (B, SHAPES[shape].seq_len)
