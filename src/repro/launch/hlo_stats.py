"""Side-effect-free helpers shared by dryrun and the tests.

(dryrun.py sets XLA_FLAGS at import, so anything that does NOT want 512 fake
devices must import from here instead.)
"""
import re

import jax

_SHAPE_RE = re.compile(
    r"(f64|f32|f16|bf16|f8e4m3fn|f8e5m2|s64|s32|s16|s8|u64|u32|u16|u8|pred)\[([\d,]*)\]")
_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
          "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4,
          "u16": 2, "u8": 1, "pred": 1}
_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")


def _shape_bytes(sig: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(sig):
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _BYTES[dt]
    return total


def collective_stats(hlo_text: str) -> dict:
    """Sum output-shape bytes of every collective op in post-SPMD HLO."""
    stats = {op: {"count": 0, "bytes": 0} for op in _COLL_OPS}
    pat = re.compile(r"=\s+((?:\([^)]*\))|(?:\S+))\s+(all-gather|all-reduce|"
                     r"reduce-scatter|all-to-all|collective-permute)")
    for line in hlo_text.splitlines():
        m = pat.search(line)
        if not m:
            continue
        sig, op = m.group(1), m.group(2)
        stats[op]["count"] += 1
        stats[op]["bytes"] += _shape_bytes(sig)
    stats["total_bytes"] = sum(v["bytes"] for v in stats.values()
                               if isinstance(v, dict))
    stats["total_count"] = sum(v["count"] for v in stats.values()
                               if isinstance(v, dict))
    return stats


def _eval_shape_with_axes(fn, *args):
    """eval_shape a (tree, axes) returning fn; captures axes eagerly."""
    box = {}

    def wrapped(*a):
        tree, axes = fn(*a)
        box["axes"] = axes
        return tree

    shapes = jax.eval_shape(wrapped, *args)
    return shapes, box["axes"]


def _mem_analysis(compiled) -> dict:
    try:
        m = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(m, k, None)
        if v is not None:
            out[k] = int(v)
    out["total_hbm_bytes"] = (out.get("argument_size_in_bytes", 0)
                              + out.get("output_size_in_bytes", 0)
                              + out.get("temp_size_in_bytes", 0)
                              - out.get("alias_size_in_bytes", 0))
    return out


def _cost_analysis(compiled) -> dict:
    try:
        c = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(c, (list, tuple)):
        c = c[0] if c else {}
    return {k: float(v) for k, v in c.items()
            if isinstance(v, (int, float)) and (
                k in ("flops", "bytes accessed", "optimal_seconds")
                or k.startswith("bytes accessed"))}
