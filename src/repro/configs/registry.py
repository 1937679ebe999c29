"""Architecture registry: --arch <id> resolution + smoke variants."""
from __future__ import annotations

import importlib

from .base import ArchConfig, SHAPES, ShapeConfig, cell_is_runnable, input_specs

ARCH_IDS = [
    "phi3-medium-14b",
    "tinyllama-1.1b",
    "granite-20b",
    "qwen3-0.6b",
    "granite-moe-3b-a800m",
    "dbrx-132b",
    "llava-next-34b",
    "musicgen-large",
    "mamba2-1.3b",
    "zamba2-1.2b",
    "granite-4.0-h-micro",
]

_MODULES = {a: a.replace("-", "_").replace(".", "_") for a in ARCH_IDS}


def get_arch(arch_id: str) -> ArchConfig:
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch_id]}")
    return mod.CONFIG


def get_smoke(arch_id: str) -> ArchConfig:
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch_id]}")
    return mod.smoke()


def all_cells() -> list[tuple[str, str, bool, str]]:
    """[(arch_id, shape_name, runnable, skip_reason)] for every cell."""
    out = []
    for a in ARCH_IDS:
        cfg = get_arch(a)
        for s in SHAPES.values():
            ok, why = cell_is_runnable(cfg, s)
            out.append((a, s.name, ok, why))
    return out
