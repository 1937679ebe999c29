"""Cells, configurations, mixes, limits and metrics are found by name, and
a new one is found from new files alone."""
from __future__ import annotations

import json
import shutil

import pytest

from bench import cells


def test_every_cell_resolves_with_its_pieces():
    bench = cells.load_benchmark()
    for w in bench["workloads"]:
        cell = cells.resolve(w["name"])
        assert cell.config_name == w["config"]
        assert cell.driver == cell.traffic["driver"]
        cells.load_driver(cell.driver)
        names = {e["name"] for e in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer, w["name"]
        for e in cell.per_layer:
            assert e["moves"] in names
            assert callable(cells.load_reader(e["name"]))


@pytest.mark.parametrize("name", ["no-such-cell", "sim-fig14 ", ""])
def test_unknown_cell_fails(name):
    with pytest.raises(cells.UnknownName):
        cells.resolve(name)


@pytest.mark.parametrize("call", [
    lambda: cells.load_driver("no_such_driver"),
    lambda: cells.load_reader("no_such.metric"),
    lambda: cells.load_peaks("TPU v0 imaginary"),
])
def test_unknown_driver_metric_and_device_fail(call):
    with pytest.raises(cells.UnknownName):
        call()


def test_peaks_of_the_v5e():
    p = cells.load_peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def _copy_benchmark(tmp_path):
    shutil.copy(cells.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(cells.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return json.loads((tmp_path / "BENCHMARK.json").read_text())


def test_new_metric_mix_and_cell_need_only_new_files(tmp_path):
    """A throwaway per-layer metric, traffic mix, limits file and cell are
    added as files and entries; no existing file under bench/ changes."""
    bench = _copy_benchmark(tmp_path)
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    (tmp_path / "bench" / "metrics" / "throwaway_share.train.py").write_text(
        "def read(rec):\n    return 42.0\n")
    mix = json.loads((tmp_path / "bench" / "traffic" /
                      "train-4k.json").read_text())
    (tmp_path / "bench" / "traffic" / "train-1k.json").write_text(
        json.dumps({**mix, "seq": 1024}))
    (tmp_path / "bench" / "limits" / "qwen3-train-1k.json").write_text(
        (tmp_path / "bench" / "limits" / "qwen3-train-4k.json").read_text())
    bench["workloads"].append({"name": "qwen3-train-1k",
                               "config": "qwen3-0.6b", "traffic": "train-1k",
                               "chips": 1, "why": "throwaway"})
    bench["per_layer"].append({"name": "throwaway_share.train", "unit": "%",
                               "better": "lower", "source": "host_clock",
                               "layer": "train driver",
                               "moves": "train_tokens_per_s",
                               "workloads": ["qwen3-train-1k"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cells.resolve("qwen3-train-1k", root=tmp_path)
    assert cell.traffic["seq"] == 1024
    assert [e["name"] for e in cell.per_layer] == ["throwaway_share.train"]
    assert cells.load_reader("throwaway_share.train", root=tmp_path)({}) == 42
    assert all(p.read_bytes() == b for p, b in before.items())
