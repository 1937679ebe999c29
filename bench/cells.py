"""Finding a cell's pieces by name.

Everything that belongs to one configuration, one traffic mix, one cell's
limits or one per-layer metric sits in a file of its own, found from the
names in ``BENCHMARK.json``:

* ``bench/configs/<config>.json`` (the entry's ``file``): the configuration
  as it is run, naming its ``driver``;
* ``bench/traffic/<traffic>.json``: the mix's parameters, for that driver;
* ``bench/limits/<cell>.json``: the limit of each number the check compares;
* ``bench/drivers/<driver>.py``: set-up, window and check of one kind of
  system;
* ``bench/metrics/<metric>.py``: a ``read(rec)`` that reduces a run's
  counters and trace to the metric, or returns ``None``.

A later change adds a configuration, a mix, a cell or a metric by adding
files and entries; none of these needs an edit to a file that is here.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import pathlib
from dataclasses import dataclass

ROOT = pathlib.Path(__file__).resolve().parents[1]


class UnknownName(LookupError):
    """A name in a command or in BENCHMARK.json that has no file or entry."""


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict
    end_to_end: tuple      # BENCHMARK.json entries this cell reports
    per_layer: tuple

    @property
    def driver(self) -> str:
        return self.config["driver"]


def _json(path: pathlib.Path, what: str) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise UnknownName(f"no {what} file {path}") from None


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json", "benchmark")


def _entry(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    known = ", ".join(e["name"] for e in entries)
    raise UnknownName(f"unknown {what} {name!r} (known: {known})")


def _applies(entry: dict, cell: str, reported: set | None = None) -> bool:
    """A metric with a ``workloads`` list applies to the cells it names;
    without one, an end-to-end metric applies to every cell, and a
    per-layer metric to every cell that reports the metric it moves."""
    if "workloads" in entry:
        return cell in entry["workloads"]
    return reported is None or entry["moves"] in reported


def resolve(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell named ``name`` with every file it needs read."""
    bench = load_benchmark(root)
    w = _entry(bench["workloads"], name, "workload")
    c = _entry(bench["configs"], w["config"], "configuration")
    config = _json(root / c["file"], "configuration")
    traffic = _json(root / "bench" / "traffic" / f"{w['traffic']}.json",
                    "traffic")
    if traffic["driver"] != config["driver"]:
        raise UnknownName(f"traffic {w['traffic']!r} is for driver "
                          f"{traffic['driver']!r}, configuration "
                          f"{w['config']!r} for {config['driver']!r}")
    limits = _json(root / "bench" / "limits" / f"{name}.json", "limits")
    e2e = tuple(e for e in bench["end_to_end"] if _applies(e, name))
    reported = {e["name"] for e in e2e}
    per = tuple(e for e in bench["per_layer"]
                if _applies(e, name, reported))
    return Cell(name=name, chips=w["chips"], config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                limits=limits, end_to_end=e2e, per_layer=per)


def load_driver(kind: str):
    """The driver module for one kind of configuration."""
    try:
        return importlib.import_module(f"bench.drivers.{kind}")
    except ModuleNotFoundError as e:
        if e.name == f"bench.drivers.{kind}":
            raise UnknownName(f"no driver {kind!r}") from None
        raise


def load_reader(metric: str, root: pathlib.Path = ROOT):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise UnknownName(f"no reader {path} for metric {metric!r}")
    spec = importlib.util.spec_from_file_location(
        "bench.metrics." + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(kind: str, root: pathlib.Path = ROOT) -> dict:
    """Published peaks of one chip, by JAX's ``device_kind``."""
    table = _json(root / "bench" / "peaks.json", "peaks")["devices"]
    if kind not in table:
        raise UnknownName(f"no peaks for device kind {kind!r} in "
                          f"bench/peaks.json (known: {', '.join(table)})")
    return table[kind]
