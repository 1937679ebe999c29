"""Doc-consistency checks: the docs/ tree cannot silently go stale.

* every `SimConfig` field and result counter must be documented in
  docs/configuration.md (new knobs cannot land undocumented);
* every `designs.py` knob — `design_config` parameter, design name,
  scheduler/bank-model/renumber mode, Table-2 memory technology — must be
  documented;
* every relative markdown link in README.md and docs/ must resolve (this is
  the CI "markdown link check" — no network, external URLs are skipped).
"""
from __future__ import annotations

import dataclasses
import inspect
import pathlib
import re

import pytest

from repro.sim import (
    BANK_MODELS, DESIGNS, INTERVAL_STRATEGIES, RENUMBER_MODES, SCHEDULERS,
    SimConfig, SimResult,
)
from repro.sim.designs import TABLE2, baseline_config, design_config

ROOT = pathlib.Path(__file__).resolve().parent.parent
DOCS = ROOT / "docs"
CONFIG_DOC = DOCS / "configuration.md"

MARKDOWN_FILES = sorted([ROOT / "README.md", *DOCS.glob("*.md")])


def test_docs_tree_exists():
    for name in ("architecture.md", "simulator.md", "configuration.md",
                 "compiler.md", "serving.md", "observability.md",
                 "analytical.md"):
        assert (DOCS / name).is_file(), f"docs/{name} missing"


def test_every_simconfig_field_documented():
    doc = CONFIG_DOC.read_text()
    missing = [f.name for f in dataclasses.fields(SimConfig)
               if f"`{f.name}`" not in doc]
    assert not missing, (
        f"SimConfig fields missing from docs/configuration.md: {missing} "
        "(document new knobs before landing them)")


def test_every_simresult_counter_documented():
    doc = CONFIG_DOC.read_text()
    missing = [f.name for f in dataclasses.fields(SimResult)
               if f.name not in ("design", "workload") and f"`{f.name}`" not in doc]
    assert not missing, \
        f"SimResult counters missing from docs/configuration.md: {missing}"


def test_every_design_config_knob_documented():
    doc = CONFIG_DOC.read_text()
    for fn in (design_config, baseline_config):
        params = [p for p in inspect.signature(fn).parameters if p != "design"]
        missing = [p for p in params if f"`{p}`" not in doc]
        assert not missing, \
            f"{fn.__name__} parameters missing from configuration.md: {missing}"


def test_design_scheduler_and_mode_names_documented():
    doc = CONFIG_DOC.read_text()
    for name in (*DESIGNS, *SCHEDULERS, *BANK_MODELS, *RENUMBER_MODES,
                 *INTERVAL_STRATEGIES):
        assert f"`{name}`" in doc, f"{name!r} not named in configuration.md"


def test_compiler_doc_names_the_pipeline():
    """docs/compiler.md documents every simulator pipeline pass and every
    interval strategy (keeps the pass/strategy docs from going stale)."""
    from repro.core.pipeline import frontend_passes, sim_passes

    doc = (DOCS / "compiler.md").read_text()
    for p in (*sim_passes(), *frontend_passes()):
        assert f"`{p.name}`" in doc, f"pass {p.name!r} undocumented"
    for s in INTERVAL_STRATEGIES:
        assert f"`{s}" in doc, f"strategy {s!r} undocumented"
    for name in ("CompileContext", "PassManager", "pass_stats",
                 "PIPELINE_REV"):
        assert name in doc, f"{name} undocumented in compiler.md"


def test_memtech_table_documented():
    """The Table-2 memory-technology table (designs.TABLE2) is in the doc:
    every config id with its capacity and latency multipliers."""
    doc = CONFIG_DOC.read_text()
    for tech in ("HP-SRAM", "LSTP", "TFET", "DWM"):
        assert tech in doc, f"memory technology {tech} undocumented"
    for tc, t in TABLE2.items():
        row = re.search(rf"^\|\s*{tc}\s*\|.*$", doc, re.M)
        assert row, f"Table-2 config #{tc} has no row in configuration.md"
        assert f"{t['lat_mult']}x" in row.group(0), \
            f"Table-2 config #{tc} row does not show {t['lat_mult']}x latency"


# ------------------------------------------------------------- link checking

_LINK = re.compile(r"(?<!\!)\[[^\]]+\]\(([^)\s]+)\)")
_CODE_FENCE = re.compile(r"```.*?```", re.S)


def _relative_links(md: pathlib.Path):
    text = _CODE_FENCE.sub("", md.read_text())
    for target in _LINK.findall(text):
        if target.startswith(("http://", "https://", "mailto:")):
            continue
        yield target


@pytest.mark.parametrize("md", MARKDOWN_FILES, ids=lambda p: p.name)
def test_markdown_relative_links_resolve(md):
    for target in _relative_links(md):
        path_part, _, anchor = target.partition("#")
        if not path_part:  # pure in-page anchor
            dest = md
        else:
            dest = (md.parent / path_part).resolve()
            assert dest.exists(), f"{md.name}: broken link -> {target}"
        if anchor and dest.suffix == ".md":
            # GitHub-style anchor: a heading must slug to it
            headings = re.findall(r"^#+\s+(.*)$", dest.read_text(), re.M)
            slugs = {re.sub(r"[^\w\- ]", "", h).strip().lower()
                     .replace(" ", "-") for h in headings}
            assert anchor.lower() in slugs, \
                f"{md.name}: dead anchor -> {target}"


def test_docs_are_linked_from_readme():
    readme = (ROOT / "README.md").read_text()
    for name in ("architecture.md", "simulator.md", "configuration.md",
                 "serving.md", "observability.md", "analytical.md"):
        assert f"docs/{name}" in readme, f"README does not index docs/{name}"


def test_observability_doc_names_every_category_and_metric():
    """docs/observability.md documents every cycle-attribution category and
    every sweep-service metric name, plus the layer's API surface — a new
    category or metric cannot land undocumented."""
    from repro.obs import CYCLE_CATEGORIES, SWEEP_METRICS

    doc = (DOCS / "observability.md").read_text()
    for cat in CYCLE_CATEGORIES:
        assert f"`{cat}`" in doc, f"cycle category {cat!r} undocumented"
    for metric in SWEEP_METRICS:
        assert f"`{metric}`" in doc, f"sweep metric {metric!r} undocumented"
    for name in ("cycle_breakdown", "check_breakdown", "classify_stall",
                 "CycleAttributionError", "TraceSink", "trace_simulation",
                 "MetricsRegistry", "metrics_snapshot", "to_prometheus",
                 "sweep_run_id", "SCHED_TID", "test_obs.py", "--strict",
                 "chrome://tracing", "fig21_breakdown"):
        assert name in doc, f"{name} undocumented in observability.md"
    # the configuration reference must cover the new knob and counter too
    cfg_doc = CONFIG_DOC.read_text()
    assert "`trace`" in cfg_doc and "`cycle_breakdown`" in cfg_doc


def test_analytical_doc_names_the_model_surface():
    """docs/analytical.md documents the fast tier's full public surface —
    every tier name, every calibration coefficient, the pinned pass-stats
    schema, the CLI workflows, and the accuracy gates — so a model change
    cannot land undocumented."""
    from repro.sim.analytic import ANALYTIC_PASS_SCHEMA, Calibration, TIERS

    doc = (DOCS / "analytical.md").read_text()
    for tier in TIERS:
        assert f"`{tier}`" in doc, f"tier {tier!r} undocumented"
    for f in dataclasses.fields(Calibration):
        assert f"`{f.name}`" in doc or f.name in doc, \
            f"Calibration field {f.name!r} undocumented"
    for name in ANALYTIC_PASS_SCHEMA:
        assert f"`{name}`" in doc, f"consumed pass {name!r} undocumented"
    for name in ("AnalyticResult", "analytic_supported", "fit_calibration",
                 "ANALYTIC_REV", "CALIB_REV", "ANALYTIC_PASS_SCHEMA",
                 "check_pass_stats", "pass_stats", "CompiledPlan",
                 "screening_jobs", "analytic_calib", "est_mrf_accesses",
                 "calibrate", "analytic_tier",
                 "scheduler_idle"):
        assert name in doc, f"{name} undocumented in analytical.md"
    # the trust gates are stated in the doc with their pinned thresholds
    for gate in ("0.9", "100x", "1.0"):
        assert gate in doc, f"accuracy gate {gate} missing from analytical.md"
    # and the sibling references exist
    cfg_doc = CONFIG_DOC.read_text()
    assert "`tier`" in cfg_doc or "tier" in cfg_doc
    assert "analytical.md" in cfg_doc
    assert "analytical.md" in (DOCS / "serving.md").read_text()


def test_serving_doc_names_every_sweep_knob():
    """docs/serving.md documents every `SweepConfig` field, every failure
    kind, and the operational surface of the sweep service (env vars,
    quarantine, report) — a new retry/timeout knob cannot land undocumented."""
    from repro.serving.sweep import (
        FAILURE_KINDS, FailureRecord, SweepConfig, SweepReport,
    )

    doc = (DOCS / "serving.md").read_text()
    missing = [f.name for f in dataclasses.fields(SweepConfig)
               if f"`{f.name}`" not in doc]
    assert not missing, \
        f"SweepConfig knobs missing from docs/serving.md: {missing}"
    for kind in FAILURE_KINDS:
        assert f"`{kind}`" in doc, f"failure kind {kind!r} undocumented"
    for f in dataclasses.fields(SweepReport):
        assert f"`{f.name}`" in doc, \
            f"SweepReport field {f.name!r} undocumented in serving.md"
    for f in dataclasses.fields(FailureRecord):
        assert f"`{f.name}`" in doc, \
            f"FailureRecord field {f.name!r} undocumented in serving.md"
    for name in ("REPRO_FAULT_PLAN", "REPRO_SIMCACHE", "REPRO_SIM_PROCS",
                 "quarantine", "sim_key", "SweepReport", "max_cycles",
                 "SimBudgetExceeded", "test_chaos_acceptance_sweep"):
        assert name in doc, f"{name} undocumented in serving.md"


def test_host_and_device_names_documented():
    """Every `RUN_STATS` key, `run_batch` phase span, model scope and
    `ATTN_STATS` and `SSD_STATS` key is on docs/observability.md, so the
    program's names cannot drift from it."""
    from repro.models.layers import ATTN_STATS
    from repro.models.mamba2 import SSD_STATS
    from repro.sim.batch import RUN_STATS

    doc = (DOCS / "observability.md").read_text()
    src = (ROOT / "src" / "repro" / "sim" / "batch.py").read_text()
    spans = re.findall(r'_phase\("(repro\.sim\.\w+)"', src)
    assert len(spans) == 4
    models = "".join((ROOT / "src" / "repro" / d).read_text() for d in (
        "models/lm.py", "models/layers.py", "models/mamba2.py",
        "optim/adamw.py"))
    scopes = set(re.findall(r'named_scope\("(\w+)"\)', models))
    assert scopes == {"embed", "attn", "ssm", "mlp", "norm", "head_loss",
                      "adamw"}
    missing = [n for n in (*RUN_STATS, *spans, *scopes, *ATTN_STATS,
                           *SSD_STATS)
               if f"`{n}`" not in doc]
    assert not missing, missing
