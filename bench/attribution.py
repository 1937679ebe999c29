#!/usr/bin/env python3
"""Where a traced window's device time goes, by the program's own names.

Two reductions of one profiler trace, beside `bench.trace.reduce_profile`
and on the same planes and clock:

* `scopes`: device-busy seconds per model scope (``embed``, ``attn``,
  ``mlp``, ``norm``, ``head_loss``, ``adamw``; ``other`` for the rest)
  inside the window.  Leaf operations only: a ``while``, ``conditional`` or
  ``call`` event spans its children's, so counting it would count them
  again.  An operation's scope comes from its ``op_name`` in the compiled
  HLO text (`op_scopes`): the outermost scope name on its path, through
  transformation wrappers such as ``transpose(jvp(attn))``.
* `idle_by_span`: idle seconds per innermost ``repro.*`` span covering the
  gap's middle, on any host thread: the program's own phase spans name the
  gaps of a window opened by a thread that only watches the clock.

The command runs one cell traced, as ``bench/run.py --trace 1`` does,
prints the harness's log and result line, and then one JSON line with the
two reductions, the window's deltas of the batch engine's ``RUN_STATS``,
and the driver's end-to-end metrics measured with the profiler on::

    python3 bench/attribution.py --workload <cell> --seed <n> --seconds <s>
"""
from __future__ import annotations

import contextlib
import re
import sys
from collections import defaultdict

if __name__ == "__main__":
    import pathlib

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import trace  # noqa: E402

SCOPES = ("embed", "attn", "mlp", "norm", "head_loss", "adamw")
OTHER = "other"
CONTAINERS = ("while", "conditional", "call")
SPAN_PREFIX = "repro."
TOP = 10

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\S.*?\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPS = re.compile(r"^(?:\w+\()+|\)+$")


def scope_of(op_name: str) -> str:
    """The outermost of `SCOPES` on an ``op_name`` path, else `OTHER`."""
    for part in op_name.split("/"):
        inner = _WRAPS.sub("", part)
        if inner in SCOPES:
            return inner
    return OTHER


def op_scopes(hlo_text: str) -> dict[str, tuple[str, str]]:
    """Each instruction of a compiled HLO module's text, by name: its
    opcode and its scope."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            n = _OP_NAME.search(line)
            out[m.group(1)] = (m.group(2), scope_of(n.group(1)) if n
                               else OTHER)
    return out


def _key(event_name: str) -> str:
    return trace.short(event_name).lstrip("%")


def _lookup(ops: dict, event_name: str):
    """The (opcode, scope) of a trace event's operation, and whether the
    HLO text has it.  An operation it lacks takes its opcode from the
    event's own text (``%while.3 = (...) while(...)``) and the scope
    `OTHER`."""
    k = _key(event_name)
    if k in ops:
        return (*ops[k], True)
    m = re.search(r"\s([a-z][\w\-]*)\(", event_name)
    return (m.group(1) if m else None), OTHER, False


def _window(prof, window_span):
    host, devices = None, []
    for plane in prof.planes:
        if plane.name == trace.HOST_PLANE:
            host = plane
        elif plane.name.startswith(trace.DEVICE_PREFIX):
            devices.append(plane)
    spans = [(e.start_ns, e.start_ns + e.duration_ns)
             for ln in (host.lines if host is not None else [])
             for e in ln.events if e.name == window_span]
    if len(spans) != 1:
        raise RuntimeError(f"expected one {window_span!r} span, found "
                           f"{len(spans)}")
    return host, sorted(devices, key=lambda p: p.name), spans[0]


def _ops(plane, lo, hi):
    lines = {ln.name: ln for ln in plane.lines}
    return (trace._clipped(lines[trace.OPS_LINE], lo, hi)
            if trace.OPS_LINE in lines else [])


def scopes(prof, window_span: str, ops: dict) -> dict:
    """Device-busy seconds per scope inside the window, averaged over the
    chips, from leaf operations; ``ops`` is `op_scopes` of the executables
    that ran.  Also the top leaf operations left in `OTHER`, and the leaf
    seconds of operations the HLO text lacks (``unknown_s``)."""
    _, devices, (lo, hi) = _window(prof, window_span)
    by_scope, other = defaultdict(float), defaultdict(float)
    unknown = 0.0
    for plane in devices:
        for name, s, e in _ops(plane, lo, hi):
            opcode, scope, known = _lookup(ops, name)
            if opcode in CONTAINERS:
                continue
            by_scope[scope] += (e - s) / 1e9
            unknown += 0.0 if known else (e - s) / 1e9
            if scope == OTHER:
                other[_key(name)] += (e - s) / 1e9
    n = max(len(devices), 1)
    top = sorted(other.items(), key=lambda x: -x[1])[:TOP]
    return {"seconds": {k: by_scope[k] / n for k in (*SCOPES, OTHER)},
            "other_ops": [[k, v / n] for k, v in top],
            "unknown_s": unknown / n}


def idle_by_span(prof, window_span: str) -> dict[str, float]:
    """Idle seconds of the first chip inside the window, per innermost
    ``repro.*`` host span on any thread covering each gap's middle (the
    window's own name where none does)."""
    host, devices, (lo, hi) = _window(prof, window_span)
    if not devices:
        return {}
    busy = trace._union([(s, e) for _, s, e in _ops(devices[0], lo, hi)])
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    spans = [(s, e, n) for ln in host.lines
             for n, s, e in trace._clipped(ln, lo, hi)
             if n.startswith(SPAN_PREFIX)]
    out = defaultdict(float)
    for j in range(0, len(edges), 2):
        a, b = edges[j], edges[j + 1]
        if b <= a:
            continue
        t = (a + b) / 2
        covering = [(s, n) for s, e, n in spans if s <= t <= e]
        out[max(covering)[1] if covering else window_span] += (b - a) / 1e9
    return dict(sorted(out.items(), key=lambda x: -x[1]))


def _compiled_text(step, *args) -> str:
    """The optimized HLO text of the jitted ``step`` at ``args``, compiled
    afresh.  The executable that ran may have come from JAX's persistent
    cache, and the same lowering hands it back; a new function object gets
    a new lowering, and with the persistent cache off a new compile of the
    same module, with its operations' metadata."""
    import functools

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    fresh = jax.jit(functools.partial(step.__wrapped__), donate_argnums=(0,))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return fresh.lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def attribute(cell, seed: int, seconds: float, devices, log=sys.stderr):
    """Run ``cell`` traced once and return ``(result, extra)``: the
    harness's result line and what this module reads from the same run."""
    import jax
    import numpy as np

    from bench import cells, harness

    seen: dict = {}
    real_window = harness.Run.window
    driver = cells.load_driver(cell.driver)
    real_run = driver.run

    def reduce(tdir, span, name_gaps=True):
        prof = trace.load(tdir)
        seen["prof"], seen["span"] = prof, span
        return trace.reduce_profile(prof, span, name_gaps)

    def run_stats():
        try:
            from repro.sim.batch import RUN_STATS
        except ImportError:
            return {}
        return dict(RUN_STATS)

    @contextlib.contextmanager
    def window(self):
        before = run_stats()
        with real_window(self):
            yield
        after = run_stats()
        seen["stats"] = {k: after[k] - before[k] for k in after}

    def run(r):
        out = real_run(r)
        seen["out"], seen["run"] = out, r
        return out

    patches = [(trace, "reduce", reduce), (harness.Run, "window", window),
               (driver, "run", run)]
    trainer = getattr(driver, "Trainer", None)
    if trainer is not None:
        real_feed = trainer.feed

        def feed(self):
            loss = real_feed(self)
            if "step" not in seen:
                def shape(x):
                    return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                sharding=x.sharding)

                seen["step"] = (self.step, jax.tree.map(shape, self.state), {
                    k: jax.ShapeDtypeStruct((self.batch, self.seq), np.int32,
                                            sharding=sh)
                    for k, sh in self.b_sh.items()})
            return loss

        patches.append((trainer, "feed", feed))
    saved = [(obj, name, getattr(obj, name)) for obj, name, _ in patches]
    try:
        for obj, name, fn in patches:
            setattr(obj, name, fn)
        result = harness.run_cell(cell, seed, seconds, True, devices, log=log)
    finally:
        for obj, name, fn in saved:
            setattr(obj, name, fn)

    extra = {"work": seen["out"]["metrics"], "window_s": seen["run"].window_s,
             "idle_by_span": idle_by_span(seen["prof"], seen["span"])}
    stats = seen.get("stats", {})
    if stats.get("ticks"):
        extra["run_stats"] = stats
        host = sum(stats.get(k, 0.0)
                   for k in ("encode_s", "build_s", "extract_s"))
        extra["batch_host_share"] = 100.0 * host / seen["run"].window_s
        if stats.get("lane_slots"):
            extra["live_lanes"] = (100.0 * stats["lane_ticks"]
                                   / stats["lane_slots"])
    if "step" in seen:
        step, st, batch = seen["step"]
        ops = op_scopes(_compiled_text(step, st, batch))
        sc = scopes(seen["prof"], seen["span"], ops)
        steps = seen["out"]["counters"]["steps"]
        leaf = sum(sc["seconds"].values())
        extra.update(
            scopes_s=sc["seconds"], other_ops=sc["other_ops"], steps=steps,
            unknown_s=sc["unknown_s"], hlo_ops=len(ops),
            hlo_scoped_ops=sum(s != OTHER for _, s in ops.values()),
            ms_per_step={k: 1000.0 * v / steps
                         for k, v in sc["seconds"].items()},
            scoped_share=(100.0 * (leaf - sc["seconds"][OTHER]) / leaf
                          if leaf else None))
    return result, extra


def main(argv=None) -> int:
    import argparse
    import json

    from bench.run import prepare

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="a cell's name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    got, err = prepare(args.workload)
    if err:
        print(f"bench: {err}", file=sys.stderr)
        return 2
    cell, devices = got
    result, extra = attribute(cell, args.seed, args.seconds, devices)
    print(json.dumps(result), flush=True)
    print(json.dumps({"attribution": extra, "workload": cell.name,
                      "seed": args.seed}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
