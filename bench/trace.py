"""Reduction of a profiler trace of the window to device metrics.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  Host threads are lines of the
``/host:CPU`` plane; each chip is a ``/device:TPU:<n>`` plane whose
``XLA Ops`` line holds one event per operation that ran and whose
``XLA Modules`` line one event per executable launch.  All events share one
clock in nanoseconds.  A chip with modules and no operations in the
trace takes its busy time from the modules.

The window is the host span named by the caller.  Per chip, busy time is
the union of the operation intervals inside it; idle is the rest.  Each
idle gap is named by what the host thread that opened the window was doing
at the gap's middle: the innermost ``bench.*`` span there, else the
innermost event, else the window itself.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
TOP = 10


def short(name: str) -> str:
    """An HLO instruction's name without its text (``%fusion.3 = ...``)."""
    return name.split(" = ", 1)[0] if name.startswith("%") else name


def _clipped(line, lo, hi):
    out = []
    for e in line.events:
        s = e.start_ns
        t = s + e.duration_ns
        if t > lo and s < hi:
            out.append((e.name, max(s, lo), min(t, hi)))
    return out


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def load(tdir: str):
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {tdir}, found {paths}")
    return ProfileData.from_file(paths[0])


def reduce(tdir: str, window_span: str, name_gaps: bool = True) -> dict:
    return reduce_profile(load(tdir), window_span, name_gaps)


def reduce_profile(prof, window_span: str, name_gaps: bool = True) -> dict:
    """Busy and idle seconds, top operations, named idle gaps and the
    seconds of each executable, from one ``ProfileData``.  With
    ``name_gaps`` off, as for a span opened by a thread that only watches
    the clock, every gap takes the span's own name."""
    host, devices = None, []
    for plane in prof.planes:
        if plane.name == HOST_PLANE:
            host = plane
        elif plane.name.startswith(DEVICE_PREFIX):
            devices.append(plane)
    window, main = [], None
    for ln in (host.lines if host is not None else []):
        for e in ln.events:
            if e.name == window_span:
                window.append((e.start_ns, e.start_ns + e.duration_ns))
                main = ln
    if len(window) != 1:
        raise RuntimeError(f"expected one {window_span!r} span, found "
                           f"{len(window)}")
    lo, hi = window[0]
    main = [ev for ev in _clipped(main, lo, hi)
            if ev[0] != window_span] if name_gaps else []

    busy, op_time, mod_time, gaps = [], defaultdict(float), \
        defaultdict(float), []
    for i, plane in enumerate(sorted(devices, key=lambda p: p.name)):
        lines = {ln.name: ln for ln in plane.lines}
        ops = _clipped(lines[OPS_LINE], lo, hi) if OPS_LINE in lines else []
        mods = (_clipped(lines[MODULES_LINE], lo, hi)
                if MODULES_LINE in lines else [])
        for n, s, e in ops:
            op_time[short(n)] += (e - s) / 1e9
        for n, s, e in mods:
            mod_time[n] += (e - s) / 1e9
        merged = _union([(s, e) for _, s, e in (ops or mods)])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        if i == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = [(edges[j], edges[j + 1])
                    for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
    named = sorted(((_host_activity(main, (s + e) / 2, window_span),
                     (e - s) / 1e9) for s, e in gaps), key=lambda x: -x[1])
    top_ops = sorted(op_time.items(), key=lambda x: -x[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / max(len(devices), 1),
        "devices": len(devices),
        "modules": dict(mod_time),
        "breakdown": {"device_ops": [[k, v] for k, v in top_ops[:TOP]],
                      "idle_gaps": [[k, v] for k, v in named[:TOP]]},
    }


def _host_activity(events, t, window_span) -> str:
    """The innermost host event covering time ``t``, preferring the
    benchmark's own spans; the window's name where none covers it."""
    covering = [(s, n) for n, s, e in events if s <= t <= e]
    if not covering:
        return window_span
    ours = [c for c in covering if c[1].startswith("bench.")]
    return max(ours or covering)[1]
