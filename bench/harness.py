"""One run of one cell: set-up, the measured window, the check, the result.

A driver (``bench/drivers/<kind>.py``) provides ``run(r: Run) -> dict``.  It
sets up, enters ``r.window()`` for the measured seconds, and after the
window checks what the timed path produced against the configuration's
plain reference.  It returns::

    {"metrics":  {end-to-end metric: value},   # besides setup_s
     "counters": {name: value},                # what per-layer readers read
     "attempted": int, "failed": int,
     "compared": [(name, value, limit), ...]}  # correct iff value <= limit

A driver may also name a ``TRACE_SLICE``, ``(delay_s, length_s)``: its
traced runs then record only that slice of the
window, from ``delay_s`` after the window opens, for ``length_s`` seconds.
A slice is for a device loop of many small operations, whose every
operation the profiler records: over a whole window such a trace takes
minutes to stop and to read.  The harness times set-up (process
start to the window), counts compiles in the window, reads the device's
peak memory as the window closes, traces the window when asked, and builds
the result line.
"""
from __future__ import annotations

import contextlib
import math
import shutil
import sys
import tempfile
import threading
import time
from collections import defaultdict

from bench import cells, trace

WINDOW_SPAN = "bench.window"
SLICE_SPAN = "bench.traced_slice"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCount:
    """Backend compiles in this process, as JAX reports them."""

    _instance = None

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, seconds, **_):
        if event == COMPILE_EVENT:
            self.n += 1

    @classmethod
    def get(cls) -> "CompileCount":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance


class Run:
    """A driver's handle on the run: arguments, spans and the window."""

    def __init__(self, cell: cells.Cell, seed: int, seconds: float,
                 traced: bool, devices, started: float,
                 trace_slice: tuple[float, float] | None = None):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace_slice = trace_slice
        self.traced, self.devices, self.started = traced, devices, started
        self.spans: dict[str, float] = defaultdict(float)
        self.setup_s = self.window_s = None
        self.compiles_in_window = None
        self.memory_peak_bytes = None
        self.trace = None
        self._open = False
        self._compiles = CompileCount.get()

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span in the profiler's trace; its seconds inside the
        window are summed per name in ``spans``."""
        import jax

        with jax.profiler.TraceAnnotation(name):
            t = time.perf_counter()
            try:
                yield
            finally:
                if self._open:
                    self.spans[name] += time.perf_counter() - t

    @contextlib.contextmanager
    def window(self):
        """The measured seconds.  The driver ends the window only once the
        device has finished the work it counts."""
        import jax

        tdir = slicer = None
        closing = threading.Event()
        if self.traced:
            tdir = tempfile.mkdtemp(prefix="bench_trace_")
            if self.trace_slice is None:
                jax.profiler.start_trace(tdir)
            else:
                slicer = threading.Thread(
                    target=_trace_slice, daemon=True,
                    args=(tdir, *self.trace_slice, closing))
        n0 = self._compiles.n
        t0 = time.perf_counter()
        self.setup_s = t0 - self.started
        self._open = True
        if slicer is not None:
            slicer.start()
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                yield
        finally:
            self.window_s = time.perf_counter() - t0
            self._open = False
            closing.set()
            self.compiles_in_window = self._compiles.n - n0
            peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                     for d in self.devices]
            self.memory_peak_bytes = max(
                (p for p in peaks if p is not None), default=None)
            if tdir is not None:
                if slicer is None:
                    jax.profiler.stop_trace()
                else:
                    slicer.join()
                try:
                    # the slice's span is the watching thread's, which
                    # does nothing that could name a gap
                    self.trace = (trace.reduce(tdir, WINDOW_SPAN)
                                  if slicer is None else
                                  trace.reduce(tdir, SLICE_SPAN, False))
                finally:
                    shutil.rmtree(tdir, ignore_errors=True)


def _trace_slice(tdir, delay_s: float, length_s: float,
                 closing: threading.Event) -> None:
    """Trace ``length_s`` seconds of the window from ``delay_s`` after it
    opens, under the span ``SLICE_SPAN``; a window that closes first cuts
    the slice short."""
    import jax

    closing.wait(delay_s)
    jax.profiler.start_trace(tdir)
    try:
        with jax.profiler.TraceAnnotation(SLICE_SPAN):
            closing.wait(length_s)
    finally:
        jax.profiler.stop_trace()


def _passes(value, limit) -> bool:
    return value is not None and not math.isnan(value) and value <= limit


def run_cell(cell: cells.Cell, seed: int, seconds: float, traced: bool,
             devices, started: float | None = None, log=sys.stderr) -> dict:
    """Run ``cell`` once on ``devices`` and return the result line's object.

    The caller has checked the devices; tests call this on the CPU at a
    tiny size, where no number in the result is a device metric."""
    started = time.perf_counter() if started is None else started
    driver = cells.load_driver(cell.driver)
    r = Run(cell, seed, seconds, traced, devices, started,
            getattr(driver, "TRACE_SLICE", None))
    out = driver.run(r)
    print(f"window: {r.window_s:.3f} s after {r.setup_s:.3f} s of set-up; "
          f"compiles in window: {r.compiles_in_window}", file=log)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": r.memory_peak_bytes}
    metrics = {}
    result = {}
    if not traced:
        values = {"setup_s": r.setup_s, **out["metrics"]}
        for e in cell.end_to_end:
            metrics[e["name"]] = {"value": values[e["name"]],
                                  "unit": e["unit"]}
    else:
        rec = {"counters": out["counters"], "spans": dict(r.spans),
               "window_s": r.window_s, "trace": r.trace,
               "memory_peak_bytes": r.memory_peak_bytes,
               "metrics": out["metrics"], "config": cell.config,
               "traffic": cell.traffic,
               "peaks": cells.load_peaks(dev.device_kind)
               if dev.platform != "cpu" else None}
        for e in cell.per_layer:
            v = cells.load_reader(e["name"])(rec)
            if v is not None:
                metrics[e["name"]] = {"value": v, "unit": e["unit"]}
        device["busy_s"] = r.trace["busy_s"]
        device["window_s"] = r.trace["window_s"]
        result["breakdown"] = r.trace["breakdown"]

    compared = {name: {"value": v, "limit": lim}
                for name, v, lim in out["compared"]}
    correct = (bool(compared) and out["failed"] == 0
               and all(_passes(c["value"], c["limit"])
                       for c in compared.values()))
    for name, c in compared.items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})",
              file=log)
    return {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device,
            **result, "compared": compared}
