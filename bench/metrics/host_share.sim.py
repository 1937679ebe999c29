"""Share of the window the host spent in ``run_batch`` outside the launches.

The harness's clock around each request, less the program's own
``RUN_STATS["run_s"]`` (launch, device loop and readback), over the window:
plan encoding, packing (``_build``), chunking and result extraction."""


def read(rec):
    c = rec["counters"]
    if not c.get("requests"):
        return None
    return 100.0 * (c["request_s"] - c["run_s"]) / rec["window_s"]
