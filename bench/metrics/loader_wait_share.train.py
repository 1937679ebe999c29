"""Share of the window spent in the data loader's ``get``.

The benchmark's span around ``PrefetchingLoader.get``: time the step loop
waited for its next batch from the prefetch thread."""


def read(rec):
    s = rec["spans"].get("bench.loader_get")
    return None if s is None else 100.0 * s / rec["window_s"]
