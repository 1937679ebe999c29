"""The benchmark: one command (``bench/run.py``) that runs a named cell of
``BENCHMARK.json`` on the chip and prints its metrics, and everything it
measures with: configurations, traffic mixes, limits, drivers, metric
readers, the table of peaks, the FLOP count and the plain references."""
