"""One reader per metric, found by the metric's name in BENCHMARK.json.

Each module has ``read(run) -> float | None``.  ``run`` holds the run's
record: the clients (what they saw, and every decode step), the
window's bounds, the serve loop's counters over the window, the sizes of
the configuration, the chip's peaks and, in a ``--trace 1`` run, the
reduced trace (``bench/trace.py``).  A reader that finds nothing to read
returns None, and the metric is left out of the result.
"""
