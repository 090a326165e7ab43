"""One reader per per-layer metric, named as in ``BENCHMARK.json``.

Each module defines ``read(rec) -> float | None`` over a
``run.TraceRecord``: the reduced trace of the traced window, its ticks,
the cell, the peaks of the device and the counts of the work.  A reader
that finds nothing to read returns None, and the metric is left out.
"""
