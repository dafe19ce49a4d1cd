"""One reader per per-layer metric, found by the metric's name.

Each module has ``read(record) -> float | None``; ``record`` is what a
driver returned for the run (passes, counters, the trace summary).  A
reader that finds nothing to read returns ``None`` and the metric is left
out of the result line.
"""
