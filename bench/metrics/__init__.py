"""Per-layer metric readers, one file per metric named in BENCHMARK.json.

Each module defines ``read(ctx) -> float | None``; ``ctx`` is
``bench.run.TraceContext``.  A reader that finds nothing to read returns
None and the metric is left out of the result line.
"""
