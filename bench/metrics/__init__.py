"""Per-layer metric readers, one module per metric, named as the metric.

Each module defines ``read(run) -> float | None`` over a ``bench.run.LayerRun``
(the trace's ``Summary``, the chip's peaks and the counted costs).  A reader
that finds nothing to read returns None, and the metric is left out of the
result line.
"""
