"""Per-layer metric readers: ``bench/metrics/<metric>.py`` defines
``read(run) -> float | None`` over one run's record (``bench.run.Run``).
A reader that finds nothing to read returns None and the metric is left
out of the result."""
