"""Per-layer metric readers, one file each, named as the metric.

Each module has ``read(window) -> float | None``: ``window`` is the
traced run's ``run.Window``. A reader that finds nothing to read returns
None, and the harness leaves the metric out of the result."""
