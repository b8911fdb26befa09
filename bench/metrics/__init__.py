"""One reader per per-layer metric: ``read(run) -> float | None``.

``run`` is the harness's record of the window (``bench.harness.Run``).
A reader that finds nothing to read returns ``None`` and the metric is
left out of the line."""
