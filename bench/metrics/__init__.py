"""One reader per per-layer metric: ``bench/metrics/<name>.py`` defines
``read(ctx) -> float | None`` (``ctx`` is a ``bench.context.Context``).  A
reader that finds nothing to read returns None and the metric is left out
of the run's line; a share of a roofline or a peak is never 0 for want of
data."""
