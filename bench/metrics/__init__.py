"""Per-layer metric readers: ``<name>.py`` defines ``read(ctx)`` and
returns the metric's value, or ``None`` where it finds nothing to read."""
