"""Per-layer metric readers, one module per metric named as in
``BENCHMARK.json``.  Each has ``read(ctx) -> float | None``: ``None`` when the
run holds nothing to read, so the harness leaves the metric out."""
