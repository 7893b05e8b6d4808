"""Per-layer metric readers, one module per metric named in
``BENCHMARK.json``; each exposes ``read(run) -> float | None``."""
