"""Per-layer metric readers, one module per metric, found by the
metric's name in ``BENCHMARK.json``. Each exposes ``read(ctx)`` (a
`bench.harness.TraceContext`) and returns the value, or None when the
traced run holds nothing to read."""
