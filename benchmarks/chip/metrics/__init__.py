"""One module per metric, named as the metric is in BENCHMARK.json. Each has
``read(ctx) -> float | None``; None means the run had nothing to read for it,
and the harness leaves the metric out of the line."""
