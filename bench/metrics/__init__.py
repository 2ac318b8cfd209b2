"""Metric readers, one file per metric of ``BENCHMARK.json``, each with
``read(run) -> float | None``; loaded by name (``bench.spec.load_module``)."""
