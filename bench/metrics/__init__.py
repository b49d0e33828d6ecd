"""Per-layer metric readers, one file per metric, found by the metric's
name in BENCHMARK.json. Each defines ``read(r)`` taking the run's
``bench.readings.Readings`` and returning a number, or None where the run
gave it nothing to read (the harness then leaves the metric out)."""
