"""One module per metric, found by the metric's name in ``BENCHMARK.json``.

Each has ``read(run) -> float | None``; ``run`` is the run's record
(``bench.run.Run``).  A reader that finds nothing to read returns None,
and the metric is left out of the result line.
"""
