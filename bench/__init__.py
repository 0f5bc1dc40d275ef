"""The benchmark of the served spatial index (see ``BENCHMARK.json``)."""
