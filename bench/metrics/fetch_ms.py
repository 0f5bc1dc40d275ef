"""Engine: the `engine.fetch` stage per front-end launch (the device-to-
host copies of the outputs (hits, visits, kNN ids and distances)), from
`AccessStats.fetch_s`."""

from bench.stages import per_launch


def read(run):
    return per_launch(run, "fetch_s", 1e3)
