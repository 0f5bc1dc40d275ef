"""Engine: the `engine.wait` stage per front-end launch (blocked until the
launch's outputs are ready on the device), from `AccessStats.wait_s`."""

from bench.stages import per_launch


def read(run):
    return per_launch(run, "wait_s", 1e3)
