"""Engine: the `engine.finish` stage per front-end launch (host work after
the fetch: cache fill, unstacking, the kNN rounds' checks, each
request's answer), from `AccessStats.finish_s`."""

from bench.stages import per_launch


def read(run):
    return per_launch(run, "finish_s", 1e3)
