"""Engine: the `engine.prepare` stage per front-end launch (host work
before the dispatch: validation, keys, cache and dedupe, padding, parent
windows, staging the inputs, the kNN rounds' rectangles), from
`AccessStats.prepare_s`."""

from bench.stages import per_launch


def read(run):
    return per_launch(run, "prepare_s", 1e3)
