"""Engine: megabytes staged from the host to the device per front-end
launch, from `AccessStats.h2d_bytes` (arrays already on the device count
nothing)."""

from bench.stages import per_launch


def read(run):
    return per_launch(run, "h2d_bytes", 1e-6)
