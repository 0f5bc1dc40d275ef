"""Set-up: process start to the start of the measured window (JAX
start-up, data from the seed, the build, the warm-up of every shape)."""


def read(run):
    return run.setup_s
