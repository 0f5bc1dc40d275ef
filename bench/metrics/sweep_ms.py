"""Kernel: device time of the region-sweep kernels per launch of the
front end (a kNN launch sweeps once per radius round), from the trace."""

from bench.kernelnames import SWEEP_KERNELS


def read(run):
    if run.trace is None:
        return None
    secs, count = run.trace.ops_matching(SWEEP_KERNELS)
    launches = len(run.launches())
    if not count or not launches:
        return None
    return secs / launches * 1e3
