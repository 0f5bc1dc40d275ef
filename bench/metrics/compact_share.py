"""Engine: share of the pyramid launches returning hit ids that no
capacity overflowed, in %, from `AccessStats.compact_launches` and
`compact_overflows` (an overflowed launch is answered with the dense
mask by the same program).  A program without the counters, or a
window with no such launch, gives no reading."""

from bench.stages import counter


def read(run):
    launches = counter(run, "compact_launches")
    overflows = counter(run, "compact_overflows")
    if not launches or overflows is None:
        return None
    return (launches - overflows) / launches * 100.0
