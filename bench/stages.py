"""Per-launch readings of the engine's stage counters.

The program's ``AccessStats`` carries the seconds of each stage of a
served launch (``prepare_s``, ``wait_s``, ``fetch_s``, ``finish_s``), the
bytes it staged to the device (``h2d_bytes``) and its kNN rounds; the
harness hands the window's deltas to the readers as ``run.stats``.

The stages split a launch beside the device trace that their profiler
annotations sit in, so they are read in traced runs that have a device
plane, as the device-trace metrics are; a CPU rehearsal has none and
gives no reading.  A program without such a counter gives none either.
"""


def counter(run, name: str):
    """The window's delta of counter ``name``, or None where it is not
    read (see the module docstring)."""
    if run.trace is None or not run.trace.n_devices:
        return None
    return run.stats.get(name)


def per_launch(run, name: str, scale: float):
    """Counter ``name`` × ``scale`` per front-end launch, or None."""
    value = counter(run, name)
    launches = len(run.launches())
    if value is None or not launches:
        return None
    return value * scale / launches
