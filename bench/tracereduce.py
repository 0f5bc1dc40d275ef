"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

* busy time: the union of the intervals in which an operation ran on a
  device (its ``XLA Ops`` line), averaged over the devices;
* device time per operation name, summed over the devices;
* idle gaps: the stretches of the window in which device 0 ran nothing,
  each named by the benchmark's own host annotation (``bench.*``) that
  overlaps it most, which says what the host was doing meanwhile, and by
  the event of JAX's own on the host's main thread (a device-to-host
  copy, argument transfer, dispatch) that covers most of the gap, if one
  covers at least half of it.

The window is the ``bench.window`` annotation that the harness puts
around the measured window, so every number is on the trace's own clock.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
HOST_PREFIX = "bench."
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                      # mean over devices
    n_devices: int
    op_seconds: Dict[str, float]       # device time by op name, all devices
    op_counts: Dict[str, int]
    gaps: List[Tuple[str, float]]      # longest idle gaps on device 0

    def ops_matching(self, prefixes) -> Tuple[float, int]:
        """Device seconds and count of the ops whose name starts with any
        of ``prefixes``."""
        secs, count = 0.0, 0
        for name, s in self.op_seconds.items():
            if name.startswith(tuple(prefixes)):
                secs += s
                count += self.op_counts[name]
        return secs, count

    def top_ops(self, k: int = 10):
        return sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:k]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals):
    """Merge (start, end) intervals; returns the sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def device_op_line(plane):
    for line in plane.lines:
        if line.name == OPS_LINE:
            return line
    return None


def summarize(path: str, *, n_gaps: int = 10) -> TraceSummary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, inner = [], []
    device_lines = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            line = device_op_line(plane)
            if line is not None:
                device_lines.append((plane.name, line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                # The main thread's line is named after the process
                # ("python", "python3"); worker threads carry "/<tid>".
                if "/" in line.name:
                    continue
                for ev in line.events:
                    span = (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    (host if ev.name.startswith(HOST_PREFIX)
                     else inner).append(span)
    windows = [(s, e) for name, s, e in host if name == WINDOW]
    if not windows:
        raise ValueError(f"trace has no {WINDOW!r} annotation")
    lo = min(s for s, _ in windows)
    hi = max(e for _, e in windows)
    device_lines.sort(key=lambda pl: pl[0])

    op_seconds: Dict[str, float] = {}
    op_counts: Dict[str, int] = {}
    busy = []
    first_union = None
    for _, line in device_lines:
        spans = []
        for ev in line.events:
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e <= lo or s >= hi:
                continue
            spans.append((s, e))
            op_seconds[ev.name] = op_seconds.get(ev.name, 0.0) + (
                min(e, hi) - max(s, lo)) / 1e9
            op_counts[ev.name] = op_counts.get(ev.name, 0) + 1
        u = _union(_clip(spans, lo, hi))
        busy.append(sum(e - s for s, e in u) / 1e9)
        if first_union is None:
            first_union = u
    gaps = []
    if first_union is not None:
        edges = [(lo, lo)] + [tuple(x) for x in first_union] + [(hi, hi)]
        outer = _AnnotationIndex([x for x in host if x[0] != WINDOW])
        within = _AnnotationIndex(inner)
        for (_, a), (b, _) in zip(edges[:-1], edges[1:]):
            if b > a:
                name = outer.attribute(a, b) or WINDOW
                detail = within.attribute(a, b, least=(b - a) / 2)
                if detail is not None:
                    name = f"{name} / {detail}"
                gaps.append((name, (b - a) / 1e9))
        gaps.sort(key=lambda g: -g[1])
    return TraceSummary(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(busy) / len(busy) if busy else 0.0,
        n_devices=len(device_lines),
        op_seconds=op_seconds,
        op_counts=op_counts,
        gaps=gaps[:n_gaps],
    )


class _AnnotationIndex:
    """Host annotations sorted by start, for finding what the host was
    doing in an interval."""

    def __init__(self, annotations):
        self.items = sorted(annotations, key=lambda x: x[1])
        self.starts = [s for _, s, _ in self.items]
        self.longest = max((e - s for _, s, e in self.items), default=0)

    def attribute(self, a: int, b: int, least: float = 0) -> Optional[str]:
        """The annotation that overlaps ``[a, b)`` most, by more than
        ``least`` ns; None where none does."""
        best: Optional[str] = None
        best_overlap = least
        i = bisect.bisect_left(self.starts, a - self.longest)
        j = bisect.bisect_right(self.starts, b)
        for name, s, e in self.items[i:j]:
            ov = min(e, b) - max(s, a)
            if ov > best_overlap:
                best, best_overlap = name, ov
        return best
