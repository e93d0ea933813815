"""Reduce a profiler trace to device busy time, operation times and idle
gaps.

The run brackets its window with a host span named ``WINDOW_SPAN``.
Within it:

- busy time is the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), averaged
  over the devices that ran anything;
- an operation's time is the sum of its events' durations, clipped to the
  window, under the HLO instruction name the trace prints (a loop and the
  operations inside it are both events, so their times overlap);
- an idle gap is a stretch of the window with no operation on the device,
  labelled with the host span (``gate.*``) that overlaps it most, or
  ``engine loop`` where none does.
"""

from __future__ import annotations

import dataclasses
import glob
import os

WINDOW_SPAN = "bench.window"
HOST_SPANS = ("gate.refill", "gate.maintain", "gate.wait")
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
ENGINE_LOOP = "engine loop"


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                       # averaged over devices
    n_devices: int
    op_seconds: dict                    # name -> seconds, summed over devices
    gaps: list                          # [(label, seconds)], longest first


def merge(intervals) -> list:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def idle_gaps(busy: list, lo: float, hi: float) -> list:
    """The stretches of [lo, hi) that no merged busy interval covers."""
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    return [(s, e) for s, e in gaps if e > s]


def label_gap(gap, spans) -> str:
    """The host span overlapping ``gap`` most (the innermost of equals, as
    ``gate.wait`` inside ``gate.refill``), or the engine loop."""
    s, e = gap
    best, key = ENGINE_LOOP, (0.0, 0.0)
    for name, ss, se in spans:
        k = (min(e, se) - max(s, ss), ss - se)
        if k[0] > 0 and k > key:
            best, key = name, k
    return best


def reduce_events(window, device_events: dict, host_spans: list) -> Reduced:
    """``window`` (start, end) and every time in ns; ``device_events`` maps
    a device to its [(name, start, end)]; ``host_spans`` is
    [(name, start, end)]."""
    lo, hi = window
    ops: dict = {}
    busy_total, gaps, n_dev = 0.0, [], 0
    for evs in device_events.values():
        clipped = [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                   if min(e, hi) > max(s, lo)]
        if not clipped:
            continue
        n_dev += 1
        for n, s, e in clipped:
            ops[n] = ops.get(n, 0.0) + (e - s) * 1e-9
        busy = merge((s, e) for _, s, e in clipped)
        busy_total += sum(e - s for s, e in busy) * 1e-9
        gaps += [(label_gap(g, host_spans), (g[1] - g[0]) * 1e-9)
                 for g in idle_gaps(busy, lo, hi)]
    gaps.sort(key=lambda g: -g[1])
    return Reduced(window_s=(hi - lo) * 1e-9,
                   busy_s=busy_total / max(n_dev, 1), n_devices=n_dev,
                   op_seconds=ops, gaps=gaps)


def op_name(event_name: str) -> str:
    """An operation's name as the trace prints it: a TPU trace names each
    event by its whole HLO instruction, ``%name = shape op(...)``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {len(files)}")
    return files[0]


def read_xplane(path: str) -> Reduced:
    """Load a profiler trace and reduce it."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window, spans, device_events = None, [], {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_events[plane.name] = [
                        (op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name in HOST_SPANS:
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    if window is None:
        raise RuntimeError(f"no {WINDOW_SPAN!r} span in {path}")
    return reduce_events(window, device_events, spans)
