"""Reduction of a ``jax.profiler`` trace to the benchmark's device numbers.

Everything here works on plain :class:`Event` lists, so it is tested on
small synthetic traces; :func:`read_xplane` is the only function that
touches the profiler's file.

* busy time: the union of the intervals in which a device op ran;
* kernel time: the summed device time of the events of one kernel, matched
  by the name the kernel carries in the trace;
* roofline share: the least time the work needs (:func:`bench.flops.
  roofline_seconds`, per call) over the kernel's measured time;
* idle gaps: the holes in the busy union, each labelled by the innermost
  host span of the benchmark (``bench.*``) that covers its middle.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
# an op event is named by its whole HLO instruction, "%name.N = type op(...)"
HLO_NAME = re.compile(r"^%?([^\s=]+)")
# ops that only hold other ops (a scan's loop): their time is their body's
CONTAINERS = ("while", "conditional", "call")
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."


@dataclass(frozen=True)
class Event:
    name: str  # a device op's HLO instruction name, e.g. "fusion.12"
    start: float  # seconds, on the trace's clock
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


def read_xplane(trace_dir: str) -> Tuple[List[Event], List[Event]]:
    """``(device ops, host spans)`` of the newest trace under ``trace_dir``.
    Device ops are the ``XLA Ops`` line of every TPU plane, named by their
    HLO instruction, without the ops that only hold others
    (:data:`CONTAINERS`): a scan's loop spans its body's ops and the gaps
    between them, and would count those gaps as busy.  Host spans are the
    benchmark's own ``bench.*`` annotations.  Both are on the trace's one
    clock."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    device, host = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    m = HLO_NAME.match(e.name)
                    name = m.group(1) if m else e.name
                    if name.split(".", 1)[0] in CONTAINERS:
                        continue
                    device.append(Event(name, e.start_ns * 1e-9,
                                        (e.start_ns + e.duration_ns) * 1e-9))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host.append(Event(e.name, e.start_ns * 1e-9,
                                          (e.start_ns + e.duration_ns) * 1e-9))
    return device, host


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge intervals into disjoint, sorted ones."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_seconds(events: Sequence[Event]) -> float:
    return sum(b - a for a, b in union((e.start, e.end) for e in events))


def matches(event: Event, names: Sequence[str]) -> bool:
    """Whether the event is a call of a kernel known in the trace by one of
    ``names``: the event's name, without its ``.N`` suffix, is one of them."""
    base = event.name.split(".", 1)[0]
    return base in names


def kernel_events(events: Sequence[Event], names: Sequence[str]) -> List[Event]:
    return [e for e in events if matches(e, names)]


def roofline_share(
    calls: Iterable[Tuple[float, float]], kernel_s: float,
    peak_flops: float, peak_bw: float,
) -> Optional[Tuple[float, str]]:
    """``(percent of roofline, bound)`` of a kernel whose calls did
    ``calls`` = ``[(flops, bytes), ...]`` of required work in ``kernel_s``
    seconds of device time.  The bound is the one that sets most of the
    least time.  None where there is no call or no time to divide by."""
    from flops import roofline_seconds

    least, by = 0.0, collections.Counter()
    for f, b in calls:
        t, bound = roofline_seconds(f, b, peak_flops, peak_bw)
        least += t
        by[bound] += t
    if least <= 0 or kernel_s <= 0:
        return None
    return 100.0 * least / kernel_s, by.most_common(1)[0][0]


def idle_gaps(
    device: Sequence[Event], spans: Sequence[Event], t0: float, t1: float,
) -> List[Tuple[str, float, float]]:
    """Every hole in the device's busy union inside ``[t0, t1]``, as
    ``(label, start, length)``; the label is the innermost ``bench.*`` span
    that covers the hole's middle, or ``"no span"``."""
    busy = union((max(e.start, t0), min(e.end, t1)) for e in device
                 if e.end > t0 and e.start < t1)
    edges = [t0] + [x for ab in busy for x in ab] + [t1]
    spans = sorted(spans, key=lambda s: s.start)
    starts = [s.start for s in spans]
    longest = max((s.dur for s in spans), default=0.0)
    out = []
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = 0.5 * (a + b)
        cover = []
        j = bisect.bisect_right(starts, mid) - 1
        while j >= 0 and spans[j].start >= mid - longest:
            if spans[j].end >= mid:
                cover.append(spans[j])
            j -= 1
        label = min(cover, key=lambda s: s.dur).name if cover else "no span"
        out.append((label, a, b - a))
    return out


def gap_summary(gaps: Sequence[Tuple[str, float, float]], top: int = 10) -> List[list]:
    """Idle time by what the host was doing: ``[label with count and longest
    gap, total seconds]``, longest total first."""
    total: Dict[str, float] = collections.defaultdict(float)
    count: Dict[str, int] = collections.defaultdict(int)
    longest: Dict[str, float] = collections.defaultdict(float)
    for label, _, length in gaps:
        total[label] += length
        count[label] += 1
        longest[label] = max(longest[label], length)
    rows = sorted(total, key=total.get, reverse=True)[:top]
    return [[f"{k} ({count[k]} gaps, longest {longest[k]!r} s)", total[k]] for k in rows]


def top_ops(events: Sequence[Event], top: int = 10) -> List[list]:
    """Device time by op name, most first."""
    total: Dict[str, float] = collections.defaultdict(float)
    for e in events:
        total[e.name] += e.dur
    return [[k, total[k]] for k in sorted(total, key=total.get, reverse=True)[:top]]
