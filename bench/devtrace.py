"""Reduce a ``jax.profiler`` trace to device busy time, program time and
idle gaps labelled by what the host was doing.

The reduction works on plain :class:`Event` rows, so it can be checked
on a synthetic trace; :func:`events_from_xplane` reads a recorded
``.xplane.pb`` into such rows.  Device operations are the events on the
``XLA Ops`` line of each ``/device:`` plane (``XLA Modules`` where a
plane has no op line); a program's device time is the sum of its
``XLA Modules`` events.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import pathlib
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def events_from_xplane(path) -> List[Event]:
    """Every event of a recorded trace (``*.xplane.pb``)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def find_xplane(log_dir) -> Optional[pathlib.Path]:
    found = sorted(pathlib.Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def device_ops(events: Iterable[Event]) -> Dict[str, List[Event]]:
    """Device operations per device plane."""
    by_plane: Dict[str, Dict[str, List[Event]]] = collections.defaultdict(
        lambda: collections.defaultdict(list))
    for e in events:
        if e.plane.startswith(DEVICE_PREFIX):
            by_plane[e.plane][e.line].append(e)
    out = {}
    for plane, lines in by_plane.items():
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        if ops:
            out[plane] = ops
    return out


def merge(intervals: Iterable[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Union of intervals clipped to ``[lo, hi]``, sorted, disjoint."""
    merged: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    return sum(e - s for s, e in merge(intervals, lo, hi))


def gaps(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The idle stretches of ``[lo, hi]`` between busy intervals."""
    out, t = [], lo
    for s, e in merge(intervals, lo, hi):
        if s > t:
            out.append((t, s))
        t = e
    if hi > t:
        out.append((t, hi))
    return out


def module_ns(events: Iterable[Event], prefix: str) -> float:
    """Summed device time of the programs whose name starts with
    ``prefix`` (``jit_<function>`` for a jitted function)."""
    return sum(e.dur_ns for e in events
               if e.plane.startswith(DEVICE_PREFIX)
               and e.line == MODULES_LINE and e.name.startswith(prefix))


def op_label(op: Event, modules: Sequence[Event],
             starts: Sequence[float]) -> str:
    """``<program>/<op>`` for a device op: the HLO instruction's name
    (``%while.3 = ...`` gives ``while.3``) under the program running at
    its start (``jit_f(123)`` gives ``jit_f``); ``modules`` sorted by
    start, ``starts`` their starts."""
    short = op.name.split(" = ", 1)[0].lstrip("%")
    i = bisect.bisect_right(starts, op.start_ns) - 1
    if i >= 0 and op.start_ns < modules[i].end_ns:
        return f"{modules[i].name.split('(', 1)[0]}/{short}"
    return short


def top_ops(events: Sequence[Event], n: int = 10
            ) -> List[Tuple[str, float]]:
    """The ``n`` device ops with the most summed time, in seconds."""
    total: Dict[str, float] = collections.defaultdict(float)
    for plane, ops in device_ops(events).items():
        modules = sorted((e for e in events if e.plane == plane
                          and e.line == MODULES_LINE),
                         key=lambda m: m.start_ns)
        starts = [m.start_ns for m in modules]
        for e in ops:
            total[op_label(e, modules, starts)] += e.dur_ns
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [(name, ns / 1e9) for name, ns in ranked]


def attribute(idle: Sequence[Interval],
              spans: Sequence[Tuple[float, float, str]],
              outside: str = "untraced") -> Dict[str, float]:
    """Split idle time by the innermost (shortest) host span covering
    each part of it; parts no span covers go to ``outside``.  Returns
    nanoseconds per label."""
    spans = sorted(spans)
    starts = [s for s, _e, _n in spans]
    longest = max((e - s for s, e, _n in spans), default=0.0)
    out: Dict[str, float] = collections.defaultdict(float)
    for g0, g1 in idle:
        # spans that can overlap [g0, g1]: start before g1, end after g0
        i0 = bisect.bisect_left(starts, g0 - longest)
        i1 = bisect.bisect_right(starts, g1)
        near = [sp for sp in spans[i0:i1] if sp[1] > g0]
        cuts = sorted({g0, g1} | {t for s, e, _n in near for t in (s, e)
                                  if g0 < t < g1})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            cover = [(e - s, n) for s, e, n in near if s <= mid < e]
            out[min(cover)[1] if cover else outside] += b - a
    return dict(out)


@dataclasses.dataclass
class DeviceTrace:
    """What the per-layer readers take from the device trace."""
    window_ns: float                 # length of the traced window
    busy_ns: float                   # union of device ops, mean over chips
    devices: int
    apply_ns: float                  # device time of the stacked apply
    top_ops: List[Tuple[str, float]]
    idle_by_host: List[Tuple[str, float]]


def reduce(events: Sequence[Event], window: Interval,
           host_spans: Sequence[Tuple[float, float, str]],
           apply_prefix: str) -> Optional[DeviceTrace]:
    """The device numbers of one traced window; None where the trace
    holds no device operation."""
    lo, hi = window
    per_device = device_ops(events)
    if not per_device or hi <= lo:
        return None
    busy = [busy_ns(((e.start_ns, e.end_ns) for e in ops), lo, hi)
            for ops in per_device.values()]
    in_window = [e for e in events if e.end_ns > lo and e.start_ns < hi]
    idle_ns: Dict[str, float] = collections.defaultdict(float)
    for ops in per_device.values():
        idle = gaps(((e.start_ns, e.end_ns) for e in ops), lo, hi)
        for name, ns in attribute(idle, host_spans).items():
            idle_ns[name] += ns / len(per_device)
    idle_rank = sorted(idle_ns.items(), key=lambda kv: -kv[1])[:10]
    return DeviceTrace(
        window_ns=hi - lo, busy_ns=sum(busy) / len(busy),
        devices=len(per_device),
        apply_ns=module_ns(in_window, apply_prefix),
        top_ops=top_ops(in_window),
        idle_by_host=[(name, ns / 1e9) for name, ns in idle_rank])
