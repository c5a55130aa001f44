"""The program's own host spans in the traced slice, and the device's
idle time inside them.

``repro.obs``'s recorder writes each of its spans into the profiler's
trace as a ``TraceAnnotation`` named ``repro.<name>`` (``submit``,
``validate``, ``batch``, ``cow``, ``sync``...), on the engine's thread
and on the profiler's clock.  This module reads them back from the run's
``.xplane.pb`` (the directory the harness writes:
``.chipbench_traces/<cell>``) and puts them beside the idle intervals of
each device: device 0's are ``run.trace.gaps``; those of further devices
are reduced here the same way.  A profile with no ``repro.*`` span, as
from a program that writes none, reads None.
"""

from __future__ import annotations

import collections
import dataclasses
import os
from typing import Optional

from chipbench import harness, trace

PREFIX = "repro."
NO_SPAN = "(none)"
_CACHE = "_program_spans"


@dataclasses.dataclass
class ProgramSpans:
    window: tuple[int, int]     # ns on the trace clock
    spans: dict                 # name (no prefix) -> [(start, end)] ns
    idle: list                  # per device: [(start, end)] ns, sorted

    def wholly_in(self, name: str) -> list[tuple[int, int]]:
        """The ``name`` spans that lie wholly in the traced slice."""
        lo, hi = self.window
        return [(s, e) for s, e in self.spans.get(name, ())
                if s >= lo and e <= hi]

    def idle_ns(self, intervals) -> float:
        """Device-idle ns inside the union of ``intervals``, the mean
        over devices."""
        cover = trace._union(intervals)
        return sum(e - s for idle in self.idle
                   for s, e in _intersect(cover, idle)) / len(self.idle)

    def idle_by_innermost(self, device: int = 0) -> dict:
        """Idle seconds of one device by the innermost span covering
        them (``NO_SPAN`` where none does)."""
        points = []
        for name, ivs in self.spans.items():
            for s, e in ivs:
                points += [(s, 1, (s, -e, name)), (e, -1, (s, -e, name))]
        for s, e in self.idle[device]:
            points += [(s, 2, None), (e, -2, None)]
        points.sort(key=lambda p: p[0])
        out: collections.Counter = collections.Counter()
        active: collections.Counter = collections.Counter()
        idle, prev = 0, None
        for t, kind, key in points:
            if idle and prev is not None and t > prev:
                out[max(active)[2] if active else NO_SPAN] += (
                    t - prev) * 1e-9
            prev = t
            if kind == 2:
                idle += 1
            elif kind == -2:
                idle -= 1
            else:
                active[key] += kind
                if not active[key]:
                    del active[key]
        return dict(out)


def _intersect(a: list, b: list) -> list:
    """The intervals in both of two sorted lists of disjoint ones."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def reduce(profile, reduced) -> Optional[ProgramSpans]:
    """The ``repro.*`` spans of ``profile`` (a ``ProfileData``) beside
    the idle intervals of the devices that ``reduced`` (its
    ``trace.Reduced``) found; None where there is no such span."""
    spans: dict = collections.defaultdict(list)
    launches, devices = [], []
    for plane in profile.planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            lines = {line.name: list(trace._events(line))
                     for line in plane.lines}
            if trace.OPS_LINE in lines:
                devices.append(
                    (int(plane.name[len(trace.DEVICE_PREFIX):]), lines))
            continue
        for line in plane.lines:
            for name, s, d in trace._events(line):
                if name.startswith(PREFIX):
                    spans[name[len(PREFIX):]].append((s, s + d))
                elif name == trace.LAUNCH:
                    launches.append(s)
    if not spans:
        return None
    idle = [[(s, e) for s, e, _ in reduced.gaps]]
    devices.sort(key=lambda dv: dv[0])
    if len(devices) > 1:
        shift = trace._device_shift(sorted(launches), sorted(
            s for _, s, _ in devices[0][1].get(trace.MODULES_LINE, [])))
        lo, hi = reduced.window
        for _, lines in devices[1:]:
            busy = trace._union(trace._clip(
                ((s + shift, s + d + shift)
                 for _, s, d in lines[trace.OPS_LINE]), lo, hi))
            idle.append(_complement(busy, lo, hi))
    return ProgramSpans(reduced.window,
                        {k: sorted(v) for k, v in spans.items()}, idle)


def _complement(busy: list, lo: int, hi: int) -> list:
    out, cur = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    return out


def of(run) -> Optional[ProgramSpans]:
    """The run's program spans, read once from its trace and kept on the
    run; None for an untraced run or a trace with no ``repro.*`` span."""
    if _CACHE not in run.__dict__:
        found = None
        if run.trace is not None:
            log_dir = os.path.join(harness.ROOT, ".chipbench_traces",
                                   run.cell.name)
            try:
                path = trace.find_xplane(log_dir)
            except FileNotFoundError:
                path = None
            if path is not None:
                from jax.profiler import ProfileData

                found = reduce(ProfileData.from_file(path), run.trace)
        run.__dict__[_CACHE] = found
    return run.__dict__[_CACHE]


def mean_ms(run, name: str) -> Optional[float]:
    """Mean duration of the ``name`` spans wholly in the slice, ms."""
    ps = of(run)
    ivs = ps.wholly_in(name) if ps is not None else []
    if not ivs:
        return None
    return 1e-6 * sum(e - s for s, e in ivs) / len(ivs)


def mean_idle_ms(run, name: str) -> Optional[float]:
    """Device-idle time inside each ``name`` span wholly in the slice,
    the mean per span, ms."""
    ps = of(run)
    ivs = ps.wholly_in(name) if ps is not None else []
    if not ivs:
        return None
    return 1e-6 * sum(ps.idle_ns([iv]) for iv in ivs) / len(ivs)


def idle_share(run, names, within: str) -> Optional[float]:
    """100 x device-idle time inside the ``names`` spans over that
    inside the ``within`` spans, in %."""
    ps = of(run)
    if ps is None:
        return None
    outer = trace._union(ps.spans.get(within, []))
    total = ps.idle_ns(outer)
    if total <= 0:
        return None
    inner = trace._union([iv for n in names for iv in ps.spans.get(n, [])])
    return 100.0 * ps.idle_ns(_intersect(inner, outer)) / total
