"""Reduce a JAX profiler trace to device busy time, idle gaps and op sums.

    python bench/trace_reduce.py <profile dir>   # print planes and lines

``load`` reads the ``.xplane.pb`` that ``jax.profiler.trace`` wrote into a
:class:`Trace`: the device's operations and program executions, and the
benchmark's own host spans (``jax.profiler.TraceAnnotation`` names that
start with ``bench.``).  Everything after ``load`` works on plain tuples,
so the tests build traces by hand.

* ``busy``: the union of operation intervals on a device, clipped to the
  window (the ``bench.window`` span).  Idle share is 1 - busy / window.
* ``gaps``: the complement of that union inside the window; each gap is
  named by the innermost benchmark span in force at its midpoint.
* ``seconds_by_name`` / ``count_by_name``: device durations and counts of
  operations or program executions, by name.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]            # (start_ns, end_ns)
Event = Tuple[str, int, int]          # (name, start_ns, end_ns)

SPAN_PREFIX = "bench."
WINDOW_SPAN = "window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass
class Trace:
    """One traced window.  ``ops`` and ``modules`` hold one list per
    device used; ``spans`` are the benchmark's host spans without their
    ``bench.`` prefix."""

    ops: List[List[Event]]
    modules: List[List[Event]]
    spans: List[Event]

    @property
    def window(self) -> Interval:
        wins = [(s, e) for n, s, e in self.spans if n == WINDOW_SPAN]
        if len(wins) != 1:
            raise ValueError(f"expected one {WINDOW_SPAN!r} span, "
                             f"found {len(wins)}")
        return wins[0]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint union of half-open intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_ns(events: Sequence[Event], window: Interval) -> int:
    """Length of the union of the events' intervals inside ``window``."""
    return sum(e - s for s, e in
               clip(union([(s, e) for _, s, e in events]), window))


def gaps(events: Sequence[Event], window: Interval) -> List[Interval]:
    """The parts of ``window`` that no event covers, in time order."""
    lo, hi = window
    out, t = [], lo
    for s, e in clip(union([(s, e) for _, s, e in events]), window):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def span_at(spans: Sequence[Event], t: int) -> str:
    """The innermost (latest-starting) span covering time ``t``."""
    best: Optional[Event] = None
    for name, s, e in spans:
        if s <= t < e and (best is None or s >= best[1]):
            best = (name, s, e)
    return best[0] if best is not None else "none"


def named_gaps(trace: Trace, device: int = 0) -> List[Tuple[str, float]]:
    """Idle gaps of one device, longest first: ``(span name, seconds)``,
    named ``window`` where no span inside the window is in force."""
    out = [(span_at(trace.spans, (s + e) // 2), (e - s) * 1e-9)
           for s, e in gaps(trace.ops[device], trace.window)]
    return sorted(out, key=lambda g: -g[1])


def seconds_by_name(events: Sequence[Event],
                    window: Optional[Interval] = None) -> Dict[str, float]:
    """Summed durations by event name (inside ``window`` when given)."""
    out: Dict[str, float] = {}
    for name, s, e in events:
        if window is not None:
            s, e = max(s, window[0]), min(e, window[1])
            if e <= s:
                continue
        out[name] = out.get(name, 0.0) + (e - s) * 1e-9
    return out


def count_by_name(events: Sequence[Event],
                  window: Optional[Interval] = None) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for name, s, e in events:
        if window is None or (s >= window[0] and e <= window[1]):
            out[name] = out.get(name, 0) + 1
    return out


def summary(trace: Trace, top: int = 10) -> Dict:
    """What the harness prints: busy and window seconds (averaged over the
    devices), the idle share, and the breakdown of device 0."""
    lo, hi = trace.window
    busy = [busy_ns(ops, (lo, hi)) * 1e-9 for ops in trace.ops]
    window_s = (hi - lo) * 1e-9
    ops = seconds_by_name(trace.ops[0], (lo, hi))
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": window_s,
        "idle_share": 1.0 - sum(busy) / len(busy) / window_s,
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": named_gaps(trace)[:top],
    }


# ----------------------------------------------------------- reading xplane


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def _device_planes(data, devices: int):
    planes = {}
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < devices:
            planes[int(m.group(1))] = plane
    if len(planes) != devices:
        raise ValueError(f"trace holds device planes {sorted(planes)}, "
                         f"expected {devices}")
    return [planes[i] for i in range(devices)]


def _line(plane, name: str) -> List[Event]:
    for line in plane.lines:
        if line.name == name:
            return [(ev.name, int(ev.start_ns), int(ev.end_ns))
                    for ev in line.events]
    return []


def module_name(event_name: str) -> str:
    """``jit__lambda(1447...)`` -> ``jit__lambda``."""
    return event_name.split("(", 1)[0]


def op_name(event_name: str) -> str:
    """An op event is named by its HLO text, ``%fusion.12 = f32[...] ...``:
    keep the instruction's name."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def qualify_ops(ops: Sequence[Event], modules: Sequence[Event]) -> List[Event]:
    """Name each op ``<program>/<instruction>`` by the program execution
    whose interval holds the op's start."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        mod = (module_name(mods[i][0])
               if i >= 0 and s < mods[i][2] else "?")
        out.append((f"{mod}/{op_name(name)}", s, e))
    return out


def load(logdir: str, devices: int = 1) -> Trace:
    """Read the newest trace under ``logdir``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(logdir))
    dev = _device_planes(data, devices)
    spans: List[Event] = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name[len(SPAN_PREFIX):],
                                  int(ev.start_ns), int(ev.end_ns)))
    modules = [_line(p, "XLA Modules") for p in dev]
    ops = [qualify_ops(_line(p, "XLA Ops"), m) for p, m in zip(dev, modules)]
    return Trace(ops=ops, modules=modules, spans=spans)


def describe(logdir: str, sample: int = 8) -> str:
    """Planes, lines, event counts and a few event names of a trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(logdir))
    out = []
    for plane in data.planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({ev.name[:100] for ev in evs})
            span = ((evs[0].start_ns, evs[-1].end_ns) if evs else None)
            out.append(f"  line {line.name!r}: {len(evs)} events, "
                       f"{len(names)} names, span {span}: {names[:sample]}")
    return "\n".join(out)


if __name__ == "__main__":
    print(describe(sys.argv[1]))
