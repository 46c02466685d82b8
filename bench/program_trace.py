"""The program's own spans and counters in a JAX profiler trace.

    python bench/program_trace.py <profile dir>   # print what they show

The series path (``src/repro``) writes host spans named ``repro.<name>``
through ``jax.profiler.TraceAnnotation``; their keyword arguments are the
events' stats, and a zero-length span is a counter.  ``load`` reads them
from the host planes of a trace, so it needs no device plane and works on
a CPU trace too, as ``(name, start_ns, end_ns, stats)`` with the
``repro.`` prefix kept, so that no program span can be taken for the
benchmark's ``window``.

The readings below work on those tuples, so the tests build them by hand:

* ``lane_use``: function A's share of the pixel-steps its vmapped loop
  issued that a lane needed (``repro.fn_a.lanes``: ``useful``/``issued``).
* ``mean_ms``: the mean duration of the spans of one name, such as
  ``repro.fn_b``, the host time of one function-B application.
* ``named_gaps``: the device's idle gaps, each named by the innermost
  benchmark or program span in force at its midpoint.

``bench/run.py`` does not read these yet: ``trace_reduce.load`` keeps the
benchmark's own spans only (``PERF.md``, Open questions).
"""

from __future__ import annotations

import sys
from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce

PREFIX = "repro."
LANES = "repro.fn_a.lanes"
FN_B = "repro.fn_b"

#: ``(name, start_ns, end_ns, stats)``
ProgramEvent = Tuple[str, int, int, Dict]


def host_events(data, prefix: str = PREFIX) -> List[ProgramEvent]:
    """Host events of a ``jax.profiler.ProfileData`` whose names start
    with ``prefix``, in time order."""
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    out.append((ev.name, int(ev.start_ns), int(ev.end_ns),
                                dict(ev.stats)))
    return sorted(out, key=lambda ev: ev[1])


def load(logdir: str) -> List[ProgramEvent]:
    """The program's events in the newest trace under ``logdir``."""
    from jax.profiler import ProfileData

    return host_events(ProfileData.from_file(trace_reduce.find_xplane(logdir)))


def inside(events: Sequence[ProgramEvent], name: str,
           window: trace_reduce.Interval) -> List[ProgramEvent]:
    """The events called ``name`` that lie wholly inside ``window``."""
    return [ev for ev in events
            if ev[0] == name and ev[1] >= window[0] and ev[2] <= window[1]]


def lane_use(events: Sequence[ProgramEvent],
             window: trace_reduce.Interval) -> Optional[float]:
    """100 * sum(useful) / sum(issued) over the ``repro.fn_a.lanes``
    counters in ``window``; None when there are none."""
    evs = inside(events, LANES, window)
    issued = sum(ev[3]["issued"] for ev in evs)
    return 100.0 * sum(ev[3]["useful"] for ev in evs) / issued \
        if issued else None


def mean_ms(events: Sequence[ProgramEvent], name: str,
            window: trace_reduce.Interval) -> Optional[float]:
    """Mean duration in ms of the ``name`` spans in ``window``; None when
    there are none."""
    evs = inside(events, name, window)
    return 1e-6 * sum(e - s for _, s, e, _ in evs) / len(evs) if evs \
        else None


def named_gaps(trace: trace_reduce.Trace, events: Sequence[ProgramEvent],
               device: int = 0) -> List[Tuple[str, float]]:
    """``trace_reduce.named_gaps`` with the program's spans beside the
    benchmark's: ``(span name, seconds)``, longest first."""
    spans = list(trace.spans) + [(n, s, e) for n, s, e, _ in events]
    return trace_reduce.named_gaps(
        trace_reduce.Trace(ops=trace.ops, modules=trace.modules, spans=spans),
        device)


def describe(logdir: str, devices: int = 1, top: int = 10) -> Dict:
    """Counts by name, the two readings and the longest named gaps."""
    events = load(logdir)
    trace = trace_reduce.load(logdir, devices)
    counts: Dict[str, int] = {}
    for name, *_ in events:
        counts[name] = counts.get(name, 0) + 1
    return {"counts": counts,
            "lane_use_pct": lane_use(events, trace.window),
            "fn_b_host_ms": mean_ms(events, FN_B, trace.window),
            "idle_gaps": named_gaps(trace, events)[:top]}


if __name__ == "__main__":
    print(describe(sys.argv[1]))
