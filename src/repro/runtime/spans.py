"""Host spans and counters of the series path in the profiler's trace.

``span(name, **stats)`` is a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``.  It is live only while a profiler session runs (about a
microsecond when none does), and its keyword arguments become event stats
on the host plane of the same ``.xplane.pb`` as the device's programs, on
the profiler's clock.  A zero-length span is a counter event.

Every span carries ``session=``: the caller's, or else that of the
enclosing :func:`serving` block on this thread.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Iterator, Optional

from jax.profiler import TraceAnnotation

_session: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "repro_session", default=None
)


def span(name: str, session: Optional[str] = None, **stats) -> TraceAnnotation:
    session = session if session is not None else _session.get()
    if session is not None:
        stats["session"] = session
    return TraceAnnotation("repro." + name, **stats)


@contextlib.contextmanager
def serving(session: str) -> Iterator[None]:
    """Tag the spans this thread opens inside the block with ``session``."""
    token = _session.set(session)
    try:
        yield
    finally:
        _session.reset(token)
