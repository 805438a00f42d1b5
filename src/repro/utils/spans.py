"""Host spans of the serving program: one region timed into a stats
mapping and marked in the profiler trace.

    with span(sched.stats, "sched.decode"):
        ...

On exit the region's seconds are added to ``stats["span_s.<name>"]`` and
1 to ``stats["span_n.<name>"]``.  While it is open a
``jax.profiler.TraceAnnotation`` of the same name (``meta`` as its
arguments, e.g. ``request_id``) marks it on the host plane of any
profiler session, on the clock of the device trace; with no session the
annotation costs about a microsecond and records nothing.  Spans nest:
the trace shows each inside its parent.  Time comes from
``time.perf_counter``, never from a scheduler's injectable clock.

The store is the scheduler's ``stats`` mapping (the engine it drives
writes into the same one); docs/serving.md lists every span.
"""
from __future__ import annotations

import time
from typing import MutableMapping

import jax

SECONDS = "span_s."
CALLS = "span_n."


def add(stats: MutableMapping[str, float], name: str, seconds: float,
        calls: int = 1) -> None:
    """Add ``seconds`` and ``calls`` to span ``name``'s counters."""
    s, n = SECONDS + name, CALLS + name
    stats[s] = stats.get(s, 0.0) + seconds
    stats[n] = stats.get(n, 0.0) + calls


class span:
    """Context manager: time the region into ``stats`` under ``name``
    and annotate it in the profiler trace (see the module docstring).
    ``seconds`` holds the region's length once it has closed."""

    __slots__ = ("stats", "name", "seconds", "_ann", "_t0")

    def __init__(self, stats: MutableMapping[str, float], name: str,
                 **meta):
        self.stats = stats
        self.name = name
        self.seconds = 0.0
        self._ann = jax.profiler.TraceAnnotation(name, **meta)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        add(self.stats, self.name, self.seconds)
