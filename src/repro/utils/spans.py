"""Named host spans and counters, on the profiler's clock.

``span(name, **args)`` is the one timing idiom of the program. It

* enters a ``jax.profiler.TraceAnnotation(name, **args)``, so that a
  profiler trace shows the span on the host plane, whose clock the device
  ops share;
* times itself with ``time.perf_counter_ns()`` (monotonic) and keeps the
  duration on the span object (``seconds``) for callers that report it;
* when a ``Recorder`` is active, records ``(start_ns, dur_ns)`` into that
  recorder's ring for ``name``. ``start_ns`` is ``time.time_ns()``, the
  host clock the profiler stamps its events with (a profile's events are
  that clock less the profile's ``profile_start_time``).

The engine and the planner open spans by name and take no recorder
argument: whoever owns a recorder makes it the active one around the work
it wants recorded (``recording``). The serving loop is single-threaded, so
the active recorder is one module-level slot. With no active recorder a
span only emits its annotation and times itself, about 2.5 us on a TPU
v5e host while the profiler is off (PERF.md, section 6); that covers
``prepare()`` and direct ``Session`` use.

A ``Recorder`` holds one bounded ring of ``(start_ns, dur_ns)`` per span
name with the total count beside it, integer counters (``count``), and
samples: durations measured rather than entered (``sample``), kept in the
same rings. ``snapshot()`` is what ``RetrievalServer.stats()["trace"]``
returns; it carries ``time.time_ns() - time.monotonic_ns()`` as read when
it was taken, so that a reader maps the rings onto the monotonic clock
with an offset read close to when they were recorded.
"""
from __future__ import annotations

import time
from typing import Dict, Optional

import numpy as np
from jax.profiler import TraceAnnotation

RING = 16384        # entries kept per span name

_active: Optional["Recorder"] = None


class _Ring:
    __slots__ = ("buf", "n")

    def __init__(self):
        self.buf = np.zeros((RING, 2), np.int64)
        self.n = 0                      # total ever added

    def add(self, start_ns: int, dur_ns: int) -> None:
        self.buf[self.n % len(self.buf)] = (start_ns, dur_ns)
        self.n += 1

    def entries(self) -> np.ndarray:
        """The kept entries, oldest first."""
        size = len(self.buf)
        if self.n <= size:
            return self.buf[:self.n]
        i = self.n % size
        return np.concatenate([self.buf[i:], self.buf[:i]])


class Recorder:
    """Rings of ``(start_ns, dur_ns)`` per span name and integer
    counters. Not thread-safe: one owner drives it."""

    def __init__(self):
        self._rings: Dict[str, _Ring] = {}
        self.counters: Dict[str, int] = {}

    def add(self, name: str, start_ns: int, dur_ns: int) -> None:
        """Record one interval under ``name``."""
        r = self._rings.get(name)
        if r is None:
            r = self._rings[name] = _Ring()
        r.add(start_ns, dur_ns)

    def sample(self, name: str, dur_ns: int, end_ns: int) -> None:
        """Record a duration measured elsewhere, as the interval that
        ends at ``end_ns`` (host clock)."""
        self.add(name, end_ns - int(dur_ns), int(dur_ns))

    def count(self, name: str, n: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + int(n)

    def snapshot(self) -> dict:
        """``{"spans": {name: {"n": total, "ring": [[start_ns, dur_ns],
        ...]}}, "counters": {name: value}, "time_minus_monotonic_ns":
        offset}`` in plain Python types: the last ``RING`` intervals of
        each name, oldest first, and the host clock less the monotonic
        clock now."""
        return {"spans": {k: {"n": r.n, "ring": r.entries().tolist()}
                          for k, r in self._rings.items()},
                "counters": dict(self.counters),
                "time_minus_monotonic_ns":
                    time.time_ns() - time.monotonic_ns()}


class recording:
    """Make ``rec`` the active recorder inside the ``with`` block (the
    previous one, usually none, comes back after it)."""

    __slots__ = ("rec", "_prev")

    def __init__(self, rec: Optional[Recorder]):
        self.rec = rec

    def __enter__(self) -> Optional[Recorder]:
        global _active
        self._prev, _active = _active, self.rec
        return self.rec

    def __exit__(self, *exc) -> None:
        global _active
        _active = self._prev


class span:
    """``with span("engine.knn_fence") as s: ...``; ``s.seconds`` holds
    the span's duration once the block has ended (see the module doc)."""

    __slots__ = ("name", "seconds", "_ann", "_rec", "_start", "_t0")

    def __init__(self, name: str, **args):
        self.name = name
        self.seconds = 0.0
        self._ann = TraceAnnotation(name, **args)

    def __enter__(self) -> "span":
        self._rec = _active
        self._ann.__enter__()
        if self._rec is not None:
            self._start = time.time_ns()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        dur = time.perf_counter_ns() - self._t0
        self._ann.__exit__(*exc)
        self.seconds = dur / 1e9
        if self._rec is not None:
            self._rec.add(self.name, self._start, dur)
