"""The open-loop drive of ``RetrievalServer`` in real wall time.

Requests are submitted when their scheduled time comes, whatever the
server is doing: one that falls due while a chunk runs is submitted as
soon as the harness gets control back, stamped with its scheduled time
(``submit(now=...)``), so its latency runs from when it was due. Between
arrivals the harness calls ``poll()``; when ``poll()`` has nothing due it
sleeps until the next arrival or the server's ``next_due()``.

The harness times each of its own calls into the server. A call in which
the server's batch count rose ran a chunk: its wall time is that chunk's
service time, and every request it resolved spent its latency minus that
time waiting. With ``spans=True`` each call is wrapped in a
``jax.profiler.TraceAnnotation`` (``bench.submit``, ``bench.poll``,
``bench.idle_wait``, ``bench.flush``) so that a profiler trace can say
what the host was doing in each device gap.
"""
from __future__ import annotations

import contextlib
import math
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence

import numpy as np

from harness.traffic import Spec


class Compiles:
    """Counts the programs JAX lowers (each new jit shape in this
    process, whether the persistent cache then has it or not) and the
    persistent-cache hits, by phase."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring as mon
        self.phase = "setup"
        self.lowered: dict = {}
        self.hits: dict = {}
        self.names: dict = {}

        def on_duration(event, duration, **kw):
            if event == self.LOWER:
                self.lowered[self.phase] = self.lowered.get(self.phase, 0) + 1
                self.names.setdefault(self.phase, []).append(
                    str(kw.get("fun_name", "?")))

        def on_event(event, **kw):
            if event == self.HIT:
                self.hits[self.phase] = self.hits.get(self.phase, 0) + 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)


@dataclass
class Call:
    kind: str          # submit | poll | flush
    start: float
    seconds: float
    batches: int       # chunks the call ran


@dataclass
class Window:
    seconds: float
    t0: float                          # clock at window start
    specs: Sequence[Spec]
    results: List[Optional[object]]    # RetrievalResult per request
    resolved_by: List[Optional[int]]   # index into calls
    calls: List[Call] = field(default_factory=list)
    lateness: List[float] = field(default_factory=list)
    error: Optional[str] = None
    stats: dict = field(default_factory=dict)


def _span(on: bool, name: str):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def drive(srv, specs: Sequence[Spec], make_request: Callable[[Spec], object],
          seconds: float, *, spans: bool = False,
          clock: Callable[[], float] = time.monotonic,
          on_close: Optional[Callable[[], None]] = None) -> Window:
    """Offer ``specs`` to ``srv`` on their schedule for ``seconds``, then
    serve whatever the window left queued (its latency counts) and
    return the record. ``on_close`` runs the moment the window closes,
    before that drain."""
    n = len(specs)
    reqs = [make_request(s) for s in specs]
    t0 = clock()
    end = t0 + seconds
    due = [t0 + s.t for s in specs]
    w = Window(seconds=seconds, t0=t0, specs=specs, results=[None] * n,
               resolved_by=[None] * n)
    futs: List[Optional[object]] = [None] * n
    open_ids: List[int] = []

    def timed(kind: str, fn, new: Optional[int] = None):
        b0 = srv.n_batches
        s = clock()
        with _span(spans, f"bench.{kind}"):
            out = fn()
        e = clock()
        if new is not None:          # a submit: its future joins the open
            futs[new] = out
            open_ids.append(new)
        ran = srv.n_batches - b0
        if ran:
            w.calls.append(Call(kind, s, e - s, ran))
            ci = len(w.calls) - 1
            still = []
            for i in open_ids:
                if futs[i].done():
                    w.resolved_by[i] = ci
                else:
                    still.append(i)
            open_ids[:] = still
        return out

    i = 0
    try:
        with _span(spans, "bench.window"):
            while True:
                now = clock()
                if now >= end:
                    break
                while i < n and due[i] <= now:
                    w.lateness.append(clock() - due[i])
                    timed("submit", lambda: srv.submit(reqs[i], now=due[i]),
                          new=i)
                    i += 1
                if timed("poll", srv.poll):
                    continue
                nxt = min(due[i] if i < n else math.inf,
                          srv.next_due() or math.inf, end)
                wait = nxt - clock()
                if wait > 0:
                    with _span(spans, "bench.idle_wait"):
                        time.sleep(wait)
        if on_close is not None:
            on_close()
        # arrivals of the window the loop had not reached yet
        while i < n and due[i] < end:
            timed("submit", lambda: srv.submit(reqs[i], now=due[i]), new=i)
            i += 1
        timed("flush", srv.flush)
    except Exception as e:  # a failed chunk: its requests stay unanswered
        w.error = f"{type(e).__name__}: {e}"
    for j, f in enumerate(futs):
        if f is not None and f.done():
            w.results[j] = f.result()
    w.specs = specs[:i]
    w.results = w.results[:i]
    w.resolved_by = w.resolved_by[:i]
    w.stats = srv.stats()
    return w


def quantile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated quantile (numpy's default), the benchmark's
    own copy of the serving benchmark's latency arithmetic."""
    return float(np.quantile(np.asarray(values, np.float64), q))
