"""Percent of the served chunks' time in which no op ran on the device:
100 * (1 - device-op time inside the ``serve.chunk`` intervals / the
chunks' total time), over the chunks inside the traced window. Unlike
``device_idle_pct`` it leaves out the batching window between chunks.

The program stamps its rings with the host clock (``time.time_ns()``);
the trace gives times from the profile's start. The two are joined
through the harness's own calls: each call that ran a chunk has its
start on ``time.monotonic()`` (``Window.calls``) and its ``bench.<kind>``
span in the trace, opened right after. ``Window.t0`` against the
``bench.window`` span gives a first estimate, the calls' least residual
the correction, and the program's ``time.time_ns() -
time.monotonic_ns()``, read when the harness took ``stats()`` right after
the window, maps the rings onto ``time.monotonic()``."""
from harness import trace as tracemod


def read(rec):
    tr = rec.trace
    prog = rec.window.stats.get("trace", {})
    chunk = prog.get("spans", {}).get("serve.chunk")
    wall = prog.get("time_minus_monotonic_ns")
    if (tr is None or not tr.devices or not chunk or not chunk["ring"]
            or wall is None):
        return None
    shift = _mono_to_trace(rec) - wall
    ivs = tracemod.merged((tracemod.Event("serve.chunk", s + shift, d)
                           for s, d in chunk["ring"]), tr.t0_ns, tr.t1_ns)
    total = sum(b - a for a, b in ivs)
    if total <= 0:
        return None
    busy = [_overlap(tracemod.merged(ops, tr.t0_ns, tr.t1_ns), ivs)
            for ops in tr.devices.values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / total)


def _overlap(xs, ys):
    """Total overlap of two sorted lists of disjoint intervals."""
    tot, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        lo = max(xs[i][0], ys[j][0])
        hi = min(xs[i][1], ys[j][1])
        if hi > lo:
            tot += hi - lo
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return tot


def _mono_to_trace(rec):
    """Nanoseconds to add to ``time.monotonic()`` * 1e9 to land on the
    trace's clock. Each call's span opens a little after the harness read
    the call's start, never before, so the least residual is the
    estimate; a call is matched only to a span of about its length."""
    off = rec.trace.t0_ns - rec.window.t0 * 1e9
    res = []
    for c in rec.window.calls:
        near = [e.start_ns - (c.start * 1e9 + off) for e in rec.trace.host
                if e.name == "bench." + c.kind
                and e.dur_ns >= 0.5 * c.seconds * 1e9]
        if near:
            res.append(min(near, key=abs))
    return off + (min(res) if res else 0.0)
