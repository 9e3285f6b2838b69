"""Median wall time of the harness's ``submit()``/``poll()`` calls that
ran a chunk, per chunk run: plan, execute, engine, kernels and re-rank
(session execute, ``core/planner.py`` and ``core/engine.py``)."""
from harness.drive import quantile


def read(rec):
    end = rec.window.t0 + rec.window.seconds
    t = [c.seconds / c.batches for c in rec.window.calls
         if c.kind in ("submit", "poll") and c.start < end]
    return quantile(t, 0.5) * 1e3 if t else None
