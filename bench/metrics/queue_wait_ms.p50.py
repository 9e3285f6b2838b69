"""Median time a request waited in the server before the harness call
that ran its chunk: its latency (from its scheduled arrival) less that
call's wall time (serving layer, ``serve/engine.py``)."""
from harness.drive import quantile


def read(rec):
    w = rec.window
    waits = [r.latency_s - w.calls[c].seconds
             for r, c in zip(w.results, w.resolved_by)
             if r is not None and c is not None]
    return quantile(waits, 0.5) * 1e3 if waits else None
