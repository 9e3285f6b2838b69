"""Median time a request waited in ``RetrievalServer`` before its chunk
started, from the program's own ``serve.queue_wait`` samples (chunk
start less the request's arrival; ``stats()["trace"]``, serving layer,
``serve/engine.py``)."""
from harness.drive import quantile


def read(rec):
    span = rec.window.stats.get("trace", {}).get("spans", {}).get(
        "serve.queue_wait")
    if not span or not span["ring"]:
        return None
    return quantile([d for _, d in span["ring"]], 0.5) / 1e6
