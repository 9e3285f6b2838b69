"""Requests per chunk that ``RetrievalServer._next_chunk`` left queued
when it rounded a partial signature group down to a power of two: the
program's ``serve.rounded_off`` counter over ``serve.chunks`` (serving
layer, ``serve/engine.py``)."""


def read(rec):
    counters = rec.window.stats.get("trace", {}).get("counters", {})
    if not counters.get("serve.chunks"):
        return None
    return counters.get("serve.rounded_off", 0) / counters["serve.chunks"]
