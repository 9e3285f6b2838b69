"""Rows fed to the top-k kernel per query: the summed
``EngineStats.rows_scanned`` of the served chunks over their
``EngineStats.queries``, from the program's counters (session execute,
``core/engine.py``)."""


def read(rec):
    counters = rec.window.stats.get("trace", {}).get("counters", {})
    if not counters.get("engine.queries") or \
            "engine.rows_scanned" not in counters:
        return None
    return counters["engine.rows_scanned"] / counters["engine.queries"]
