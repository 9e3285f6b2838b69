"""Beam rounds per served chunk: the summed ``EngineStats.knn_rounds``
of the chunks (first rounds and straggler rounds of every KNN group)
over ``serve.chunks``, from the program's counters (session execute,
``core/engine.py``)."""


def read(rec):
    counters = rec.window.stats.get("trace", {}).get("counters", {})
    if not counters.get("serve.chunks") or \
            "engine.knn_rounds" not in counters:
        return None
    return counters["engine.knn_rounds"] / counters["serve.chunks"]
