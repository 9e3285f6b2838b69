"""Median host time of a served chunk: each ``serve.chunk`` span less the
fence spans nested in it (``engine.masks_fence``, ``engine.knn_fence``,
``engine.straggler_fence``: where the host waits on the device), from
the program's ``stats()["trace"]`` (session execute, ``serve/engine.py``,
``core/planner.py``, ``core/engine.py``)."""
import numpy as np

from harness.drive import quantile

FENCES = ("engine.masks_fence", "engine.knn_fence", "engine.straggler_fence")


def read(rec):
    spans = rec.window.stats.get("trace", {}).get("spans", {})
    chunk = spans.get("serve.chunk")
    if not chunk or not chunk["ring"]:
        return None
    c = np.asarray(chunk["ring"], np.int64)
    c = c[np.argsort(c[:, 0], kind="stable")]
    host = c[:, 1].astype(np.float64)
    for name in FENCES:
        for s, d in spans.get(name, {}).get("ring", []):
            i = int(np.searchsorted(c[:, 0], s, side="right")) - 1
            if i >= 0 and s < c[i, 0] + c[i, 1]:
                host[i] -= d
    return quantile(host, 0.5) / 1e6
