"""Seconds of the build's index phase (``core/index.py``,
``core/dpc.py``), from ``prepare()``'s report, ``phase_s["index"]``."""


def read(rec):
    return rec.phase_s.get("index")
