"""Seconds of the build's LPGF phase (``core/lpgf.py``), from
``prepare()``'s report, ``phase_s["lpgf"]``."""


def read(rec):
    return rec.phase_s.get("lpgf")
