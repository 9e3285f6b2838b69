"""Percent of the traced window in which no op ran on the device:
1 - (union of the device ops' intervals / window)."""
from harness import trace as tracemod


def read(rec):
    if rec.trace is None or not rec.trace.devices or rec.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - tracemod.busy_s(rec.trace) / rec.trace.window_s)
