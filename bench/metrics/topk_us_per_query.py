"""Device time of the top-k Pallas kernels in the traced window, summed
and divided by the requests served in it (``kernels/fused_topk.py``)."""
from harness import kernels
from harness import trace as tracemod


def read(rec):
    if rec.trace is None or not rec.served_in_trace:
        return None
    ops = tracemod.kernel_ops(rec.trace, kernels.TOPK)
    if not ops:
        return None
    ndev = max(1, len(rec.trace.devices))
    return sum(e.dur_ns for e in ops) / ndev / 1e3 / rec.served_in_trace
