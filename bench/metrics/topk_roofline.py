"""Share of the top-k kernels' roofline in the traced window, percent:
the least time their calls need (the larger of operations over the bf16
peak and bytes over HBM bandwidth, per call, from the operand shapes in
the trace) over the time they took (``kernels/fused_topk.py``). Left out
where the trace carries no shapes."""
from harness import kernels, peaks
from harness import trace as tracemod


def read(rec):
    if rec.trace is None:
        return None
    ops = tracemod.kernel_ops(rec.trace, kernels.TOPK)
    least = took = 0.0
    for e in ops:
        c = kernels.cost(e.detail)
        if c is None:
            return None
        least += peaks.least_time(c[0], c[1], rec.device_kind)[0]
        took += e.dur_ns / 1e9
    return 100.0 * least / took if took > 0 else None
