"""Operations and bytes of the top-k kernels, from one call's shapes.

The top-k kernels of ``kernels/fused_topk.py`` appear in the device trace
as custom calls named after the functions that make their ``pallas_call``
(``topk_l2_pallas``, ``topk_l2_masked_pallas``). A call scans candidate
rows against its queries: for each (query, candidate) pair the squared
distance ``|q|^2 + |p|^2 - 2 q.p`` costs 4 * D operations (the
candidate's norm and the dot product), over operands of shape
``(G, C, D)`` (the masked
kernel: each query its own candidate tile) or ``(M, D)`` against
``(N, D)`` (the plain kernel). The running top-k merge is not counted:
the least time is what the distances alone need. Bytes are every operand
read and every output written once.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from harness import trace as tracemod

TOPK = ("topk_l2_pallas", "topk_l2_masked_pallas")


def flops(ins: List[Tuple[str, List[int]]]) -> Optional[float]:
    cube = [d for t, d in ins if len(d) == 3]
    if cube:
        g, c, d = max(cube, key=lambda s: s[0] * s[1] * s[2])
        return 4.0 * g * c * d
    mats = [d for t, d in ins if len(d) == 2]
    if len(mats) >= 2:
        (m, d), (n, _) = mats[0], mats[1]
        return 4.0 * m * n * d
    return None


def cost(detail: str) -> Optional[Tuple[float, float]]:
    """``(operations, bytes)`` of one kernel call from its HLO text, or
    None where the trace does not carry the shapes."""
    sh = tracemod.operand_shapes(detail)
    if sh is None:
        return None
    outs, ins = sh
    f = flops(ins)
    if f is None:
        return None
    return f, tracemod.nbytes(ins) + tracemod.nbytes(outs)
