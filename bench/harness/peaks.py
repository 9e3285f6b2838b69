"""Published peaks per device kind, and the roofline share they give.

Keyed by ``jax.Device.device_kind``. A device that is not in the table
is an error, never a default.
"""
from __future__ import annotations

from typing import Tuple

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture): per
    # chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s.
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add "
            f"them to bench/harness/peaks.py with their source") from None


def least_time(flops: float, nbytes: float, device_kind: str
               ) -> Tuple[float, str]:
    """The least time the chip could take for ``flops`` operations and
    ``nbytes`` of HBM traffic, and which bound binds. Float32 operations
    are counted against the bf16 peak: that can only understate a share,
    never push it past 100%."""
    p = peaks(device_kind)
    t_c = flops / p["bf16_flops"]
    t_m = nbytes / p["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
