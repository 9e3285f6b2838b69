"""Pallas TPU kernel: blocked pairwise squared-L2 distances.

The platform's hottest op (DPC density pass, V.K/V.R scans, LPGF) — exact
all-pairs distances in the MXU form ||q||^2 - 2 q.pT + ||p||^2.

Tiling: grid over (M/BM, N/BN); each program loads a (BM, D) query tile and
a (BN, D) point tile into VMEM, runs one (BM x D) @ (D x BN) MXU matmul in
fp32, and fuses the norm terms. BM/BN default 256 and D is padded to a
multiple of 128 by the wrapper, so every matmul dim is MXU-aligned.
VMEM/program ~= (BM + BN) * D * 4B + BM * BN * 4B  (~1.3 MB at D=512).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(q_ref, p_ref, out_ref):
    q = q_ref[...].astype(jnp.float32)          # (BM, D)
    p = p_ref[...].astype(jnp.float32)          # (BN, D)
    qq = jnp.sum(q * q, axis=1, keepdims=True)  # (BM, 1)
    pp = jnp.sum(p * p, axis=1, keepdims=True)  # (BN, 1)
    cross = jax.lax.dot_general(
        q, p, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)      # (BM, BN)
    out_ref[...] = jnp.maximum(qq + pp.T - 2.0 * cross, 0.0)


def _pad(x, m, axis, value=0):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def pairwise_sq_l2_pallas(q, p, *, bm: int = 256, bn: int = 256,
                          interpret: bool = False):
    """q: (M, D), p: (N, D) -> (M, N) fp32 squared distances."""
    m, d = q.shape
    n = p.shape[0]
    q2 = _pad(_pad(q.astype(jnp.float32), 128, 1), bm, 0)
    p2 = _pad(_pad(p.astype(jnp.float32), 128, 1), bn, 0)
    mp, dp = q2.shape
    np_ = p2.shape[0]
    grid = (mp // bm, np_ // bn)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, dp), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(q2, p2)
    return out[:m, :n]
