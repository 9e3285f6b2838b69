"""Jitted op wrappers over the Pallas kernels with CPU fallbacks.

Dispatch: on TPU the Pallas kernels run natively; on CPU we run either the
pure-jnp oracle (fast XLA path, default) or the Pallas kernel in
``interpret=True`` mode (used by the correctness tests). All three share one
signature per op, so the platform code never branches.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref

_BACKEND = None


def backend() -> str:
    global _BACKEND
    if _BACKEND is None:
        _BACKEND = jax.default_backend()
    return _BACKEND


def use_pallas() -> bool:
    return backend() == "tpu"


# ---------------------------------------------------------------------------
# pairwise squared-L2
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("interpret",))
def pairwise_sq_l2(q, p, interpret: bool = False):
    if use_pallas() or interpret:
        from repro.kernels.pairwise_l2 import pairwise_sq_l2_pallas
        return pairwise_sq_l2_pallas(q, p, interpret=not use_pallas())
    return ref.pairwise_sq_l2(q, p)


def pairwise_sq_l2_host(q, p) -> "np.ndarray":
    """``pairwise_sq_l2`` of host arrays, back on the host, with both row
    counts zero-padded to powers of two (at least 8). A recursive
    caller — the index build clusters thousands of node sizes — then
    compiles O(log^2 n) programs instead of one per size."""
    import numpy as np
    q = np.asarray(q, np.float32)
    p = np.asarray(p, np.float32)
    m, n = len(q), len(p)
    qp = np.zeros((max(8, _ceil_pow2(m)), q.shape[1]), np.float32)
    pp = np.zeros((max(8, _ceil_pow2(n)), p.shape[1]), np.float32)
    qp[:m] = q
    pp[:n] = p
    return np.asarray(pairwise_sq_l2(qp, pp))[:m, :n]


# ---------------------------------------------------------------------------
# top-k nearest
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def topk_l2(q, p, k: int, interpret: bool = False):
    if use_pallas() or interpret:
        from repro.kernels.fused_topk import topk_l2_pallas
        return topk_l2_pallas(q, p, k, interpret=not use_pallas())
    return ref.topk_l2(q, p, k)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def topk_l2_masked(q, p, valid, k: int, interpret: bool = False,
                   lb2=None, kth0=None):
    """Per-query candidate tiles + validity mask (hybrid-engine leaf scan).
    q: (G, D), p: (G, C, D), valid: (G, C). ``lb2`` (optional, (G, C)):
    per-candidate squared ball lower bounds — enables the Pallas tile
    early-out (skip a grid step's distance + merge when no valid
    candidate's bound beats the threshold). ``kth0`` (optional, (G,)):
    the kth squared distance of the carry the caller merges the result
    into, carry first, with a stable top-k — candidates at or above it
    are left out. Neither changes the merged result. Returns (d2, idx,
    work): ``work`` (2,) int32 is the kernel's grid steps that computed
    distances and the merge passes they ran. The pure-jnp reference
    path computes everything regardless, ignores both hints and reports
    no kernel work."""
    if use_pallas() or interpret:
        from repro.kernels.fused_topk import topk_l2_masked_pallas
        d2, idx, work = topk_l2_masked_pallas(
            q, p, valid, k, interpret=not use_pallas(), lb2=lb2, kth0=kth0)
        return d2, idx, jnp.sum(work, axis=0)
    d2, idx = ref.topk_l2_masked(q, p, valid, k)
    return d2, idx, jnp.zeros(2, jnp.int32)


def _ceil_pow2(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


def quant_lb2(q, codes, cscale, cppq, ceps, valid, *, precision: str,
              interpret: bool = False):
    """Widened squared lower bounds from a reduced-precision candidate
    scan (semantics: ``ref.quant_lb2``; conservative-bound contract: for
    every valid candidate the result is <= the true fp32 squared
    distance)."""
    if use_pallas() or interpret:
        from repro.kernels.fused_topk import quant_lb2_pallas
        return quant_lb2_pallas(q, codes, cscale, cppq, ceps, valid,
                                precision=precision,
                                interpret=not use_pallas())
    return ref.quant_lb2(q, codes, cscale, cppq, ceps, valid,
                         precision=precision)


@functools.partial(jax.jit, static_argnames=("k", "precision", "interpret"))
def topk_l2_masked_mp(q, sel, valid, data_tiles, pdata, pscale, pppq, peps,
                      k: int, lb2=None, kth0=None, *, precision: str,
                      interpret: bool = False):
    """Mixed-precision leaf scan with exact fp32 rescue — row-identical
    to ``topk_l2_masked`` over the gathered candidates.

    Instead of gathering fp32 points, takes the per-round tile selection
    ``sel`` (G, W) plus the FULL per-layout arrays so the wide gather
    happens on the narrow codes: ``data_tiles`` (T, cap, D) fp32,
    ``pdata``/``pscale``/``pppq``/``peps`` the matching quantized planes
    (``repro.utils.quant.plan_tiles``). Candidate c of query g is slot
    ``c % cap`` of tile ``sel[g, c // cap]`` — the same ordering the
    fp32 path's ``bucket_rows[sel].reshape(g, -1)`` produces.

    Three stages, all under one jit:
      1. reduced-precision scan -> widened squared lower bounds
         (``quant_lb2``); optionally tightened with the caller's ball
         bounds ``lb2`` (same units).
      2. iterative fp32 rescue: repeatedly rescore the R lowest-bound
         unrescued candidates in fp32, tightening the running kth; a
         candidate whose bound exceeds ``min(kth0, running kth)``
         STRICTLY is refuted — its true distance can then never reach
         (or even tie) the final top-k, so omitting it is exact.
         ``kth0`` (G,) optional: the caller's carry-over kth SQUARED
         distance (tightens refutation from the first iteration).
      3. stable top-k over the rescued distances in candidate-index
         order — the same tie-break law as the fp32 kernel/oracle.

    Returns (d2 (G, k) ascending, idx (G, k) into [0, W*cap), rescued
    (G,) int32 — per-query fp32-rescored candidate counts, the
    numerator of the rescue ratio reported by ``explain()``).
    """
    g, w = sel.shape
    t, cap, d = data_tiles.shape
    c = w * cap
    kk = max(1, min(k, c))
    qf = q.astype(jnp.float32)

    codes = jnp.take(pdata, sel, axis=0).reshape(g, c, d)
    cscale = jnp.repeat(jnp.take(pscale, sel, axis=0), cap, axis=1)
    cppq = jnp.take(pppq, sel, axis=0).reshape(g, c)
    ceps = jnp.repeat(jnp.take(peps, sel, axis=0), cap, axis=1)
    lb2q = quant_lb2(qf, codes, cscale, cppq, ceps, valid,
                     precision=precision, interpret=interpret)
    if lb2 is not None:
        lb2q = jnp.maximum(lb2q, lb2)

    qq = jnp.sum(qf * qf, axis=1)[:, None]
    kvec = (kth0.astype(jnp.float32) if kth0 is not None
            else jnp.full((g,), jnp.inf, jnp.float32))
    vmask = valid != 0

    r = min(c, max(32, _ceil_pow2(2 * k)))
    budget = c // r + (1 if c % r else 0) + 1
    rows_idx = jnp.arange(g, dtype=jnp.int32)[:, None]

    def _live(d2full, bd):
        thresh = jnp.minimum(kvec, bd[:, -1])
        return vmask & jnp.isinf(d2full) & (lb2q <= thresh[:, None])

    def cond(st):
        it, d2full, bd = st
        return (it < budget) & jnp.any(_live(d2full, bd))

    def body(st):
        it, d2full, bd = st
        live = _live(d2full, bd)
        key = jnp.where(live, lb2q, jnp.inf)
        negk, pick = jax.lax.top_k(-key, r)          # R lowest bounds
        pv = jnp.isfinite(-negk)                     # real (live) picks
        tile = jnp.take_along_axis(sel, pick // cap, axis=1)
        slot = pick % cap
        pts = data_tiles[tile, slot]                 # (G, R, D) fp32
        pp = jnp.sum(pts * pts, axis=2)
        cross = jnp.einsum("gd,grd->gr", qf, pts,
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
        d2 = jnp.maximum(qq + pp - 2.0 * cross, 0.0)
        d2 = jnp.where(pv, d2, jnp.inf)
        d2full = d2full.at[rows_idx, pick].min(d2)
        alld = jnp.concatenate([bd, d2], axis=1)
        negd, _ = jax.lax.top_k(-alld, kk)
        return it + 1, d2full, -negd

    d2full0 = jnp.full((g, c), jnp.inf, jnp.float32)
    bd0 = jnp.full((g, kk), jnp.inf, jnp.float32)
    _, d2full, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), d2full0, bd0))
    rescued = jnp.sum((jnp.isfinite(d2full) & vmask).astype(jnp.int32),
                      axis=1)
    dfin = jnp.where(vmask & jnp.isfinite(d2full), d2full, jnp.inf)
    negd, idx = jax.lax.top_k(-dfin, kk)
    dd = -negd
    idx = jnp.where(jnp.isfinite(dd), idx, -1)
    if kk < k:
        dd = jnp.pad(dd, ((0, 0), (0, k - kk)), constant_values=jnp.inf)
        idx = jnp.pad(idx, ((0, 0), (0, k - kk)), constant_values=-1)
    return dd, idx, rescued


def topk_l2_blocked(q, p, k: int, row_block: int = 2048):
    import numpy as np
    ds, is_ = [], []
    for i in range(0, q.shape[0], row_block):
        d, ix = topk_l2(q[i:i + row_block], p, k)
        ds.append(np.asarray(d))
        is_.append(np.asarray(ix))
    return np.concatenate(ds), np.concatenate(is_)


# ---------------------------------------------------------------------------
# LPGF force field
# ---------------------------------------------------------------------------
@functools.partial(jax.jit,
                   static_argnames=("radius", "g_mean", "interpret"))
def lpgf_force(points, radius: float, g_mean: float,
               interpret: bool = False):
    if use_pallas() or interpret:
        from repro.kernels.lpgf_force import lpgf_force_pallas
        return lpgf_force_pallas(points, radius, g_mean,
                                 interpret=not use_pallas())
    return ref.lpgf_force(points, radius, g_mean)


# ---------------------------------------------------------------------------
# flash attention (model hot path; models call through here on TPU)
# ---------------------------------------------------------------------------
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    interpret: bool = False):
    if use_pallas() or interpret:
        from repro.kernels.flash_attention import flash_attention_pallas
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      interpret=not use_pallas())
    return ref.flash_attention(q, k, v, causal=causal, window=window)
