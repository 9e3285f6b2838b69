"""Hyperspace Movement — Local Parallelized Gravitational Field (paper §5.2.3).

LPGF improves HIBOG's three weaknesses:
  1. radius-bounded force area (R = r_mult · G, G = mean NN distance) instead
     of K-nearest sorting;
  2. piecewise force law (Fig 13) that avoids movement anomalies in tight
     clusters (near ring pulls weakly via 1/C);
  3. parallel evaluation: the paper grid-partitions space across Spark
     executors; the TPU adaptation shards POINTS across the mesh data axis
     (shard_map) and evaluates the radius-masked all-pairs force with the
     blocked pairwise kernel — exact, static-shape, MXU-friendly.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops


def mean_nn_distance(x, sample: int = 4096, seed: int = 0) -> float:
    """G: average distance from each point to its nearest neighbor."""
    n = len(x)
    rng = np.random.default_rng(seed)
    idx = rng.choice(n, size=min(sample, n), replace=False)
    d, _ = ops.topk_l2_blocked(jnp.asarray(x)[idx], jnp.asarray(x), k=2)
    # k=2: first hit is the point itself (distance 0)
    return float(np.sqrt(np.maximum(d[:, 1], 0.0)).mean())


_TEMP_BYTES = 1 << 30   # cap on one (block, N) fp32 temporary


def lpgf_step(x, radius: float, g_mean: float,
              step: float = 0.5) -> np.ndarray:
    """One force-and-move step. x: (N, D) host array -> moved (N, D).

    Displacement = step * F / Σw — the weight-normalized (bounded) pull;
    the raw resultant of the paper's Fig-13 force law grows with the
    neighbor count and diverges if applied directly. Rows are evaluated
    in blocks whose (block, N) fp32 temporaries stay within
    ``_TEMP_BYTES`` each: 4096 rows up to N = 64k, 256 at N = 1M."""
    xj = jnp.asarray(x, jnp.float32)
    n = x.shape[0]
    block = max(8, min(4096, _TEMP_BYTES // (4 * max(1, n))) // 8 * 8)
    if n <= block:
        f, w = ops.lpgf_force(xj, float(radius), float(g_mean))
        disp = f / jnp.maximum(w, 1.0)[:, None]
        return np.asarray(xj + step * disp)
    # blocked evaluation over point tiles (per-tile force vs all points)
    out = np.empty_like(np.asarray(x, np.float32))
    for i in range(0, n, block):
        disp = _tile_disp(xj[i:i + block], xj, radius, g_mean)
        out[i:i + block] = np.asarray(xj[i:i + block] + step * disp)
    return out


@jax.jit
def _tile_disp(tile, allpts, radius, g_mean, c: float = 1.1):
    """Weight-normalized displacement on `tile` points from ALL points."""
    d2 = ops.pairwise_sq_l2(tile, allpts)                  # (T, N)
    # self-distances: exact zeros — mask them
    self_mask = d2 <= 1e-12
    big = 1e30
    d2m = jnp.where(self_mask, big, d2)
    d1sq = jnp.min(d2m, axis=1)                            # nearest^2
    thresh_near = g_mean * jnp.sqrt(d1sq)
    in_r = d2m <= radius * radius
    near = d2m <= thresh_near[:, None]
    far = (~near) & in_r
    w_far = jnp.where(far, d1sq[:, None] / jnp.maximum(d2m, 1e-12), 0.0)
    w = w_far + jnp.where(near & in_r, 1.0 / c, 0.0)
    # F_i = sum_j w_ij (p_j - p_i) = (w @ P) - (sum_j w_ij) * p_i
    wsum = jnp.sum(w, axis=1, keepdims=True)
    f = w @ allpts - wsum * tile
    return f / jnp.maximum(wsum, 1.0)


def lpgf(x, *, r_mult: float = 7.5, iters: int = 2, step: float = 0.5,
         g_mean: Optional[float] = None, seed: int = 0) -> np.ndarray:
    """Full LPGF movement: returns the moved copy of x (original kept by the
    caller for traceability; the displacement matrix M = moved - x)."""
    x = np.asarray(x, np.float32)
    out = x.copy()
    for _ in range(iters):
        g = g_mean if g_mean is not None else mean_nn_distance(out, seed=seed)
        out = lpgf_step(out, radius=r_mult * g, g_mean=g, step=step)
    return out


def hibog(x, *, k: int = 8, iters: int = 2, step: float = 0.5) -> np.ndarray:
    """HIBOG baseline (Li et al. 2021): K-nearest attraction, for the
    paper's comparison experiments (Table 6)."""
    out = np.asarray(x, np.float32).copy()
    for _ in range(iters):
        xj = jnp.asarray(out)
        d, idx = ops.topk_l2_blocked(xj, xj, k=k + 1)
        nbrs = out[np.asarray(idx)[:, 1:]]                 # (N, k, D)
        f = (nbrs - out[:, None, :]).mean(axis=1)
        out = out + step * f
    return out
