"""Density Peaks Clustering (Rodriguez & Laio 2014) — the split routine of
the divisive hierarchical index build (paper §6.1.1, Table 7).

DPC picks cluster centers as points that maximize γ = ρ·δ where ρ is local
density and δ is the distance to the nearest higher-density point; the
number of clusters is determined automatically by the γ gap. Exact O(N²)
distances go through the blocked pairwise kernel (TPU adaptation of the
paper's Spark shuffle).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops


@dataclass
class DPCResult:
    labels: np.ndarray       # (N,) cluster id
    centers: np.ndarray      # (K,) indices of center points
    rho: np.ndarray
    delta: np.ndarray


def dpc(x: np.ndarray, *, dc: Optional[float] = None,
        max_clusters: int = 16, min_clusters: int = 2,
        gamma_gap: float = 3.0, block: int = 4096,
        seed: int = 0) -> DPCResult:
    """Cluster x (N, D). Returns labels + center indices.

    dc: density cutoff; default = 2% quantile of pairwise distances
    (sampled). Centers: sorted by γ, cut at the largest relative gap
    (bounded to [min_clusters, max_clusters]).
    """
    x = np.asarray(x, np.float32)
    n = len(x)
    if n <= 2:
        return DPCResult(labels=np.zeros(n, np.int32),
                         centers=np.array([0] if n else [], np.int64),
                         rho=np.ones(n), delta=np.ones(n))
    rng = np.random.default_rng(seed)

    # --- dc from a sampled distance quantile
    if dc is None:
        s = x[rng.choice(n, size=min(1024, n), replace=False)]
        d2s = ops.pairwise_sq_l2_host(s, s)
        pos = np.sqrt(d2s[d2s > 1e-12])
        dc = float(np.quantile(pos, 0.02)) if len(pos) else 1.0
        dc = max(dc, 1e-6)

    # --- rho (gaussian kernel density) and delta, blocked over rows, on
    # the device: only (n,) vectors come back. Row counts are padded to
    # powers of two (the index build calls this for thousands of node
    # sizes) and padding columns are masked out of every reduction.
    m = max(128, ops._ceil_pow2(n))
    xp = np.zeros((m, x.shape[1]), np.float32)
    xp[:n] = x
    xp = jnp.asarray(xp)
    bs = min(block, m)
    rho = np.concatenate([
        np.asarray(_rho_block(xp, i, bs, n, dc * dc))
        for i in range(0, n, bs)])[:n].astype(np.float64)

    order = np.argsort(-rho, kind="stable")  # descending density
    # delta_i = min distance to any point ahead of i in that order: higher
    # density, or equal density and a lower index
    rank = np.empty(m, np.int32)
    rank[order] = np.arange(n, dtype=np.int32)
    rank[n:] = m
    rank = jnp.asarray(rank)
    delta = np.empty(n, np.float64)
    nneigh = np.zeros(n, np.int64)
    for i in range(0, n, bs):
        d2min, arg = _delta_block(xp, rank, i, bs, n)
        delta[i:i + bs] = np.sqrt(np.asarray(d2min))[:n - i]
        nneigh[i:i + bs] = np.asarray(arg)[:n - i]
    top = order[0]
    delta[top] = max(delta[np.isfinite(delta)].max(initial=1.0), 1.0)
    nneigh[top] = top

    # --- centers from the gamma gap
    gamma = rho * delta
    gorder = np.argsort(-gamma, kind="stable")
    gs = gamma[gorder]
    kmax = min(max_clusters, n)
    ratios = (gs[:kmax - 1] + 1e-12) / (gs[1:kmax] + 1e-12)
    k = min_clusters
    if len(ratios) > min_clusters - 1:
        cut = int(np.argmax(ratios[min_clusters - 1:kmax])) + min_clusters
        if ratios[cut - 1] >= gamma_gap:
            k = cut
        else:
            k = min(max(min_clusters, 2), kmax)
    centers = gorder[:k]
    if top not in centers:
        # the global density peak must be a center or the nneigh chain of
        # the peak would self-loop unlabeled
        centers = np.concatenate([[top], centers[:-1]])

    # --- assignment: centers claim themselves; others follow nneigh chains
    labels = np.full(n, -1, np.int32)
    labels[centers] = np.arange(k, dtype=np.int32)
    for idx in order:  # descending density => parent already labeled
        if labels[idx] < 0:
            labels[idx] = labels[nneigh[idx]]
    return DPCResult(labels=labels, centers=centers.astype(np.int64),
                     rho=rho, delta=delta)


@functools.partial(jax.jit, static_argnames=("bs",))
def _rho_block(xp, i0, bs: int, n, dc2):
    """Gaussian-kernel density of rows [i0, i0 + bs) against the n real
    rows, self excluded (summed without the self term, so sparse points
    keep their small densities distinct in fp32)."""
    xb = jax.lax.dynamic_slice_in_dim(xp, i0, bs)
    d2 = ops.pairwise_sq_l2(xb, xp)                       # (bs, m)
    col = jnp.arange(xp.shape[0])[None, :]
    row = i0 + jnp.arange(bs)[:, None]
    w = jnp.where((col < n) & (col != row), jnp.exp(-d2 / dc2), 0.0)
    return jnp.sum(w, axis=1)


@functools.partial(jax.jit, static_argnames=("bs",))
def _delta_block(xp, rank, i0, bs: int, n):
    """Squared distance from rows [i0, i0 + bs) to their nearest point of
    lower rank (ahead in the density order), and its index — the first
    such index among equal distances."""
    xb = jax.lax.dynamic_slice_in_dim(xp, i0, bs)
    d2 = ops.pairwise_sq_l2(xb, xp)
    rb = jax.lax.dynamic_slice_in_dim(rank, i0, bs)[:, None]
    ahead = (rank[None, :] < rb) & (jnp.arange(xp.shape[0])[None, :] < n)
    dm = jnp.where(ahead, jnp.maximum(d2, 0.0), jnp.inf)
    return jnp.min(dm, axis=1), jnp.argmin(dm, axis=1)
