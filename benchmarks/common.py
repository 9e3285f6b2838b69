"""Shared benchmark utilities: datasets (paper §7.1 scaled to CPU),
timing, and metrics.

Scale adaptation: the paper runs 0.3M-210M records on a Spark cluster; the
CPU container uses 4-50k records with identical protocols (selectivities,
K values, query generation) — the comparisons are relative, matching the
paper's claims rather than its absolute wall-times.
"""
from __future__ import annotations

import os
import subprocess
import time
from typing import Callable, Dict, List, Tuple

import numpy as np


# ------------------------------------------------------------------ smoke
# --smoke (benchmarks/run.py, scripts/check.sh) runs every bench module at
# toy scale with repeat=1 so bench code is executed in CI instead of
# bit-rotting; numbers from a smoke run are NOT comparable to full runs.
SMOKE = False


def smoke_n(n: int, tiny: int) -> int:
    """Full-run size ``n``, or ``tiny`` under --smoke."""
    return tiny if SMOKE else n


# ------------------------------------------------------------------ build id
def git_stamp() -> Tuple[str, bool]:
    """(short HEAD, dirty) of the repo AT BENCH TIME — the commit whose
    code actually ran, resolved fresh on every call rather than copied
    from an older artifact (BENCH_*.json rows used to inherit a stale
    seed-commit tag). ``dirty`` flags uncommitted changes so a row from
    a modified tree is never mistaken for the tagged commit's numbers."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        head = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, timeout=10, cwd=root).stdout.strip() or "unknown"
        status = subprocess.run(
            ["git", "status", "--porcelain"], capture_output=True,
            text=True, timeout=10, cwd=root).stdout.strip()
        return head, bool(status)
    except Exception:
        return "unknown", True


# ------------------------------------------------------------------ datasets
def gaussmix(n: int = 8000, d: int = 8, k: int = 8, seed: int = 0,
             spread: float = 6.0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(k, d)).astype(np.float32) * spread
    lab = rng.integers(0, k, n)
    x = (centers[lab] + rng.normal(size=(n, d))).astype(np.float32)
    return x, lab


def uniform(n: int = 8000, d: int = 8, seed: int = 0):
    rng = np.random.default_rng(seed)
    return rng.uniform(-10, 10, (n, d)).astype(np.float32), None


def skewed(n: int = 8000, d: int = 8, seed: int = 0):
    rng = np.random.default_rng(seed)
    x = rng.lognormal(0.0, 1.0, (n, d)).astype(np.float32)
    return x * np.sign(rng.normal(size=(n, d))), None


DATASETS = {"GaussMix": gaussmix, "Uniform": uniform, "Skewed": skewed}


# ------------------------------------------------------------------ timing
def fence(x):
    """Explicit device fence for timed regions: block until every jax
    value in ``x`` (tree or scalar) has actually been computed, so a
    timed call that ends in async-dispatched device work is charged its
    full cost inside the timed region — not lazily on the next
    materialize. Host-resident numpy results pass through untouched
    (the call is then a no-op, kept for timing discipline). A device
    error raises here rather than vanishing from the timed region."""
    import jax
    jax.block_until_ready(x)
    return x


def timeit(fn: Callable, *args, repeat: int = 3,
           fence_result: bool = False, **kw) -> Tuple[float, object]:
    """Best-of-``repeat`` wall time. ``fence_result=True`` fences the
    return value INSIDE the timed region (see ``fence``) — required for
    any ``fn`` whose tail is async device dispatch."""
    out = None
    best = float("inf")
    for _ in range(1 if SMOKE else repeat):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        if fence_result:
            fence(out)
        best = min(best, time.perf_counter() - t0)
    return best, out


def us(seconds: float) -> float:
    return seconds * 1e6


# ------------------------------------------------------------------ metrics
def calinski_harabasz(x: np.ndarray, labels: np.ndarray) -> float:
    n = len(x)
    uniq = np.unique(labels)
    k = len(uniq)
    if k < 2:
        return 0.0
    mean = x.mean(0)
    b = sum((labels == u).sum() * np.sum((x[labels == u].mean(0) - mean) ** 2)
            for u in uniq)
    w = sum(np.sum((x[labels == u] - x[labels == u].mean(0)) ** 2)
            for u in uniq)
    return float((b / max(k - 1, 1)) / max(w / max(n - k, 1), 1e-12))


def nmi(a: np.ndarray, b: np.ndarray) -> float:
    """Normalized mutual information between two labelings."""
    a = np.asarray(a)
    b = np.asarray(b)
    ua, ia = np.unique(a, return_inverse=True)
    ub, ib = np.unique(b, return_inverse=True)
    n = len(a)
    cm = np.zeros((len(ua), len(ub)))
    np.add.at(cm, (ia, ib), 1)
    pij = cm / n
    pi = pij.sum(1, keepdims=True)
    pj = pij.sum(0, keepdims=True)
    nz = pij > 0
    mi = float(np.sum(pij[nz] * np.log(pij[nz] / (pi @ pj)[nz])))
    ha = -float(np.sum(pi[pi > 0] * np.log(pi[pi > 0])))
    hb = -float(np.sum(pj[pj > 0] * np.log(pj[pj > 0])))
    return mi / max(np.sqrt(ha * hb), 1e-12)


def recall(found: np.ndarray, truth: np.ndarray) -> float:
    t = set(np.asarray(truth).tolist())
    if not t:
        return 1.0
    return len(set(np.asarray(found).tolist()) & t) / len(t)


class Csv:
    """Collects `name,us_per_call,derived` rows."""

    def __init__(self):
        self.rows: List[Tuple[str, float, str]] = []

    def add(self, name: str, us_per_call: float, derived: str = ""):
        self.rows.append((name, us_per_call, derived))

    def emit(self):
        for name, t, d in self.rows:
            print(f"{name},{t:.1f},{d}")
