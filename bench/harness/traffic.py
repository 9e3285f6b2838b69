"""The one traffic generator: it reads a mix's data file and makes the
requests of a run from the seed.

A mix file (``bench/traffic/<mix>.json``) holds:

- ``arrival``: ``{"process": "poisson", "rate_per_s": r}``, open loop at a
  fixed offered rate;
- ``archetypes``: a list of ``{"name", "share", "k", "filter"}``, where a
  filter is ``null`` or ``{"column", "pass_fraction"}`` — a numeric range
  predicate ``lo <= column <= max`` whose ``lo`` lets exactly that share of
  the table's rows pass;
- ``server``: ``{"batch_size", "max_delay_ms"}``, the serving settings;
- ``warmup_s``: the unmeasured stretch of this traffic in set-up.

Steadiness: every seed gets the same number of requests, the same set of
inter-arrival gaps and the same count of each archetype; the seed only
orders them and picks the held-out query vectors. The gaps are the
exponential distribution's quantiles at ``(i + 0.5) / n`` (a stratified
Poisson process), so a run's offered load does not drift with the seed.
Query vectors are drawn from the pool without replacement: the window's
requests and the warm-up's never share one.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np


@dataclass(frozen=True)
class Spec:
    t: float                                   # scheduled offset, seconds
    pool_idx: int                              # held-out query vector
    k: int
    archetype: str
    filter: Optional[Tuple[str, float, float]]  # (column, lo, hi) or None


def _gaps(n: int, rate: float) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def _counts(shares: Sequence[float], n: int) -> List[int]:
    """Largest-remainder split of ``n`` requests by share."""
    s = np.asarray(shares, np.float64)
    raw = s / s.sum() * n
    out = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - out), kind="stable")[:n - out.sum()]:
        out[i] += 1
    return out.tolist()


def filter_bounds(column: np.ndarray, pass_fraction: float
                  ) -> Tuple[float, float]:
    """``(lo, hi)`` such that ``lo <= column <= hi`` holds for
    ``round(pass_fraction * rows)`` rows (exactly, for distinct values)."""
    m = max(1, int(round(pass_fraction * len(column))))
    srt = np.sort(column)
    return float(srt[len(srt) - m]), float(srt[-1])


def count(mix: dict, seconds: float) -> int:
    """Requests in ``seconds`` of the mix, the same for every seed."""
    return max(1, int(round(float(mix["arrival"]["rate_per_s"]) * seconds)))


def schedule(mix: dict, numeric: dict, pool_size: int, seed: int,
             seconds: float, *, skip: int = 0) -> List[Spec]:
    """The requests of ``seconds`` of the mix, for ``seed``. ``skip``
    query vectors of the seed's pool order are left to an earlier
    stretch (the window takes the first ones, the warm-up the next)."""
    arr = mix["arrival"]
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    rate = float(arr["rate_per_s"])
    n = count(mix, seconds)
    rng = np.random.default_rng([seed, skip])
    gaps = rng.permutation(_gaps(n, rate))
    t = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    order = np.random.default_rng(seed).permutation(pool_size)
    if skip + n > pool_size:
        raise ValueError(f"query pool of {pool_size} is too small for "
                         f"{skip + n} requests")
    picks = order[skip:skip + n]
    arch = mix["archetypes"]
    kinds = np.repeat(np.arange(len(arch)),
                      _counts([a["share"] for a in arch], n))
    kinds = rng.permutation(kinds)
    bounds = {}
    for a in arch:
        f = a.get("filter")
        if f is not None:
            bounds[a["name"]] = (f["column"],) + filter_bounds(
                numeric[f["column"]], float(f["pass_fraction"]))
    return [Spec(t=float(t[i]), pool_idx=int(picks[i]),
                 k=int(arch[kinds[i]]["k"]), archetype=arch[kinds[i]]["name"],
                 filter=bounds.get(arch[kinds[i]]["name"]))
            for i in range(n)]


def pow2_sizes(batch_size: int) -> List[int]:
    """The micro-batch sizes the server can run: powers of two up to and
    including ``batch_size``."""
    out, s = [], 1
    while s < batch_size:
        out.append(s)
        s *= 2
    return out + [batch_size]
