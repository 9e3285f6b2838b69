"""The one traffic generator: it reads a mix's data file and makes the
requests of a run from the seed.

A mix file (``bench/traffic/<mix>.json``) holds:

- ``arrival``: ``{"process": "poisson", "rate_per_s": r}``, open loop at a
  fixed offered rate;
- ``vectors`` (optional, default ``["q"]``): the held-out pool vectors
  each request draws, by name; the first is its V.K query, the others
  are there for its filter to name;
- ``archetypes``: a list of ``{"name", "share", "k", "filter"}``, where a
  filter is ``null`` or a tree of predicate kinds (``predicates.py``),
  such as ``{"or": [{"vr": {...}}, {"nr": {...}}]}``; the first mixes'
  ``{"column", "pass_fraction"}`` is an ``nr`` node, a numeric range
  ``lo <= column <= max`` whose ``lo`` lets exactly that share of the
  table's rows pass;
- ``server``: ``{"batch_size", "max_delay_ms"}``, the serving settings;
- ``warmup_s``: the unmeasured stretch of this traffic in set-up.

Steadiness: every seed gets the same number of requests, the same set of
inter-arrival gaps and the same count of each archetype; the seed only
orders them and picks the held-out vectors. The gaps are the
exponential distribution's quantiles at ``(i + 0.5) / n`` (a stratified
Poisson process), so a run's offered load does not drift with the seed.
Pool vectors are drawn without replacement: the window's requests and the
warm-up's never share one. A request's vector named ``vectors[j]`` is
the seed's pool order at ``skip + j * n + i`` for its index ``i`` of
``n``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence

import numpy as np

from harness import predicates


@dataclass(frozen=True)
class Spec:
    t: float                                   # scheduled offset, seconds
    pool_idx: int                              # held-out query vector
    k: int
    archetype: str
    filter: Optional[predicates.Node]          # bound filter tree or None


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


def count(mix: dict, seconds: float) -> int:
    """Requests in ``seconds`` of the mix, the same for every seed."""
    return max(1, int(round(float(mix["arrival"]["rate_per_s"]) * seconds)))


def pool_needed(mix: dict, seconds: float) -> int:
    """Pool vectors that ``seconds`` of the mix draw."""
    return count(mix, seconds) * len(predicates.vectors_of(mix))


def schedule(mix: dict, ds, seed: int, seconds: float, *,
             skip: int = 0) -> List[Spec]:
    """The requests of ``seconds`` of the mix over the dataset ``ds``,
    for ``seed``. ``skip`` pool vectors of the seed's pool order are
    left to an earlier stretch (the window takes the first ones, the
    warm-up the next)."""
    arr = mix["arrival"]
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    rate = float(arr["rate_per_s"])
    n = count(mix, seconds)
    names = predicates.vectors_of(mix)
    pool_size = len(ds.pool)
    rng = np.random.default_rng([seed, skip])
    gaps = rng.permutation(_gaps(n, rate))
    t = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    order = np.random.default_rng(seed).permutation(pool_size)
    if skip + n * len(names) > pool_size:
        raise ValueError(f"query pool of {pool_size} is too small for "
                         f"{skip + n * len(names)} vectors")
    picks = [order[skip + j * n:skip + (j + 1) * n]
             for j in range(len(names))]
    arch = mix["archetypes"]
    kinds = np.repeat(np.arange(len(arch)),
                      _counts([a["share"] for a in arch], n))
    kinds = rng.permutation(kinds)
    out = []
    for i in range(n):
        a = arch[kinds[i]]
        f = a.get("filter")
        node = None if f is None else predicates.bind(
            f, ds, {name: int(p[i]) for name, p in zip(names, picks)})
        out.append(Spec(t=float(t[i]), pool_idx=int(picks[0][i]),
                        k=int(a["k"]), archetype=a["name"], filter=node))
    return out


def pow2_sizes(batch_size: int) -> List[int]:
    """The micro-batch sizes the server can run: powers of two up to and
    including ``batch_size``."""
    out, s = [], 1
    while s < batch_size:
        out.append(s)
        s *= 2
    return out + [batch_size]
