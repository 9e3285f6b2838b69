"""A configuration's table and held-out query pool.

The table and the pool stand for the source's public data set, which is
one fixed set: they are made from the configuration's own ``data_seed``,
the same in every run, while a run's ``--seed`` draws which pool queries
it sends and in what order (``traffic.py``). A table per run seed would
also change the index's tile and leaf counts, hence the shapes of the
engine's compiled programs, and every run would compile them anew.

The generator is named by the configuration file (``"generator"``) and
reads its sizes from there. ``gaussian_mixture`` is the SIFT1M-shaped
generator of ``chip_smoke.py`` (a mixture of ``clusters`` Gaussians of
unit variance around standard-normal centres), kept here as the
benchmark's own copy, with an optional unit-norm projection for cosine
deployments and named numeric columns.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import numpy as np


@dataclass
class Dataset:
    vectors: np.ndarray              # (rows, dim) float32, the searched column
    numeric: Dict[str, np.ndarray]   # name -> (rows,) float32
    pool: np.ndarray                 # (query_pool, dim) float32, held out


def _numeric(spec: dict, rows: int, rng: np.random.Generator) -> np.ndarray:
    kind = spec["kind"]
    if kind == "uniform":
        return rng.uniform(spec["lo"], spec["hi"], rows).astype(np.float32)
    if kind == "normal":
        return rng.standard_normal(rows, dtype=np.float32)
    if kind == "permutation":
        # row ids in random order: exact in float32 below 2**24 rows
        if rows > 1 << 24:
            raise ValueError("a permutation column needs rows <= 2**24")
        return rng.permutation(rows).astype(np.float32)
    raise ValueError(f"unknown numeric column kind {kind!r}")


def gaussian_mixture(cfg: dict) -> Dataset:
    rows, dim, k = int(cfg["rows"]), int(cfg["dim"]), int(cfg["clusters"])
    rng = np.random.default_rng(int(cfg["data_seed"]))
    centers = rng.standard_normal((k, dim), dtype=np.float32)
    vec = rng.standard_normal((rows, dim), dtype=np.float32)
    vec += centers[rng.integers(0, k, rows)]
    pool = rng.standard_normal((int(cfg["query_pool"]), dim),
                               dtype=np.float32)
    pool += centers[rng.integers(0, k, len(pool))]
    if cfg.get("normalize"):
        vec /= np.linalg.norm(vec, axis=1, keepdims=True)
        pool /= np.linalg.norm(pool, axis=1, keepdims=True)
    numeric = {name: _numeric(spec, rows, rng)
               for name, spec in sorted(cfg.get("numeric", {}).items())}
    return Dataset(vectors=vec, numeric=numeric, pool=pool)


GENERATORS = {"gaussian_mixture": gaussian_mixture}


def make(cfg: dict) -> Dataset:
    """The configuration's table and pool."""
    try:
        gen = GENERATORS[cfg["generator"]]
    except KeyError:
        raise ValueError(f"unknown generator {cfg.get('generator')!r}")
    return gen(cfg)
