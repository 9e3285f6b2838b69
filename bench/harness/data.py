"""A configuration's table and held-out query pool.

The table and the pool stand for the source's public data set, which is
one fixed set: they are made from the configuration's own ``data_seed``,
the same in every run, while a run's ``--seed`` draws which pool queries
it sends and in what order (``traffic.py``). A table per run seed would
also change the index's tile and leaf counts, hence the shapes of the
engine's compiled programs, and every run would compile them anew.

The generator is named by the configuration file (``"generator"``) and
is the file ``bench/generators/<generator>.py``; it reads its sizes from
the configuration and draws each column through the column kind the
configuration names (``bench/columns/<kind>.py``).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict

import numpy as np

from harness.kinds import DEFAULT, Kinds


@dataclass
class Dataset:
    vectors: np.ndarray              # (rows, dim) float32, the searched column
    numeric: Dict[str, np.ndarray]   # name -> (rows,) column values
    pool: np.ndarray                 # (query_pool, dim) float32, held out
    kinds: Kinds = DEFAULT
    cache: dict = field(default_factory=dict)   # bound constants (traffic)


def make(cfg: dict, kinds: Kinds) -> Dataset:
    """The configuration's table and pool, made by the generator and
    column kinds of ``kinds``."""
    name = cfg.get("generator")
    if not name or not kinds.has("generators", name):
        raise ValueError(f"unknown generator {name!r}")

    def column(spec: dict, rows: int, rng: np.random.Generator):
        return kinds.module("columns", spec["kind"]).make(spec, rows, rng)

    ds = kinds.module("generators", name).make(cfg, column)
    ds.kinds = kinds
    return ds
