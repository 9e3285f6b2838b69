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
configuration names (``bench/columns/<kind>.py``). The configuration's
``numeric`` names every column of the table, of whatever kind and shape.

A column is ``(rows, ...)``: one value a row, or a fixed number of them,
such as a set of tags held in ``T`` slots with a fill where a row has
fewer. The held-out pool may carry columns of its own
(``pool_columns``), such as each query's own tags: the generator makes
them, a predicate kind's ``bind`` reads them at the request's pool index
(``harness/predicates.py``), and they go neither into the program's
table nor into the reference's device columns.

``make`` holds the vectors and the pool as float32, which is exact for
the uint8 codes some sources ship, so a generator may return a source's
codes as they are and the program, the reference and its float64
recheck all read the same values.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict

import numpy as np

from harness.kinds import DEFAULT, Kinds


@dataclass
class Dataset:
    vectors: np.ndarray              # (rows, dim) float32, the searched column
    numeric: Dict[str, np.ndarray]   # name -> (rows, ...) column values
    pool: np.ndarray                 # (query_pool, dim) float32, held out
    # name -> (query_pool, ...): the held-out queries' own columns
    pool_columns: Dict[str, np.ndarray] = field(default_factory=dict)
    kinds: Kinds = DEFAULT
    cache: dict = field(default_factory=dict)   # bound constants (traffic)


def make(cfg: dict, kinds: Kinds) -> Dataset:
    """The configuration's table and pool, made by the generator and
    column kinds of ``kinds``; vectors and pool as float32."""
    name = cfg.get("generator")
    if not name or not kinds.has("generators", name):
        raise ValueError(f"unknown generator {name!r}")

    def column(spec: dict, rows: int, rng: np.random.Generator):
        return kinds.module("columns", spec["kind"]).make(spec, rows, rng)

    ds = kinds.module("generators", name).make(cfg, column)
    ds.vectors = np.asarray(ds.vectors, np.float32)
    ds.pool = np.asarray(ds.pool, np.float32)
    ds.kinds = kinds
    return ds
