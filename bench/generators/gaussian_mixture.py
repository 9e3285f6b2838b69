"""The SIFT1M-shaped generator of ``chip_smoke.py``: a mixture of
``clusters`` Gaussians of unit variance around standard-normal centres,
kept as the benchmark's own copy, with an optional unit-norm projection
for cosine deployments and named columns drawn, in name order, after the
vectors and the pool (``column`` draws one through its column kind)."""
from harness.data import Dataset


def make(cfg, column):
    import numpy as np
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
    numeric = {name: column(spec, rows, rng)
               for name, spec in sorted(cfg.get("numeric", {}).items())}
    return Dataset(vectors=vec, numeric=numeric, pool=pool)
