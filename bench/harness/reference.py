"""The plain reference and the comparison that decides ``correct``.

The reference answers each request as a filtered exact k-NN under L2 in
straightforward ``jax.numpy``: a ``(block, rows)`` matrix of squared
distances from the benchmark's own copy of the data, rows that fail the
request's range filter set to +inf, ``lax.top_k``. It imports nothing of
the program under test. It runs on the device in blocks of queries after
the program's state has been freed, and returns ``k + extra``
candidates per request; the comparison then recomputes every candidate's
and every served row's squared distance in float64 on the host, so the
fp32 rounding of the device pass decides nothing.

``precision="bf16"`` is the control: the same reference in the next
precision below the configuration's fp32 (bfloat16 data and queries,
float32 accumulation), put in the program's place. Its answers must fail
the comparison.

What a served answer is held to (``compare``):

- ``unanswered``: requests with no result (limit 0);
- ``wrong_rows``: rows out of range, repeated, failing the filter, and
  rows missing from an answer shorter than ``min(k, rows passing)``
  (limit 0);
- ``kth_excess``: the largest relative amount by which a served row's
  squared distance exceeds the true k-th smallest, over all requests;
- ``order_excess``: the largest relative inversion between consecutive
  served rows, whose order must be ascending in distance.

The last two are 0 for an exact answer up to the rounding of the
program's fp32 distances; their limits come from runs of the program
and of the control (``bench/limits.json``, see ``PERF.md``).
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import numpy as np

from harness.traffic import Spec

_CHUNK = 1 << 15          # rows per partial top-k inside one block


def _dist_block(x, xx, qs, col, lo, hi, *, kk: int, bf16: bool):
    import jax
    import jax.numpy as jnp
    if bf16:
        # the table and the queries held in bfloat16, summed in float32
        xb, qb = x.astype(jnp.bfloat16), qs.astype(jnp.bfloat16)
        xf, qf = xb.astype(jnp.float32), qb.astype(jnp.float32)
        dot = jnp.dot(qb, xb.T, preferred_element_type=jnp.float32)
        d2 = (jnp.sum(qf * qf, axis=1)[:, None]
              + jnp.sum(xf * xf, axis=1)[None, :] - 2.0 * dot)
    else:
        dot = jnp.dot(qs, x.T, precision=jax.lax.Precision.HIGHEST)
        d2 = jnp.sum(qs * qs, axis=1)[:, None] + xx[None, :] - 2.0 * dot
    keep = (col[None, :] >= lo[:, None]) & (col[None, :] <= hi[:, None])
    d2 = jnp.where(keep, jnp.maximum(d2, 0.0), jnp.inf)
    b, n = d2.shape
    # partial top-k per chunk of rows, then over the chunks' winners
    parts = d2.reshape(b, n // _CHUNK, _CHUNK)
    pv, pi = jax.lax.top_k(-parts, kk)
    pi = pi + (jnp.arange(n // _CHUNK) * _CHUNK)[None, :, None]
    v, j = jax.lax.top_k(pv.reshape(b, -1), kk)
    return -v, jnp.take_along_axis(pi.reshape(b, -1), j, axis=1)


@functools.lru_cache(maxsize=None)
def _jitted(kk: int, bf16: bool):
    import jax
    return jax.jit(functools.partial(_dist_block, kk=kk, bf16=bf16))


class Reference:
    """The reference over one dataset, resident on the device while it
    lives. Build it only after the program's state is freed."""

    def __init__(self, vectors: np.ndarray, numeric: Dict[str, np.ndarray],
                 *, block: int = 128):
        import jax.numpy as jnp
        n = len(vectors)
        self.n = n
        self.block = block
        pad = -n % _CHUNK
        x = np.concatenate([vectors, np.zeros((pad, vectors.shape[1]),
                                              np.float32)])
        self.x = jnp.asarray(x)
        self.xx = jnp.sum(self.x * self.x, axis=1)
        # padded rows sit outside every filter: their column is NaN
        self.cols = {name: jnp.asarray(np.concatenate(
            [c, np.full(pad, np.nan, np.float32)]))
            for name, c in numeric.items()}
        self.anycol = jnp.asarray(np.concatenate(
            [np.zeros(n, np.float32), np.full(pad, np.nan, np.float32)]))

    def topk(self, pool: np.ndarray, specs: Sequence[Spec], *, extra: int,
             precision: str = "fp32") -> List[np.ndarray]:
        """Per request, the ``k + extra`` nearest passing rows (fewer
        where fewer pass), nearest first by the device's distances."""
        import jax.numpy as jnp
        if precision not in ("fp32", "bf16"):
            raise ValueError(f"unknown reference precision {precision!r}")
        kk = max(s.k for s in specs) + extra
        out: List[Optional[np.ndarray]] = [None] * len(specs)
        by_col: Dict[Optional[str], List[int]] = {}
        for i, s in enumerate(specs):
            by_col.setdefault(s.filter[0] if s.filter else None,
                              []).append(i)
        fn = _jitted(kk, precision == "bf16")
        for name, idx in by_col.items():
            col = self.anycol if name is None else self.cols[name]
            for b0 in range(0, len(idx), self.block):
                part = idx[b0:b0 + self.block]
                m = len(part)
                rows = np.zeros(self.block, np.int64)
                rows[:m] = [specs[i].pool_idx for i in part]
                lo = np.full(self.block, -np.inf, np.float32)
                hi = np.full(self.block, np.inf, np.float32)
                for j, i in enumerate(part):
                    if specs[i].filter:
                        lo[j], hi[j] = specs[i].filter[1:]
                d2, ids = fn(self.x, self.xx, jnp.asarray(pool[rows]),
                             col, jnp.asarray(lo), jnp.asarray(hi))
                d2, ids = np.asarray(d2), np.asarray(ids)
                for j, i in enumerate(part):
                    live = np.isfinite(d2[j])
                    out[i] = ids[j][live][:specs[i].k + extra]
        return out  # type: ignore[return-value]


def _passes(spec: Spec, numeric: Dict[str, np.ndarray]) -> np.ndarray:
    if spec.filter is None:
        return None
    name, lo, hi = spec.filter
    c = numeric[name]
    return (c >= lo) & (c <= hi)


def _d2(vectors: np.ndarray, rows: np.ndarray, q: np.ndarray) -> np.ndarray:
    diff = vectors[rows].astype(np.float64) - q.astype(np.float64)[None, :]
    return np.einsum("ij,ij->i", diff, diff)


def compare(specs: Sequence[Spec], served: Sequence[Optional[np.ndarray]],
            candidates: Sequence[np.ndarray], vectors: np.ndarray,
            numeric: Dict[str, np.ndarray], pool: np.ndarray
            ) -> Dict[str, float]:
    """The numbers that decide ``correct`` (see the module docstring).
    ``served[i]`` is request i's answer in original row ids, ``None``
    where it never came; ``candidates[i]`` is the reference's."""
    n = len(vectors)
    unanswered = wrong = 0
    kth_excess = order_excess = 0.0
    n_pass_cache: Dict = {}
    for s, got, cand in zip(specs, served, candidates):
        if got is None:
            unanswered += 1
            continue
        got = np.asarray(got, np.int64)
        q = pool[s.pool_idx]
        if s.filter not in n_pass_cache:
            m = _passes(s, numeric)
            n_pass_cache[s.filter] = n if m is None else int(m.sum())
        want_len = min(s.k, n_pass_cache[s.filter])
        ok = (got >= 0) & (got < n)
        bad = int((~ok).sum())
        got = got[ok]
        uniq, first = np.unique(got, return_index=True)
        bad += len(got) - len(uniq)
        got = got[np.sort(first)]
        if s.filter is not None:
            name, lo, hi = s.filter
            c = numeric[name][got]
            fits = (c >= lo) & (c <= hi)
            bad += int((~fits).sum())
            got = got[fits]
        bad += abs(want_len - len(got))
        wrong += bad
        if len(got) == 0 or want_len == 0:
            continue
        ref = np.sort(_d2(vectors, cand, q))
        kth = ref[want_len - 1]
        d = _d2(vectors, got, q)
        scale = max(kth, np.finfo(np.float64).tiny)
        kth_excess = max(kth_excess, float((d.max() - kth) / scale))
        if len(d) > 1:
            inv = (d[:-1] - d[1:]) / np.maximum(d[1:], scale)
            order_excess = max(order_excess, float(inv.max()))
    return {"unanswered": unanswered, "wrong_rows": wrong,
            "kth_excess": max(0.0, kth_excess),
            "order_excess": max(0.0, order_excess)}
