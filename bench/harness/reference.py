"""The plain reference and the comparison that decides ``correct``.

The reference answers each request as a filtered exact k-NN under L2 in
straightforward ``jax.numpy``: a ``(block, rows)`` matrix of squared
distances from the benchmark's own copy of the data, rows that fail the
request's filter set to +inf, ``lax.top_k``. The filter is the request's
tree of predicate kinds, whose ``mask`` each kind's file gives
(``harness/predicates.py``). It imports nothing of the program under
test. It runs on the device in blocks of queries of one archetype after
the program's state has been freed, and returns ``k + extra`` candidates
per request; the comparison then recomputes every candidate's and every
served row's squared distance, and the filter at those rows, in float64
on the host, so the fp32 rounding of the device pass decides nothing.

A filter whose boundary rounding decides (V.R's radius) is held to a
band (``vr_band`` in ``bench/limits.json``): a row whose float64
distance lies within that share of the radius of it may be served or
left out. Such a row is "in the band"; a row that passes the filter
outside the band "surely passes". Every other row is held exactly. The
device pass takes its candidates from the rows that pass the band's
outer edge widened by ``MARGIN``, over the device's own rounding, and the
host sorts them into sure, in the band and out.

``precision="bf16"`` is the control: the same reference in the next
precision below the configuration's fp32 (bfloat16 data and queries,
float32 accumulation, for the filter's distances too), put in the
program's place. Its answers must fail the comparison.

What a served answer is held to (``compare``):

- ``unanswered``: requests with no result (limit 0);
- ``wrong_rows``: rows out of range, repeated, failing the filter (out
  of the band), and rows missing from an answer shorter than
  ``min(k, rows passing)``, where the rows passing are those that surely
  pass and the served ones in the band (limit 0);
- ``kth_excess``: the largest relative amount by which a served row's
  squared distance exceeds the true k-th smallest among those same
  rows, over all requests;
- ``order_excess``: the largest relative inversion between consecutive
  served rows, whose order must be ascending in distance.

The last two are 0 for an exact answer up to the rounding of the
program's fp32 distances; their limits come from runs of the program
and of the control (``bench/limits.json``, see ``PERF.md``).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from harness import predicates
from harness.traffic import Spec

_CHUNK = 1 << 15          # rows per partial top-k inside one block
MARGIN = 1e-4             # device masks' widening of the band: over the
#                           relative error of a HIGHEST fp32 distance
VECTOR = "v"              # the searched column's name in the program


def _sq(x, xx, qs, bf16: bool):
    import jax
    import jax.numpy as jnp
    if bf16:
        # the table and the queries held in bfloat16, summed in float32
        xb, qb = x.astype(jnp.bfloat16), qs.astype(jnp.bfloat16)
        xf, qf = xb.astype(jnp.float32), qb.astype(jnp.float32)
        dot = jnp.dot(qb, xb.T, preferred_element_type=jnp.float32)
        return (jnp.sum(qf * qf, axis=1)[:, None]
                + jnp.sum(xf * xf, axis=1)[None, :] - 2.0 * dot)
    dot = jnp.dot(qs, x.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.sum(qs * qs, axis=1)[:, None] + xx[None, :] - 2.0 * dot


class _DeviceView:
    """The whole table on the device, for a kind's ``mask``."""

    def __init__(self, x, xx, cols, pool, bf16):
        self.x, self.xx, self.cols, self.pool = x, xx, cols, pool
        self.bf16 = bf16

    def column(self, name):
        return self.cols[name]

    def sq_dist(self, column, around):
        if column != VECTOR:
            raise KeyError(f"no vector column {column!r}")
        return _sq(self.x, self.xx, self.pool[around], self.bf16)


class _HostView:
    """A few rows of the table in float64, for a kind's ``mask``; a
    column's rows keep its trailing shape."""

    def __init__(self, ds, rows: np.ndarray):
        self.ds, self.rows = ds, rows

    def column(self, name):
        return self.ds.numeric[name][self.rows].astype(np.float64)

    def sq_dist(self, column, around):
        if column != VECTOR:
            raise KeyError(f"no vector column {column!r}")
        x = self.ds.vectors[self.rows].astype(np.float64)
        q = self.ds.pool[np.asarray(around)].astype(np.float64)
        diff = x[None, :, :] - q[:, None, :]
        return np.einsum("bij,bij->bi", diff, diff)


def _block(x, xx, qs, valid, cols, pool, arrays, *, kk: int, bf16: bool,
           static, bands, kinds):
    import jax
    import jax.numpy as jnp
    d2 = _sq(x, xx, qs, bf16)
    if static is None:
        keeps = [valid[None, :]] * len(bands)
    else:
        node = predicates.join(static, arrays)
        view = _DeviceView(x, xx, cols, pool, bf16)
        keeps = [predicates.mask(node, view, b, kinds) & valid[None, :]
                 for b in bands]
    d2 = jnp.where(keeps[0], jnp.maximum(d2, 0.0), jnp.inf)
    b, n = d2.shape
    # partial top-k per chunk of rows, then over the chunks' winners
    parts = d2.reshape(b, n // _CHUNK, _CHUNK)
    pv, pi = jax.lax.top_k(-parts, kk)
    pi = pi + (jnp.arange(n // _CHUNK) * _CHUNK)[None, :, None]
    v, j = jax.lax.top_k(pv.reshape(b, -1), kk)
    counts = [jnp.sum(jnp.broadcast_to(k, (b, n)), axis=1, dtype=jnp.int32)
              for k in keeps]
    return -v, jnp.take_along_axis(pi.reshape(b, -1), j, axis=1), counts


@functools.lru_cache(maxsize=None)
def _jitted(kk: int, bf16: bool, static, bands, kinds):
    import jax
    return jax.jit(functools.partial(_block, kk=kk, bf16=bf16, static=static,
                                     bands=bands, kinds=kinds))


def _device_arrays(arrays: Sequence[np.ndarray]) -> list:
    """Bound parameters for the device: floats as float32, ints as
    int32."""
    import jax.numpy as jnp
    out = []
    for a in arrays:
        a = np.asarray(a)
        out.append(jnp.asarray(a.astype(np.float32) if a.dtype.kind == "f"
                               else a.astype(np.int32)))
    return out


@dataclass
class Candidates:
    rows: np.ndarray     # nearest first by the device's distances
    n_loose: int         # rows passing the band's widened outer edge
    n_strict: int        # rows passing its narrowed inner edge
    complete: bool       # rows holds every one of the n_loose


class Reference:
    """The reference over one dataset, resident on the device while it
    lives. Build it only after the program's state is freed."""

    def __init__(self, ds, *, block: int = 128):
        import jax.numpy as jnp
        vectors = ds.vectors
        n = len(vectors)
        self.ds = ds
        self.n = n
        self.block = block
        pad = -n % _CHUNK
        x = np.concatenate([vectors, np.zeros((pad, vectors.shape[1]),
                                              np.float32)])
        self.x = jnp.asarray(x)
        self.xx = jnp.sum(self.x * self.x, axis=1)
        self.valid = jnp.asarray(np.arange(n + pad) < n)
        # padded rows are outside every filter by ``valid``, whatever a
        # kind would make of their zeros
        self.cols = {name: jnp.asarray(np.concatenate(
            [c, np.zeros((pad,) + c.shape[1:], c.dtype)]))
            for name, c in ds.numeric.items()}
        self.pool = jnp.asarray(ds.pool)

    def _run(self, specs: Sequence[Spec], kk: int, precision: str,
             bands: Tuple[float, ...]):
        """Per request: device distances and ids of its ``kk`` nearest
        rows passing ``bands[0]``, and the count passing each band."""
        import jax.numpy as jnp
        if precision not in ("fp32", "bf16"):
            raise ValueError(f"unknown reference precision {precision!r}")
        out: List[Optional[tuple]] = [None] * len(specs)
        groups: Dict[Optional[tuple], List[int]] = {}
        for i, s in enumerate(specs):
            key = None if s.filter is None else \
                predicates.split(predicates.stack([s.filter]))[0]
            groups.setdefault(key, []).append(i)
        for static, idx in groups.items():
            fn = _jitted(kk, precision == "bf16", static, bands,
                         self.ds.kinds)
            for b0 in range(0, len(idx), self.block):
                part = idx[b0:b0 + self.block]
                m = len(part)
                slots = part + [part[0]] * (self.block - m)
                rows = np.zeros(self.block, np.int64)
                rows[:m] = [specs[i].pool_idx for i in part]
                arrays = [] if static is None else _device_arrays(
                    predicates.split(predicates.stack(
                        [specs[i].filter for i in slots]))[1])
                d2, ids, counts = fn(self.x, self.xx,
                                     jnp.asarray(self.ds.pool[rows]),
                                     self.valid, self.cols, self.pool,
                                     arrays)
                d2, ids = np.asarray(d2), np.asarray(ids)
                counts = [np.asarray(c) for c in counts]
                for j, i in enumerate(part):
                    out[i] = (d2[j], ids[j], [int(c[j]) for c in counts])
        return out

    def topk(self, specs: Sequence[Spec], *, extra: int,
             precision: str = "fp32") -> List[np.ndarray]:
        """Per request, the ``k + extra`` nearest passing rows (fewer
        where fewer pass), nearest first by the device's distances, with
        the filter's boundary where this precision puts it."""
        kk = max(s.k for s in specs) + extra
        return [ids[np.isfinite(d2)][:s.k + extra] for s, (d2, ids, _)
                in zip(specs, self._run(specs, kk, precision, (0.0,)))]

    def candidates(self, specs: Sequence[Spec], *, extra: int,
                   band: float) -> List[Candidates]:
        """Per request, the ``k + extra`` nearest rows that may pass
        under ``band``, for ``compare``."""
        kk = max(s.k for s in specs) + extra
        wide = band + MARGIN
        out = []
        for s, (d2, ids, (n_loose, n_strict)) in zip(
                specs, self._run(specs, kk, "fp32", (wide, -wide))):
            rows = ids[np.isfinite(d2)][:s.k + extra]
            out.append(Candidates(rows=rows, n_loose=n_loose,
                                  n_strict=n_strict,
                                  complete=n_loose <= s.k + extra))
        return out


def _host_masks(node, ds, rows: np.ndarray, band: float):
    """Float64 masks of one request's filter at ``rows``: passing the
    band's outer edge, and its inner edge."""
    batched = predicates.stack([node])
    view = _HostView(ds, rows)
    return (predicates.mask(batched, view, band, ds.kinds)[0],
            predicates.mask(batched, view, -band, ds.kinds)[0])


def _d2(vectors: np.ndarray, rows: np.ndarray, q: np.ndarray) -> np.ndarray:
    diff = vectors[rows].astype(np.float64) - q.astype(np.float64)[None, :]
    return np.einsum("ij,ij->i", diff, diff)


def compare(specs: Sequence[Spec], served: Sequence[Optional[np.ndarray]],
            candidates: Sequence[Candidates], ds, band: float
            ) -> Dict[str, float]:
    """The numbers that decide ``correct`` (see the module docstring).
    ``served[i]`` is request i's answer in original row ids, ``None``
    where it never came; ``candidates[i]`` is the reference's."""
    vectors = ds.vectors
    n = len(vectors)
    unanswered = wrong = 0
    kth_excess = order_excess = 0.0
    for s, got, c in zip(specs, served, candidates):
        if got is None:
            unanswered += 1
            continue
        got = np.asarray(got, np.int64)
        q = ds.pool[s.pool_idx]
        ok = (got >= 0) & (got < n)
        bad = int((~ok).sum())
        got = got[ok]
        uniq, first = np.unique(got, return_index=True)
        bad += len(got) - len(uniq)
        got = got[np.sort(first)]
        cand = np.asarray(c.rows, np.int64)
        in_band = got[:0]
        n_sure = c.n_strict
        if s.filter is not None:
            loose, strict = _host_masks(s.filter, ds,
                                        np.concatenate([got, cand]), band)
            g = len(got)
            fits = loose[:g]
            bad += int((~fits).sum())
            in_band = got[fits & ~strict[:g]]
            got = got[fits]
            sure = strict[g:]
            cand = cand[sure]
            n_sure = int(sure.sum()) if c.complete \
                else max(int(sure.sum()), c.n_strict)
        want_len = min(s.k, n_sure + len(in_band))
        bad += abs(want_len - len(got))
        wrong += bad
        if len(got) == 0 or want_len == 0:
            continue
        rows = cand if not len(in_band) else np.union1d(cand, in_band)
        ref = np.sort(_d2(vectors, rows, q))
        kth = ref[min(want_len, len(ref)) - 1]
        d = _d2(vectors, got, q)
        scale = max(kth, np.finfo(np.float64).tiny)
        kth_excess = max(kth_excess, float((d.max() - kth) / scale))
        if len(d) > 1:
            inv = (d[:-1] - d[1:]) / np.maximum(d[1:], scale)
            order_excess = max(order_excess, float(inv.max()))
    return {"unanswered": unanswered, "wrong_rows": wrong,
            "kth_excess": max(0.0, kth_excess),
            "order_excess": max(0.0, order_excess)}
