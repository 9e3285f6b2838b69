"""Bring-up run of the hybrid-query retrieval platform on a TPU.

Drives the main path once through the entry points a user calls, at the
ANN-Benchmarks SIFT1M shape, and checks every result against the
brute-force oracle:

1. an ``MMOTable`` made from ``--seed``: ``--rows`` (default 1,000,000)
   x 128-d fp32 vectors drawn from a Gaussian mixture, plus two numeric
   columns;
2. ``MQRLD(table).prepare()`` and the device upload of the engine, with
   the time of each build phase;
3. held-out query batches (fresh draws from the mixture, not rows of the
   table) through ``MQRLD.session().plan(batch).execute()``: V.K with
   k=10 and k=100, V.K And N.R, V.R, V.R And N.R, and an Or of numeric
   predicates — each batch is run cold (compile + execute) and warm;
4. a few dozen requests through ``RetrievalServer.submit()/result()``
   with a host stub embedder;
5. one batch with ``precision="int8"``, whose rows must equal fp32's.

Every result must match ``query.execute_bruteforce`` row for row; any
mismatch or failed phase exits non-zero. With ``--chips 4`` only the
sharded path runs: ``session(shards=4)`` against ``session(shards=0)``
and the oracle over the same table.

The script needs a TPU: it exits non-zero, printing no result, where JAX
finds none, and when it is run outside a checkout of this repository.
Every phase runs in this one process (a chip serves one process at a
time). The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Usage: python chip_smoke.py [--rows N] [--seed S] [--chips 1|4]
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

D = 128                # SIFT descriptor width
CLUSTERS = 256         # mixture components
BATCH = 64             # queries per Session batch


def log(msg: str) -> None:
    print(msg, flush=True)


def _rss_kb() -> int:
    """Resident host memory of this process, KiB (0 where the kernel
    does not report it)."""
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1])
    return 0


def host_gb() -> str:
    """Resident and peak host memory of this process."""
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss   # KiB
    return (f"host_rss_gb={_rss_kb() / 2**20:.2f} "
            f"host_peak_gb={peak / 2**20:.2f}")


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def make_data(rows: int, n_queries: int, seed: int):
    """SIFT1M-shaped table plus held-out queries from the same mixture."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(CLUSTERS, D)).astype(np.float32)
    vec = rng.standard_normal((rows, D), dtype=np.float32)
    vec += centers[rng.integers(0, CLUSTERS, rows)]
    a = rng.uniform(0.0, 100.0, rows).astype(np.float32)
    b = rng.standard_normal(rows, dtype=np.float32)
    qv = rng.standard_normal((n_queries, D), dtype=np.float32)
    qv += centers[rng.integers(0, CLUSTERS, n_queries)]
    return vec, a, b, qv, rng


def same_rows(q, got, want, vec) -> bool:
    """Row for row against the oracle: the same rows, and for a top-level
    V.K the engine's order must be ascending in exact (float64) distance.
    Rows whose fp32 distances tie may come back in either order — the
    oracle's own fp32 sort cannot tell them apart either."""
    from repro.core import query as Q
    got = np.asarray(got, np.int64)
    want = np.asarray(want, np.int64)
    if len(got) != len(want) or not np.array_equal(np.sort(got),
                                                   np.sort(want)):
        return False
    if isinstance(q, Q.VK) and not np.array_equal(got, want):
        d = np.sum((vec[got].astype(np.float64) - q.vec()) ** 2, axis=1)
        return bool(np.all(np.diff(d) >= -1e-6 * d[1:]))
    return True


class Oracle:
    """``query.execute_bruteforce`` over the platform's view, fanned out
    over host threads (numpy releases the GIL in the distance sums)."""

    def __init__(self, platform):
        from repro.core import query as Q
        self.view = platform.view()
        self.vec = self.view.vector["v"]
        self.bf = Q.execute_bruteforce
        self.pool = ThreadPoolExecutor(max_workers=min(4, os.cpu_count()))

    def check(self, queries, results) -> int:
        """Number of queries whose rows differ from the oracle's."""
        truth = self.pool.map(lambda q: self.bf(self.view, q), queries)
        return sum(not same_rows(q, r, t, self.vec)
                   for q, r, t in zip(queries, results, truth))


def run_batch(session, name, queries, oracle):
    """Cold (plan + compile + execute) and warm execution of one batch;
    returns the warm rows after checking them against the oracle."""
    t0 = time.perf_counter()
    session.plan(queries).execute()
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    rows, stats = session.plan(queries).execute()
    warm = time.perf_counter() - t0
    log(f"batch {name}: queries={len(queries)} first_s={cold:.3f} "
        f"execute_s={warm:.3f} rows_returned={sum(len(r) for r in rows)} "
        f"knn_rounds={stats.knn_rounds} {host_gb()}")
    bad = oracle.check(queries, rows)
    log(f"batch {name}: mismatches={bad} {host_gb()}")
    if bad:
        fail(f"batch {name}: {bad} queries differ from the oracle")
    return rows


def hybrid_batches(qv, radius, rng):
    """The paper's query archetypes over held-out vectors, BATCH each."""
    from repro.core import query as Q

    def take(i):
        return qv[i * BATCH:(i + 1) * BATCH]

    lo = rng.uniform(0.0, 90.0, BATCH)
    blo = rng.normal(size=BATCH)
    return {
        "vk10": [Q.VK.of("v", q, 10) for q in take(0)],
        "vk100": [Q.VK.of("v", q, 100) for q in take(1)],
        "vk10_and_nr": [Q.And.of(Q.NR("a", float(l), float(l) + 10.0),
                                 Q.VK.of("v", q, 10))
                        for q, l in zip(take(2), lo)],
        "vr": [Q.VR.of("v", q, radius) for q in take(3)],
        "vr_and_nr": [Q.And.of(Q.VR.of("v", q, radius),
                               Q.NR("a", float(l), float(l) + 50.0))
                      for q, l in zip(take(4), lo)],
        "or_numeric": [Q.Or.of(Q.NR("a", float(l), float(l) + 0.05),
                               Q.NR("b", float(m), float(m) + 0.002))
                       for l, m in zip(lo, blo)],
    }


def vr_radius(vec, qv) -> float:
    """A V.R radius that admits some tens of rows per held-out query: the
    median 50th-neighbour distance of a few of them."""
    r = []
    for q in qv[:8]:
        d2 = np.sum((vec - q) ** 2, axis=1)
        r.append(np.sqrt(np.partition(d2, 50)[50]))
    return float(np.median(r))


class StubEmbedder:
    """Host stub: token[0] picks a held-out query vector."""

    def __init__(self, qv):
        self.qv = qv

    def embed(self, tokens):
        return self.qv[np.asarray(tokens)[:, 0] % len(self.qv)]


def serve(platform, qv, oracle, rng, n_requests=48):
    from repro.core import query as Q
    from repro.serve.engine import RetrievalRequest, RetrievalServer
    srv = RetrievalServer(platform, StubEmbedder(qv), batch_size=16)
    reqs = []
    for i in range(n_requests):
        lo = float(rng.uniform(0.0, 80.0))
        pred = None if i % 2 == 0 else Q.NR("a", lo, lo + 20.0)
        reqs.append(RetrievalRequest(
            tokens=np.asarray([i, 0], np.int32), attr="v", k=10,
            predicate=pred))
    t0 = time.perf_counter()
    futs = [srv.submit(r) for r in reqs]
    res = [srv.result(f) for f in futs]
    dt = time.perf_counter() - t0
    bad = oracle.check([r.query for r in res], [r.rows for r in res])
    log(f"server: requests={len(res)} batch_size=16 wall_s={dt:.3f} "
        f"mismatches={bad}")
    if bad or any(r.shed for r in res):
        fail(f"server: {bad} requests differ from the oracle")


def build(rows, seed):
    from repro.core.lake import MMOTable
    from repro.core.platform import MQRLD
    t0 = time.perf_counter()
    vec, a, b, qv, rng = make_data(rows, 8 * BATCH, seed)
    table = (MMOTable("sift").add_vector("v", vec)
             .add_numeric("a", a).add_numeric("b", b))
    log(f"data: rows={rows} dim={D} clusters={CLUSTERS} "
        f"held_out_queries={len(qv)} gen_s={time.perf_counter() - t0:.3f}")
    platform = MQRLD(table, seed=seed)
    t0 = time.perf_counter()
    rep = platform.prepare()
    t_prep = time.perf_counter() - t0
    phases = " ".join(f"{k}_s={v:.3f}" for k, v in rep.phase_s.items())
    log(f"build: prepare_s={t_prep:.3f} {phases} leaves={rep.n_leaves} "
        f"depth={rep.max_depth} {host_gb()}")
    return platform, vec, qv, rng


def upload(platform, **kw):
    t0 = time.perf_counter()
    eng = platform.engine(**kw)
    import jax
    jax.block_until_ready(eng.vec_tiles_dev)
    log(f"build: device_upload_s={time.perf_counter() - t0:.3f} "
        f"tiles={eng.n_tiles} {host_gb()} "
        f"{' '.join(f'{k}={v}' for k, v in kw.items())}")


def one_chip(rows, seed):
    platform, vec, qv, rng = build(rows, seed)
    upload(platform)
    oracle = Oracle(platform)
    radius = vr_radius(platform.view().vector["v"], qv)
    log(f"queries: vr_radius={radius:.4f}")
    session = platform.session()
    fp32 = {}
    for name, batch in hybrid_batches(qv, radius, rng).items():
        fp32[name] = (batch, run_batch(session, name, batch, oracle))
    serve(platform, qv, oracle, rng)
    upload(platform, precision="int8")
    batch, want = fp32["vk10_and_nr"]
    got = run_batch(platform.session(precision="int8"),
                    "int8_vk10_and_nr", batch, oracle)
    same = all(np.array_equal(g, w) for g, w in zip(got, want))
    log(f"int8: identical_to_fp32={same}")
    if not same:
        fail("int8 rows differ from fp32 rows")


def four_chips(rows, seed):
    platform, vec, qv, rng = build(rows, seed)
    upload(platform)
    upload(platform, shards=4)
    oracle = Oracle(platform)
    radius = vr_radius(platform.view().vector["v"], qv)
    single, sharded = platform.session(shards=0), platform.session(shards=4)
    for name, batch in hybrid_batches(qv, radius, rng).items():
        want = run_batch(single, name, batch, oracle)
        got = run_batch(sharded, f"{name}_shards4", batch, oracle)
        same = all(same_rows(q, g, w, oracle.vec)
                   for q, g, w in zip(batch, got, want))
        log(f"shards=4 vs shards=0 {name}: identical={same}")
        if not same:
            fail(f"{name}: sharded rows differ from single-device rows")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(_SRC, "repro")):
        fail(f"no repro package under {_SRC}: run from a checkout")
    sys.path.insert(0, _SRC)
    from repro.utils.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX's first device is {dev.platform!r}")
    if len(devs) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, "
             f"found {len(devs)}")
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)} jax={jax.__version__} compile_cache={cache} "
        f"{host_gb()}")
    t0 = time.perf_counter()
    (one_chip if args.chips == 1 else four_chips)(args.rows, args.seed)
    log(f"total_s={time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
