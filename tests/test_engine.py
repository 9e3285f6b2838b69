"""Batched hybrid-query engine: exactness parity against the scalar path
and the brute-force oracle for every MOAPI archetype, the Pallas
(interpret) vs pure-jnp kernel paths, masked-KNN edge cases, unplannable
fallback, the retrieval-serving wiring, and the device (lax.while_loop)
vs host beam loops — including a property-based / seeded-fuzz oracle
suite over randomly generated rich hybrid batches."""
import numpy as np
import pytest
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.core import query as Q
from repro.core.engine import (EngineStats, batched_knn,
                               batched_knn_device, plannable)
from repro.core.lake import MMOTable
from repro.core.platform import MQRLD
from repro.serve.engine import RetrievalRequest, RetrievalServer


@pytest.fixture(scope="module")
def platform():
    rng = np.random.default_rng(0)
    n, d = 2500, 12
    centers = rng.normal(size=(6, d)).astype(np.float32) * 7
    lab = rng.integers(0, 6, n)
    vec = (centers[lab] + rng.normal(size=(n, d))).astype(np.float32)
    vec2 = rng.normal(size=(n, 6)).astype(np.float32)
    t = (MMOTable("shop")
         .add_vector("img", vec, model="clip")
         .add_vector("audio", vec2, model="audioclip")
         .add_numeric("price", rng.uniform(0, 100, n).astype(np.float32))
         .add_numeric("delivery", rng.uniform(0, 24, n).astype(np.float32)))
    p = MQRLD(t, seed=0)
    p.prepare(min_leaf=16, max_leaf=256, dpc_max_clusters=6)
    return p


def _cases(p):
    t = p.table
    v1 = t.vector["img"][10]
    v2 = t.vector["audio"][10]
    return [
        # basic queries
        Q.NE("price", float(t.numeric["price"][7]), 0.5),
        Q.NR("price", 10, 30),
        Q.VR.of("img", v1, 3.0),
        Q.VK.of("img", v1, 12),
        # the paper's three typical rich hybrids
        Q.And.of(Q.VR.of("img", v1, 4.0), Q.NR("price", 20, 80)),
        Q.And.of(Q.NR("price", 20, 80), Q.VK.of("img", v1, 10)),
        Q.And.of(Q.VR.of("img", v1, 5.0), Q.VK.of("img", v1, 10)),
        # multi-vector, unions, nesting
        Q.And.of(Q.VR.of("img", v1, 6.0), Q.VR.of("audio", v2, 4.0)),
        Q.Or.of(Q.NR("price", 0, 5), Q.VR.of("img", v1, 2.0)),
        Q.And.of(Q.Or.of(Q.NR("price", 0, 50), Q.NR("delivery", 0, 6)),
                 Q.VK.of("img", v1, 15)),
        Q.Or.of(Q.VK.of("img", v1, 5), Q.NR("price", 99, 100)),
        # edges: near-empty filter under VK, tight mask, empty predicate
        Q.And.of(Q.NR("price", 40, 41), Q.VK.of("img", v1, 50)),
        Q.And.of(Q.VR.of("img", v1, 0.1), Q.VK.of("img", v1, 5)),
        Q.NR("price", 200, 300),
    ]


def _rowset(rows):
    return set(np.asarray(rows).tolist())


def test_execute_batch_parity(platform):
    """One batch, every archetype: engine == scalar execute == oracle.
    Runs on the engine default, i.e. the Pallas fused_topk kernel in
    interpret mode on CPU."""
    p = platform
    cases = _cases(p)
    results, stats = p.execute_batch(cases)
    assert stats.queries == len(cases)
    for q, rows in zip(cases, results):
        scalar, _ = p.execute(q, record=False)
        assert _rowset(rows) == _rowset(scalar), q
        assert _rowset(rows) == _rowset(p.oracle(q)), q


def test_execute_batch_kernel_paths_agree(platform):
    """interpret=True (Pallas interpret kernel) and interpret=False
    (pure-jnp ref on CPU) return identical rows."""
    p = platform
    cases = _cases(p)
    got_pallas, _ = p.execute_batch(cases, interpret=True)
    got_ref, _ = p.execute_batch(cases, interpret=False)
    for q, a, b in zip(cases, got_pallas, got_ref):
        assert _rowset(a) == _rowset(b), q


def test_toplevel_vk_distance_order(platform):
    """Top-level V.K results come back distance-ordered, like the scalar
    executor's ranking."""
    p = platform
    v = p.table.vector["img"][77]
    (rows,), _ = p.execute_batch([Q.VK.of("img", v, 9)])
    d = ((p.table.vector["img"][rows] - v) ** 2).sum(1)
    assert (np.diff(d) >= -1e-6).all()
    assert len(rows) == 9


def test_masked_knn_fewer_matches_than_k(platform):
    """And(NR, VK) where the filter admits fewer rows than k: the engine
    returns exactly the surviving rows, like the scalar path."""
    p = platform
    price = p.table.numeric["price"]
    lo = float(np.sort(price)[3])  # filter admits ~4 rows
    q = Q.And.of(Q.NR("price", 0.0, lo), Q.VK.of("img",
                                                 p.table.vector["img"][5],
                                                 20))
    (rows,), _ = p.execute_batch([q])
    scalar, _ = p.execute(q, record=False)
    assert _rowset(rows) == _rowset(scalar) == _rowset(p.oracle(q))
    assert len(rows) <= 20


def test_unplannable_falls_back_to_scalar(platform):
    """A V.K nested under a combiner that is a *sibling* of other And
    parts is order-dependent in the scalar executor: the engine refuses it
    and MQRLD.execute_batch transparently falls back."""
    p = platform
    v = p.table.vector["img"][3]
    q = Q.And.of(Q.Or.of(Q.VK.of("img", v, 10), Q.NR("price", 0, 1)),
                 Q.NR("price", 0, 60))
    assert not plannable(q)
    ok = Q.And.of(Q.NR("price", 0, 60), Q.VK.of("img", v, 10))
    assert plannable(ok)
    results, _ = p.execute_batch([q, ok])
    for qq, rows in zip([q, ok], results):
        scalar, _ = p.execute(qq, record=False)
        assert _rowset(rows) == _rowset(scalar), qq


def test_batched_knn_matches_oracle(platform):
    """The engine's beam-doubled masked KNN core, standalone: exact
    against brute force with and without a row mask."""
    p = platform
    eng = p.engine()
    col = p.table.vector["img"]
    rng = np.random.default_rng(3)
    qs = col[rng.integers(0, len(col), 6)] + \
        rng.normal(size=(6, col.shape[1])).astype(np.float32) * 0.2
    mask = p.table.numeric["price"] < 50.0
    stats = EngineStats()
    _, rows = batched_knn(eng.geom["img"], eng.vec_tiles["img"],
                          qs.astype(np.float32), 7,
                          masks=np.broadcast_to(mask, (6, len(mask))),
                          beam=4, stats=stats)
    d2 = ((col[None, :, :] - qs[:, None, :]) ** 2).sum(-1)
    d2 = np.where(mask[None, :], d2, np.inf)
    for i in range(6):
        want = set(np.argsort(d2[i], kind="stable")[:7].tolist())
        assert set(rows[i][rows[i] >= 0].tolist()) == want
    assert stats.knn_rounds >= 1 and stats.rows_scanned > 0


def test_engine_rebuilt_after_prepare():
    rng = np.random.default_rng(5)
    vec = rng.normal(size=(400, 8)).astype(np.float32)
    p = MQRLD(MMOTable("t").add_vector("v", vec), seed=1)
    p.prepare(min_leaf=8, max_leaf=64)
    e1 = p.engine()
    q = Q.VK.of("v", vec[0], 5)
    (r1,), _ = p.execute_batch([q])
    p.prepare(min_leaf=8, max_leaf=128)
    assert p.engine() is not e1  # stale device state was invalidated
    (r2,), _ = p.execute_batch([q])
    assert _rowset(r2) == _rowset(p.oracle(q))


class _StubEmbedder:
    """Duck-typed embedder: maps a token row to a table vector, so the
    serving path is testable without a model forward pass."""

    def __init__(self, table):
        self.table = table

    def embed(self, tokens):
        rows = np.asarray(tokens)[:, 0] % self.table.n_rows
        return self.table.vector["img"][rows] + 0.01


# ---------------------------------------------------------------------------
# Device (lax.while_loop) vs host beam loop
# ---------------------------------------------------------------------------
def test_execute_batch_host_loop_parity(platform):
    """device_loop=False (the host-driven exactness oracle) returns the
    same rows as the device loop and the brute-force oracle."""
    p = platform
    cases = _cases(p)
    dev, _ = p.execute_batch(cases, device_loop=True)
    host, _ = p.execute_batch(cases, device_loop=False)
    for q, a, b in zip(cases, dev, host):
        assert _rowset(a) == _rowset(b) == _rowset(p.oracle(q)), q


def test_batched_knn_device_matches_host_and_oracle(platform):
    """The standalone device beam loop: row-for-row identical to the
    host loop (shared tile layout) and exact against brute force, with
    and without a row mask, across k edge cases."""
    p = platform
    eng = p.engine()
    col = p.table.vector["img"]
    rng = np.random.default_rng(7)
    qs = (col[rng.integers(0, len(col), 9)] +
          rng.normal(size=(9, col.shape[1])).astype(np.float32) * 0.3
          ).astype(np.float32)
    mask = p.table.numeric["price"] < 35.0
    masks = np.broadcast_to(mask, (9, len(mask)))
    for use_mask in (False, True):
        for k in (1, 8, 40):
            m = masks if use_mask else None
            _, rh = batched_knn(eng.geom["img"], eng.vec_tiles["img"],
                                qs, k, masks=m, beam=4)
            _, rd = batched_knn_device(eng.geom["img"],
                                       eng.vec_tiles["img"],
                                       qs, k, masks=m, beam=4)
            # same layout + same stable tie-break => identical arrays
            assert np.array_equal(rh, rd), (use_mask, k)
            d2 = ((np.asarray(col)[None] - qs[:, None]) ** 2).sum(-1)
            if use_mask:
                d2 = np.where(np.asarray(mask)[None], d2, np.inf)
            for i in range(len(qs)):
                sel = np.argsort(d2[i], kind="stable")[:k]
                want = set(sel[np.isfinite(d2[i][sel])].tolist())
                assert set(rd[i][rd[i] >= 0].tolist()) == want


def test_mask_rows_may_stop_short_of_the_queries(platform):
    """A group's masked jobs come first and only their rows are passed:
    the unmasked tail's all-true rows are built on device, and both
    beam loops return what a full mask array gives."""
    p = platform
    eng = p.engine()
    col = p.table.vector["img"]
    qs = col[[3, 70, 500, 900, 1500, 2200]].astype(np.float32) + 0.2
    mask = p.table.numeric["price"] < 35.0
    part = np.broadcast_to(mask, (2, len(mask)))
    full = np.concatenate([part, np.ones((4, len(mask)), bool)])
    for knn, geom, tiles in (
            (batched_knn, eng.geom["img"], eng.vec_tiles["img"]),
            (batched_knn_device, eng.geom_dev["img"],
             eng.vec_tiles_dev["img"])):
        _, want = knn(geom, tiles, qs, 8, masks=full, beam=4)
        _, got = knn(geom, tiles, qs, 8, masks=part, beam=4)
        assert np.array_equal(got, want)
        assert not np.isin(got[:2], np.nonzero(~mask)[0]).any()


def test_device_loop_empty_mask(platform):
    """A filter admitting zero rows: the device loop returns no rows
    instead of looping to the budget."""
    p = platform
    eng = p.engine()
    qs = p.table.vector["img"][:3].astype(np.float32)
    masks = np.zeros((3, p.table.n_rows), bool)
    stats = EngineStats()
    _, rows = batched_knn_device(eng.geom_dev["img"],
                                 eng.vec_tiles_dev["img"], qs, 5,
                                 masks=masks, beam=4, stats=stats)
    assert (rows == -1).all()
    assert stats.knn_rounds == 1  # bound fires right after round 1


# ---------------------------------------------------------------------------
# Property-based / seeded-fuzz oracle suite: random rich hybrid batches
# must match brute force exactly on BOTH beam loops
# ---------------------------------------------------------------------------
_FUZZ_KS = (1, 5, 17)  # small set keeps the static-k compile universe tiny


@pytest.fixture(scope="module")
def fuzz_platform():
    rng = np.random.default_rng(11)
    n = 700
    centers = rng.normal(size=(5, 8)).astype(np.float32) * 5
    lab = rng.integers(0, 5, n)
    img = (centers[lab] + rng.normal(size=(n, 8))).astype(np.float32)
    audio = rng.normal(size=(n, 5)).astype(np.float32) * 2
    t = (MMOTable("fuzz")
         .add_vector("img", img)
         .add_vector("audio", audio)
         .add_numeric("price", rng.uniform(0, 100, n).astype(np.float32))
         .add_numeric("stock", rng.integers(0, 50, n).astype(np.float32)))
    p = MQRLD(t, seed=2)
    p.prepare(min_leaf=8, max_leaf=64, dpc_max_clusters=5)
    return p


def _rand_basic(rng, tab):
    kind = rng.integers(0, 4)
    if kind == 0:
        attr = ("price", "stock")[rng.integers(0, 2)]
        col = tab.numeric[attr]
        v = float(col[rng.integers(0, len(col))])
        tol = float(rng.choice([1e-6, 0.5, 5.0]))
        return Q.NE(attr, v, tol)
    if kind == 1:
        attr = ("price", "stock")[rng.integers(0, 2)]
        lo = float(rng.uniform(-10, 100))
        return Q.NR(attr, lo, lo + float(rng.uniform(0, 60)))
    attr = ("img", "audio")[rng.integers(0, 2)]
    col = tab.vector[attr]
    base = col[rng.integers(0, len(col))]
    v = base + rng.normal(size=col.shape[1]).astype(np.float32) \
        * float(rng.uniform(0, 0.5))
    if kind == 2:
        anchor = col[rng.integers(0, len(col))]
        r = float(np.sqrt(((anchor - v) ** 2).sum()) * rng.uniform(0.3, 1.5))
        return Q.VR.of(attr, v, max(r, 1e-3))
    return Q.VK.of(attr, v, int(rng.choice(_FUZZ_KS)))


def _rand_query(rng, tab, depth=2):
    if depth == 0 or rng.random() < 0.45:
        return _rand_basic(rng, tab)
    parts = tuple(_rand_query(rng, tab, depth - 1)
                  for _ in range(rng.integers(2, 4)))
    return Q.And(parts) if rng.random() < 0.5 else Q.Or(parts)


def _check_fuzz_batch(p, rng, batch_size=3):
    """One random hybrid batch through BOTH beam loops.

    Plannable trees must match the brute-force oracle exactly.
    Unplannable trees ride along deliberately: their contract is SCALAR
    parity — ``MQRLD.execute_batch`` falls back to the scalar executor,
    whose one order-dependent corner (a V.K inside a combiner that is a
    sibling of other And parts sees partially-accumulated masks)
    intentionally deviates from the oracle; see the engine module
    docstring."""
    batch = [_rand_query(rng, p.table) for _ in range(batch_size)]
    truth = [Q.execute_bruteforce(p.table, q) if plannable(q)
             else p.execute(q, record=False)[0] for q in batch]
    for dl in (True, False):
        got, _ = p.execute_batch(batch, device_loop=dl)
        for q, rows, want in zip(batch, got, truth):
            assert _rowset(rows) == _rowset(want), (dl, q)


@pytest.mark.parametrize("seed", range(8))
def test_fuzz_hybrid_batches_match_oracle(fuzz_platform, seed):
    """Seeded fuzz (no hypothesis needed): 8 seeds x 25 batches = 200
    generated hybrid batches, each checked on both beam loops."""
    rng = np.random.default_rng(1000 + seed)
    for _ in range(25):
        _check_fuzz_batch(fuzz_platform, rng)


@settings(max_examples=20, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(st.integers(min_value=0, max_value=2**31 - 1))
def test_property_hybrid_batch_matches_oracle(fuzz_platform, seed):
    """Hypothesis-driven variant of the fuzz suite (skips via the
    conftest shim when hypothesis is unavailable)."""
    _check_fuzz_batch(fuzz_platform, np.random.default_rng(seed))


def test_fuzz_toplevel_vk_distance_ordered(fuzz_platform):
    """Top-level V.K results stay distance-ordered on both loops for
    random queries."""
    p = fuzz_platform
    rng = np.random.default_rng(77)
    col = p.table.vector["img"]
    for _ in range(10):
        v = col[rng.integers(0, len(col))] + \
            rng.normal(size=col.shape[1]).astype(np.float32) * 0.2
        q = Q.VK.of("img", v, int(rng.choice(_FUZZ_KS)))
        for dl in (True, False):
            (rows,), _ = p.execute_batch([q], device_loop=dl)
            d = ((col[rows] - q.vec()) ** 2).sum(1)
            assert (np.diff(d) >= -1e-6).all()


# ---------------------------------------------------------------------------
# Stats regression pin: beam seeding / pruning changes must not silently
# regress round counts or V.R tile pruning
# ---------------------------------------------------------------------------
_PINNED_STATS = {
    "dev_rounds": 2, "dev_buckets": 56,
    "dev_vr_scanned": 20, "dev_vr_pruned": 140,
    "dev_pred_buckets": 64,
    "host_rounds": 2, "host_buckets": 48, "host_vr_pruned": 140,
}


def test_engine_stats_pinned_on_fixed_seed():
    """Beam-seeding or pruning changes must not silently regress round
    counts / pruned-tile counts: pinned on a fixed seed, raw-space
    build (tight tiles, so the V.R tile route engages)."""
    rng = np.random.default_rng(42)
    n, d = 6000, 8
    centers = rng.normal(size=(8, d)).astype(np.float32) * 6
    lab = rng.integers(0, 8, n)
    vec = (centers[lab] + rng.normal(size=(n, d))).astype(np.float32)
    t = (MMOTable("pin").add_vector("v", vec)
         .add_numeric("price", rng.uniform(0, 100, n).astype(np.float32)))
    p = MQRLD(t, seed=3)
    p.prepare(min_leaf=16, max_leaf=256, use_transform=False,
              use_lpgf=False)
    v0, v1 = vec[10], vec[999]
    batch = [
        Q.VK.of("v", v0, 10),
        Q.And.of(Q.NR("price", 20, 60), Q.VK.of("v", v1, 10)),
        Q.VR.of("v", v0, 2.0),
        Q.And.of(Q.VR.of("v", v1, 2.0), Q.VK.of("v", v1, 5)),
    ]
    results, dev = p.execute_batch(batch, device_loop=True)
    for q, r in zip(batch, results):  # exactness first, stats second
        assert _rowset(r) == _rowset(p.oracle(q)), q
    _, host = p.execute_batch(batch, device_loop=False)
    got = {
        "dev_rounds": dev.knn_rounds,
        "dev_buckets": dev.knn_buckets,
        "dev_vr_scanned": dev.vr_tiles_scanned,
        "dev_vr_pruned": dev.vr_tiles_pruned,
        "dev_pred_buckets": dev.predicate_buckets,
        "host_rounds": host.knn_rounds,
        "host_buckets": host.knn_buckets,
        "host_vr_pruned": host.vr_tiles_pruned,
    }
    assert got == _PINNED_STATS, (
        f"EngineStats drifted from the pinned seed-42 values: {got} != "
        f"{_PINNED_STATS}. If the change to beam seeding / pruning is "
        f"intentional and exactness tests still pass, update "
        f"_PINNED_STATS.")


def test_retrieval_server_serves_batches(platform):
    p = platform
    server = RetrievalServer(p, _StubEmbedder(p.table), batch_size=4)
    reqs = [RetrievalRequest(tokens=np.asarray([i, 1, 2], np.int32),
                             attr="img", k=5,
                             predicate=Q.NR("price", 10, 90))
            for i in (3, 50, 999, 1500, 2222)]
    out = server.serve(reqs)
    assert len(out) == 5
    stub = _StubEmbedder(p.table)
    for req, res in zip(reqs, out):
        assert 0 < len(res.rows) <= 5
        prices = p.table.numeric["price"][res.rows]
        assert ((prices >= 10) & (prices <= 90)).all()
        assert _rowset(res.rows) == _rowset(p.oracle(res.query))
        # filtered results are re-ranked: rows come back distance-ordered
        emb = stub.embed(req.tokens[None, :])[0]
        d2 = ((p.table.vector["img"][res.rows] - emb) ** 2).sum(1)
        assert (np.diff(d2) >= -1e-6).all()


def test_retrieval_server_submission_order_with_fallbacks(platform):
    """Results come back in SUBMISSION order even when the planner
    splits the batch: plannable requests go through the engine in
    groups while non-plannable predicates (a V.K inside the filter
    tree) fall back to the scalar path, interleaved. Each result must
    belong to ITS OWN request — distinct ks and filters make any
    positional mix-up detectable."""
    p = platform
    v = p.table.vector["img"][3]
    # a predicate tree containing a VK makes And(pred, VK) unplannable
    npred = Q.Or.of(Q.VK.of("img", v, 50), Q.NR("price", 0, 2))
    reqs = []
    for i, r0 in enumerate((3, 50, 999, 150, 720, 42, 7)):
        pred = npred if i % 2 else Q.NR("price", 10, 90)
        reqs.append(RetrievalRequest(
            tokens=np.asarray([r0, 1], np.int32), attr="img",
            k=3 + i, predicate=pred))
    server = RetrievalServer(p, _StubEmbedder(p.table), batch_size=4)
    out = server.serve(reqs)
    assert len(out) == len(reqs)
    stub = _StubEmbedder(p.table)
    for i, (req, res) in enumerate(zip(reqs, out)):
        # the returned query must be the one built from THIS request
        vks = [b for b in Q.basic_queries(res.query)
               if isinstance(b, Q.VK) and b.k == req.k]
        assert vks, (i, req.k, res.query)
        emb = stub.embed(req.tokens[None, :])[0]
        assert np.allclose(vks[0].vec(), emb, atol=1e-5)
        assert req.predicate in res.query.parts
        # and the rows must be that query's exact answer
        assert _rowset(res.rows) == _rowset(p.oracle(res.query)), i


def test_retrieval_server_device_loop_flag(platform):
    """device_loop=False routes serving through the host oracle loop;
    results match the default device path."""
    p = platform
    reqs = [RetrievalRequest(tokens=np.asarray([i, 1], np.int32),
                             attr="img", k=6,
                             predicate=Q.NR("price", 5, 95))
            for i in (12, 88, 1021)]
    dev = RetrievalServer(p, _StubEmbedder(p.table)).serve(reqs)
    host = RetrievalServer(p, _StubEmbedder(p.table),
                           device_loop=False).serve(reqs)
    for a, b in zip(dev, host):
        assert np.array_equal(a.rows, b.rows)
