"""Spans and counters (``repro.utils.spans``) and what ``RetrievalServer``
records with them: bounded rings and their counts, nested spans, every
span and counter of a served chunk in ``stats()["trace"]`` (serial and
pipelined), the engine counters against the ``EngineStats`` the planned
path returned for the same chunks, and results unchanged by recording."""
import time

import numpy as np
import pytest

from repro.core import query as Q
from repro.core.lake import MMOTable
from repro.core.planner import ExecutablePlan
from repro.core.platform import MQRLD
from repro.serve.engine import RetrievalRequest, RetrievalServer
from repro.utils import spans
from repro.utils.spans import Recorder, span

SERVE_SPANS = ("serve.chunk", "serve.queue_wait", "serve.embed",
               "serve.queries", "serve.plan", "serve.execute",
               "serve.rank", "serve.finish")
ENGINE_SPANS = ("engine.masks", "engine.masks_fence",
                "engine.knn_dispatch", "engine.knn_fence",
                "engine.stragglers", "engine.straggler_fence",
                "engine.walk", "plan.feedback")
ENGINE_COUNTERS = ("queries", "rows_scanned", "knn_rounds", "knn_buckets",
                   "predicate_buckets", "topk_blocks", "topk_merge_passes")


# ---------------------------------------------------------------------------
# the facility
# ---------------------------------------------------------------------------
def _spans(rec):
    return rec.snapshot()["spans"]


def _ring(rec, name):
    return _spans(rec)[name]["ring"]


def test_ring_keeps_the_newest_entries_and_counts_all(monkeypatch):
    monkeypatch.setattr(spans, "RING", 4)
    rec = Recorder()
    for i in range(10):
        rec.add("s", i, 10 * i)
    assert _spans(rec)["s"] == {"n": 10, "ring": [[6, 60], [7, 70],
                                                  [8, 80], [9, 90]]}
    rec.add("t", 1, 2)
    assert _spans(rec)["t"] == {"n": 1, "ring": [[1, 2]]}
    assert "missing" not in _spans(rec)


def test_default_ring_holds_16384_entries():
    rec = Recorder()
    for i in range(spans.RING + 5):
        rec.add("s", i, 1)
    snap = _spans(rec)["s"]
    assert snap["n"] == spans.RING + 5
    kept = snap["ring"]
    assert len(kept) == spans.RING >= 16384
    assert kept[0][0] == 5 and kept[-1][0] == spans.RING + 4


def test_counters_and_samples():
    rec = Recorder()
    rec.count("c")
    rec.count("c", 4)
    rec.sample("wait", 1_000, 5_000)
    rec.sample("wait", 7, 7)
    lo = time.time_ns() - time.monotonic_ns()
    snap = rec.snapshot()
    hi = time.time_ns() - time.monotonic_ns()
    assert snap["counters"] == {"c": 5}
    assert snap["spans"]["wait"] == {"n": 2, "ring": [[4_000, 1_000],
                                                      [0, 7]]}
    # the host clock less the monotonic one, as read with the snapshot
    assert lo - 1_000_000 <= snap["time_minus_monotonic_ns"] \
        <= hi + 1_000_000


def test_nested_spans_record_inside_their_parent():
    rec = Recorder()
    with spans.recording(rec):
        with span("outer", size=3) as outer:
            time.sleep(0.002)
            with span("inner") as inner:
                time.sleep(0.002)
            with span("inner"):
                pass
    (o_start, o_dur), = _ring(rec, "outer")
    inners = _ring(rec, "inner")
    assert _spans(rec)["inner"]["n"] == 2
    assert o_dur >= inners[0][1] >= 2_000_000
    for s, d in inners:   # start on the host clock, length monotonic
        assert o_start <= s and s + d <= o_start + o_dur + 1_000_000
    assert outer.seconds == pytest.approx(o_dur / 1e9)
    assert inner.seconds == pytest.approx(inners[0][1] / 1e9)


def test_span_without_recorder_only_times():
    rec = Recorder()
    with span("alone") as s:
        time.sleep(0.001)
    assert s.seconds >= 0.001
    with spans.recording(rec):
        with spans.recording(None):
            with span("hidden"):
                pass
        with span("seen"):          # the outer recorder is back
            pass
    with span("after"):
        pass
    assert _spans(rec).keys() == {"seen"}


def test_span_records_when_its_block_raises():
    rec = Recorder()
    with pytest.raises(KeyError), spans.recording(rec), span("failing"):
        raise KeyError("x")
    with span("after"):
        pass
    assert _spans(rec).keys() == {"failing"}
    assert _spans(rec)["failing"]["n"] == 1


# ---------------------------------------------------------------------------
# the server's record
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def platform():
    rng = np.random.default_rng(3)
    n, d = 800, 8
    centers = rng.normal(size=(6, d)).astype(np.float32) * 6
    vec = (centers[rng.integers(0, 6, n)]
           + rng.normal(size=(n, d))).astype(np.float32)
    t = (MMOTable("spans_shop").add_vector("img", vec)
         .add_numeric("price", rng.uniform(0, 100, n).astype(np.float32)))
    p = MQRLD(t, seed=0)
    p.prepare(min_leaf=8, max_leaf=64, dpc_max_clusters=5)
    return p


class _Embedder:
    def __init__(self, table):
        self.table = table

    def embed(self, tokens):
        rows = np.asarray(tokens)[:, 0] % self.table.n_rows
        return self.table.vector["img"][rows] + 0.25


def _requests(n=22):
    """Unfiltered and filtered k-NN, interleaved, with large k so that
    the beam needs straggler rounds."""
    out = []
    for i in range(n):
        pred = Q.NR("price", 10.0, 70.0) if i % 2 else None
        out.append(RetrievalRequest(tokens=np.asarray([37 * i + 5, 1],
                                                      np.int32),
                                    attr="img", k=60, predicate=pred))
    return out


def _serve(platform, depth, reqs):
    srv = RetrievalServer(platform, _Embedder(platform.table),
                          batch_size=4, pipeline_depth=depth)
    futs = [srv.submit(r) for r in reqs]
    srv.flush()
    return srv, [f.result() for f in futs]


@pytest.mark.parametrize("depth", [1, 2])
def test_server_trace_names_every_span_and_counter(platform, depth):
    srv, res = _serve(platform, depth, _requests())
    st = srv.stats()
    tr = st["trace"]
    names = set(tr["spans"])
    want = set(SERVE_SPANS) | set(ENGINE_SPANS)
    if depth > 1:
        want |= {"serve.dispatch", "serve.retire"}
    assert want <= names, want - names
    assert tr["spans"]["serve.chunk"]["n"] == st["batches"]
    assert tr["spans"]["serve.queue_wait"]["n"] == st["served"] == 22
    assert all(r.rows.size for r in res)
    c = tr["counters"]
    assert c["serve.chunks"] == st["batches"]
    why = {k: v for k, v in c.items() if k.startswith("serve.chunks.")}
    assert sum(why.values()) == st["batches"]
    assert set(why) <= {"serve.chunks." + r for r in
                        ("full", "window", "deadline", "flush",
                         "backpressure")}
    # 11 per signature at batch 4: full chunks at submit, then the
    # flush takes 2 of each remaining 3 and leaves 1 for another chunk
    assert why["serve.chunks.full"] == 4
    assert c["serve.rounded_off"] == 2
    for name in ENGINE_COUNTERS:
        assert c["engine." + name] > 0
    assert c["engine.queries"] == 22
    chunks = tr["spans"]["serve.chunk"]["ring"]
    for stage in ("serve.embed", "serve.rank", "serve.finish"):
        for s, d in tr["spans"][stage]["ring"]:
            assert any(cs <= s + 1_000_000 and s + d <= cs + cd + 1_000_000
                       for cs, cd in chunks)


def test_engine_counters_equal_the_plans_engine_stats(platform,
                                                      monkeypatch):
    seen = []
    run = ExecutablePlan.execute

    def spy(self):
        rows, stats = run(self)
        seen.append(stats)
        return rows, stats

    monkeypatch.setattr(ExecutablePlan, "execute", spy)
    srv, _ = _serve(platform, 1, _requests(14))
    c = srv.stats()["trace"]["counters"]
    assert len(seen) == srv.stats()["batches"]
    for name in ENGINE_COUNTERS:
        assert c["engine." + name] == sum(getattr(s, name) for s in seen)


@pytest.mark.parametrize("depth", [1, 2])
def test_topk_counters_bound_the_merge(platform, depth):
    """The top-k kernel's grid steps and merge passes reach the trace:
    at most k passes a step (k=60), and far fewer once the straggler
    rounds are seeded with their carry's kth."""
    srv, _ = _serve(platform, depth, _requests())
    c = srv.stats()["trace"]["counters"]
    blocks, passes = c["engine.topk_blocks"], c["engine.topk_merge_passes"]
    assert 0 < passes <= 60 * blocks


def test_window_and_backpressure_reasons(platform):
    clock = [0.0]
    srv = RetrievalServer(platform, _Embedder(platform.table),
                          batch_size=4, max_delay_ms=50.0, max_queue=3,
                          clock=lambda: clock[0])
    reqs = _requests(4)
    for r in reqs[:3]:                 # two signatures, no full group
        srv.submit(r)
    assert srv.poll() == 0             # inside the window
    clock[0] = 0.06
    assert srv.poll() == 2             # the window ran out: 2 of the VK
    for r in (reqs[3], reqs[0]):
        srv.submit(r)                  # three queued: at max_queue
    assert srv.stats()["batches"] == 1
    srv.submit(reqs[2])                # backpressure runs a chunk first
    c = srv.stats()["trace"]["counters"]
    assert c["serve.chunks.window"] == 1
    assert c["serve.chunks.backpressure"] == 1
    waits = [d for _, d in _ring(srv.recorder, "serve.queue_wait")]
    assert waits[:2] == pytest.approx([6e7, 6e7], abs=1)


def test_results_identical_with_and_without_recorder(platform):
    reqs = _requests(12)
    queries = [Q.And.of(r.predicate, Q.VK.of("img", e, r.k))
               if r.predicate is not None else Q.VK.of("img", e, r.k)
               for r, e in zip(reqs, _Embedder(platform.table).embed(
                   np.stack([r.tokens for r in reqs])))]
    sess = platform.session()
    plain, _ = sess.plan(queries).execute()
    rec = Recorder()
    with spans.recording(rec):
        recorded, _ = sess.plan(queries).execute()
    assert _spans(rec)["engine.knn_fence"]["n"] > 0
    assert len(plain) == len(recorded)
    for a, b in zip(plain, recorded):
        np.testing.assert_array_equal(a, b)
    _, served = _serve(platform, 1, reqs)
    for q, r in zip(queries, served):
        truth = platform.oracle(q)
        assert set(r.rows.tolist()) == set(np.asarray(truth).tolist())
