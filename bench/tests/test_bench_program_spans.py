"""The per-layer metrics that read the program's own spans and counters
(``RetrievalServer.stats()["trace"]``): each reader on a synthetic
record, ``execute.device_idle_pct`` on a synthetic trace whose overlap
with the chunks is known, and all of them on a tiny traced window on the
CPU (where the trace has no device plane, so the idle share is left
out)."""
import pytest

import benchpaths
from benchpaths import BENCH

from harness import cells, drive, runner
from harness import trace as tracemod

NEW = ("serve.queue_wait_ms.p50", "serve.rounded_off_per_chunk",
       "execute.host_ms.p50", "execute.device_idle_pct",
       "knn.rounds_per_chunk", "knn.rows_per_query")
MS = 1_000_000


def reader(name):
    return cells._reader(BENCH, name)


def record(stats, t0=1000.0, trace=None):
    win = drive.Window(seconds=0.1, t0=t0, specs=[], results=[],
                       resolved_by=[], stats=stats)
    return runner.Record(window=win, phase_s={}, device_kind="TPU v5 lite",
                         trace=trace)


WALL = 1_792_000_000_000_000_000      # host clock less monotonic, ns


def program(spans=None, counters=None):
    return {"trace": {"spans": {k: {"n": len(v), "ring": v}
                                for k, v in (spans or {}).items()},
                      "counters": dict(counters or {}),
                      "time_minus_monotonic_ns": WALL}}


@pytest.mark.parametrize("name", NEW)
def test_reader_returns_nothing_without_the_programs_record(name):
    """The parent's server has no ``trace`` in ``stats()``."""
    assert reader(name)(record({"served": 3, "batches": 1})) is None
    assert reader(name)(record(program())) is None


def test_queue_wait_is_the_median_sample():
    rec = record(program({"serve.queue_wait": [[0, 1 * MS], [5, 3 * MS],
                                               [9, 2 * MS]]}))
    assert reader("serve.queue_wait_ms.p50")(rec) == pytest.approx(2.0)


def test_counter_ratios():
    c = {"serve.chunks": 4, "serve.rounded_off": 6, "engine.knn_rounds": 10,
         "engine.rows_scanned": 5000, "engine.queries": 20}
    rec = record(program(counters=c))
    assert reader("serve.rounded_off_per_chunk")(rec) == 1.5
    assert reader("knn.rounds_per_chunk")(rec) == 2.5
    assert reader("knn.rows_per_query")(rec) == 250.0
    del c["serve.rounded_off"]         # never rounded: none left behind
    assert reader("serve.rounded_off_per_chunk")(
        record(program(counters=c))) == 0.0


def test_host_time_is_the_chunk_less_its_fences():
    rec = record(program({
        "serve.chunk": [[20_000, 8_000], [1_000, 10_000], [40_000, 4_000]],
        "engine.knn_fence": [[1_100, 2_000]],
        "engine.straggler_fence": [[5_000, 1_000]],
        "engine.masks_fence": [[20_100, 500], [30_000, 100]],  # 2nd: none
        "engine.walk": [[2_000, 3_000]],                       # no fence
    }))
    # host 7,000, 7,500 and 4,000 ns: the median is 7,000 ns
    assert reader("execute.host_ms.p50")(rec) == pytest.approx(0.007)


def test_device_idle_inside_the_chunks():
    """Window 100 ms, chunks at 10-30, 50-70 and 95-110 ms (the last cut
    at the window's end): 45 ms of chunk, of which 22 ms see an op. The
    harness opened ``bench.window`` 0.3 ms after it read ``Window.t0``;
    its calls' spans, opened as it read their starts, correct that."""
    w0 = 5 * MS                         # bench.window start, trace clock
    t0 = 1234.5                         # Window.t0, time.monotonic()
    gap = 0.3 * MS
    host0 = t0 * 1e9 + gap + WALL

    def ring(a, b):
        return [host0 + a * MS, (b - a) * MS]

    ops = [tracemod.Event(f"op{i}", w0 + a * MS, (b - a) * MS)
           for i, (a, b) in enumerate([(12, 22), (25, 40), (60, 65),
                                       (80, 90), (96, 98)])]
    # the polls that ran chunks open their spans 2-40 us after the
    # harness read their starts; a short poll just before is no match
    calls = [drive.Call("poll", t0 + (gap + a * MS) / 1e9, 0.001, 1)
             for a in (9.9, 49.9, 94.9)]
    host = [tracemod.Event("bench.window", w0, 100 * MS)] + [
        tracemod.Event("bench.poll", w0 + a * MS + late, 1 * MS)
        for a, late in ((2.0, 0), (9.9, 2_000), (30.5, 0), (49.9, 40_000),
                        (94.9, 9_000))]
    host += [tracemod.Event("bench.poll", w0 + 9.85 * MS, 0.005 * MS),
             tracemod.Event("bench.submit", w0 + 49.95 * MS, 0.01 * MS)]
    tr = tracemod.Trace(devices={"/device:TPU:0": ops}, host=host,
                        t0_ns=w0, t1_ns=w0 + 100 * MS)
    stats = program({"serve.chunk": [ring(50, 70), ring(10, 30),
                                     ring(95, 110)]})
    read = reader("execute.device_idle_pct")
    rec = record(stats, t0, tr)
    rec.window.calls = calls
    assert read(rec) == pytest.approx(100 * (1 - 22 / 45), rel=2e-4)
    rec.window.calls = []               # the first estimate alone is off
    assert read(rec) != pytest.approx(100 * (1 - 22 / 45), rel=1e-4)
    assert read(record(stats, t0, None)) is None
    empty = tracemod.Trace(devices={}, host=tr.host, t0_ns=w0,
                           t1_ns=w0 + 100 * MS)
    assert read(record(stats, t0, empty)) is None
    del stats["trace"]["time_minus_monotonic_ns"]   # no clock: no join
    assert read(record(stats, t0, tr)) is None


def test_tiny_traced_window_reports_the_new_metrics(tmp_path):
    import jax
    path = benchpaths.add_cell(str(tmp_path))
    cell = cells.resolve(path, "tiny.tiny-mix")
    out = runner.run_cell(cell, 2 ** 31 + 11, 1.0, trace=True,
                          t_start=0.0, device=jax.devices()[0],
                          log=lambda m: None)
    assert out["correct"] is True, out["checks"]
    m = out["metrics"]
    assert set(NEW) - set(m) == {"execute.device_idle_pct"}
    assert m["knn.rows_per_query"]["value"] > 0
    assert m["knn.rounds_per_chunk"]["value"] >= 1
    assert m["serve.queue_wait_ms.p50"]["value"] >= 0
    assert m["execute.host_ms.p50"]["value"] > 0
