"""``topk.merge_passes_per_block``, the reader of the top-k kernel's work
counters (``engine.topk_blocks``, ``engine.topk_merge_passes`` in
``RetrievalServer.stats()["trace"]``): on synthetic records, and on a
tiny traced window on the CPU, where the kernel runs in interpret mode
and so counts its work as on the chip."""
import pytest

import benchpaths
from benchpaths import BENCH

from harness import cells, drive, runner

NAME = "topk.merge_passes_per_block"


def record(stats):
    win = drive.Window(seconds=0.1, t0=1000.0, specs=[], results=[],
                       resolved_by=[], stats=stats)
    return runner.Record(window=win, phase_s={}, device_kind="TPU v5 lite",
                         trace=None)


def counters(**c):
    return {"trace": {"spans": {}, "counters": c,
                      "time_minus_monotonic_ns": 0}}


@pytest.mark.parametrize("stats", [
    {"served": 3, "batches": 1},                       # the parent: no trace
    counters(),
    counters(**{"engine.topk_merge_passes": 4}),       # no blocks ran
    counters(**{"engine.topk_blocks": 0, "engine.topk_merge_passes": 0}),
    counters(**{"engine.topk_blocks": 12}),            # no pass counter
])
def test_reader_returns_nothing_without_the_counters(stats):
    assert cells._reader(BENCH, NAME)(record(stats)) is None


def test_passes_per_block():
    read = cells._reader(BENCH, NAME)
    c = {"engine.topk_blocks": 400, "engine.topk_merge_passes": 250,
         "serve.chunks": 3}
    assert read(record(counters(**c))) == pytest.approx(0.625)
    c["engine.topk_merge_passes"] = 0                  # nothing inserted
    assert read(record(counters(**c))) == 0.0


def test_tiny_traced_window_reports_passes_per_block(tmp_path):
    import jax
    path = benchpaths.add_cell(str(tmp_path))
    cell = cells.resolve(path, "tiny.tiny-mix")
    out = runner.run_cell(cell, 2 ** 31 + 29, 1.0, trace=True,
                          t_start=0.0, device=jax.devices()[0],
                          log=lambda m: None)
    assert out["correct"] is True, out["checks"]
    v = out["metrics"][NAME]["value"]
    k = max(a["k"] for a in cell.mix["archetypes"])
    assert 0 < v <= k
