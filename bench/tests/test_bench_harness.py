"""The benchmark harness on the CPU at a tiny size: cells resolved by
name, a cell added as files alone, the generator's steadiness, the peaks
table and the refusal to measure without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import benchpaths
from benchpaths import BENCH, ROOT

from harness import cells, data, peaks, runner, traffic


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", [w["name"] for w in _spec()["workloads"]])
def test_cells_resolve_by_name(cell):
    c = cells.resolve(os.path.join(ROOT, "BENCHMARK.json"), cell)
    spec = _spec()
    w = {x["name"]: x for x in spec["workloads"]}[cell]
    assert c.config["name"] == w["config"]
    assert c.mix["server"]["batch_size"] >= 1
    assert {m.name for m in c.end_to_end} >= {"setup_s", "qps"}
    assert {m.name for m in c.per_layer} == {
        m["name"] for m in spec["per_layer"]}
    assert all(callable(m.read) for m in c.per_layer)


def test_config_files_list_every_reduction():
    spec = _spec()
    for c in spec["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
        assert cfg["name"] == c["name"]


def test_new_cell_from_added_files_runs_on_cpu(tmp_path):
    import jax
    path = benchpaths.add_cell(str(tmp_path))
    cell = cells.resolve(path, "tiny.tiny-mix")
    assert cell.config["rows"] == 1500
    out = runner.run_cell(cell, 2 ** 31 + 77, 1.0, trace=False,
                          t_start=0.0, device=jax.devices()[0],
                          log=lambda m: None)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] == 20 and out["failed"] == 0
    assert set(out["metrics"]) >= {"qps", "p50_ms", "p95_ms", "setup_s"}
    assert list(out)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())


def _table(col, pool_size):
    return data.Dataset(vectors=np.zeros((len(col), 2), np.float32),
                        numeric={"id": col},
                        pool=np.zeros((pool_size, 2), np.float32))


def test_schedule_gives_every_seed_the_same_work():
    mix = benchpaths.TINY_MIX
    col = np.random.default_rng(0).permutation(1000).astype(np.float32)
    a = traffic.schedule(mix, _table(col, 300), 1, 5.0)
    b = traffic.schedule(mix, _table(col, 300), 2 ** 31 + 5, 5.0)
    assert len(a) == len(b) == traffic.count(mix, 5.0) == 100
    assert [x.t for x in a] != [x.t for x in b]
    assert sorted(x.archetype for x in a) == sorted(x.archetype for x in b)
    # the gaps are one fixed set, reordered: all but one are shared
    ga = np.round(np.diff([x.t for x in a]), 9)
    gb = np.round(np.diff([x.t for x in b]), 9)
    assert len(set(ga.tolist()) & set(gb.tolist())) >= len(ga) - 1
    assert 0.0 == a[0].t and max(x.t for x in a) < 5.0
    assert len({x.pool_idx for x in a}) == len(a)
    lo = [x.filter[1]["lo"] for x in a if x.filter]
    assert lo and (col >= lo[0]).sum() == 100


def test_schedule_windows_and_warmup_share_no_query():
    mix = benchpaths.TINY_MIX
    col = np.arange(1000, dtype=np.float32)
    w = traffic.schedule(mix, _table(col, 300), 9, 5.0)
    u = traffic.schedule(mix, _table(col, 300), 9, 2.0, skip=len(w))
    assert not {x.pool_idx for x in w} & {x.pool_idx for x in u}
    with pytest.raises(ValueError):
        traffic.schedule(mix, _table(col, 90), 9, 5.0)


def test_peaks_refuse_an_unknown_device():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        peaks.peaks("cpu")
    t, bound = peaks.least_time(1e9, 1e9, "TPU v5 lite")
    assert bound == "memory" and t == pytest.approx(1e9 / 819e9)


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sift1m.knn10-open",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_tpu_exits_nonzero_with_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
