"""Filters as trees of predicate kinds, the V.R band, and cells made of
added files, on the CPU at a tiny size: each kind's reference mask
against the program's own brute-force oracle, a tiny hybrid window, a
served row just outside the band, the bfloat16 control, a generator,
column kind and predicate kind written only into a copy of the
benchmark (plain ones, and a tag bag with the queries' own tags and
uint8 codes), and a four-chip cell served sharded."""
import dataclasses
import filecmp
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import benchpaths
from benchpaths import BENCH

from harness import cells, data, predicates, reference, runner, traffic
from harness.kinds import DEFAULT

VR = {"column": "v", "around": "q2"}
DESCS = {
    "nr": {"nr": {"column": "a", "pass_fraction": 0.3}},
    "vr": {"vr": dict(VR, pass_fraction=0.05)},
    "and": {"and": [{"vr": dict(VR, pass_fraction=0.2)},
                    {"nr": {"column": "b", "pass_fraction": 0.4}}]},
    "or": {"or": [{"vr": dict(VR, pass_fraction=0.01)},
                  {"nr": {"column": "a", "pass_fraction": 0.1}}]},
}


def _random_table(seed):
    rng = np.random.default_rng(seed)
    rows, dim = 2000, 8
    return data.Dataset(
        vectors=rng.standard_normal((rows, dim), dtype=np.float32),
        numeric={"a": rng.uniform(0, 100, rows).astype(np.float32),
                 "b": rng.standard_normal(rows, dtype=np.float32)},
        pool=rng.standard_normal((50, dim), dtype=np.float32))


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
@pytest.mark.parametrize("kind", sorted(DESCS))
def test_reference_mask_agrees_with_bruteforce(kind, seed):
    from repro.core import query as Q
    from repro.core.lake import MMOTable
    ds = _random_table(seed)
    table = MMOTable("t").add_vector("v", ds.vectors)
    for name, col in ds.numeric.items():
        table.add_numeric(name, col)
    ref = reference.Reference(ds)
    device = reference._DeviceView(ref.x, ref.xx, ref.cols, ref.pool, False)
    host = reference._HostView(ds, np.arange(len(ds.vectors)))
    n = len(ds.vectors)
    for i in range(5):
        node = predicates.bind(DESCS[kind], ds, {"q": 2 * i, "q2": 2 * i + 1})
        want = np.zeros(n, bool)
        want[Q.execute_bruteforce(table, predicates.build(node, ds))] = True
        assert 0 < want.sum() < n
        batched = predicates.stack([node])
        for view in (host, device):
            def m(band):
                return np.asarray(predicates.mask(batched, view, band,
                                                  ds.kinds))[0][:n]
            inner, outer, exact = m(-1e-5), m(1e-5), m(0.0)
            assert not (inner & ~want).any() and not (want & ~outer).any()
            assert (exact != want).sum() <= (outer & ~inner).sum()
            if kind == "nr":
                assert (exact == want).all()


def _hybrid_cell(tmp, rate=20.0):
    return cells.resolve(benchpaths.add_cell(
        str(tmp), benchpaths.TINY_HYBRID_CONFIG,
        benchpaths.tiny_hybrid_mix(rate)), "tiny.tiny-mix")


def test_hybrid_schedule_draws_two_vectors_per_request():
    ds = data.make(benchpaths.TINY_HYBRID_CONFIG, DEFAULT)
    mix = benchpaths.tiny_hybrid_mix()
    a = traffic.schedule(mix, ds, 1, 5.0)
    b = traffic.schedule(mix, ds, 2 ** 31 + 5, 5.0)
    assert traffic.pool_needed(mix, 5.0) == 2 * len(a) == 200
    assert sorted(x.archetype for x in a) == sorted(x.archetype for x in b)
    assert {x.archetype for x in a} == {"vk10_vr", "vk10_vr_or_nr",
                                        "vk10_vr_and_nr"}

    def around(node):
        kind, p = node
        return [p["around"]] if kind == "vr" else \
            [] if kind == "nr" else [j for c in p for j in around(c)]

    q = [x.pool_idx for x in a]
    q2 = [j for x in a for j in around(x.filter)]
    assert len(q2) == len(a) and len(set(q) | set(q2)) == 2 * len(a)
    # every request of an archetype gets the same radius
    radii = {}
    for x in a:
        kind, p = x.filter
        r = p["radius"] if kind == "vr" else p[0][1]["radius"]
        radii.setdefault(x.archetype, set()).add(r)
    assert all(len(v) == 1 for v in radii.values())
    r = {k: v.pop() for k, v in radii.items()}
    assert r["vk10_vr_and_nr"] > r["vk10_vr"] > r["vk10_vr_or_nr"]


def test_tiny_hybrid_window_is_correct(tmp_path):
    import jax
    cell = _hybrid_cell(tmp_path)
    out = runner.run_cell(cell, 2 ** 31 + 99, 2.0, trace=False, t_start=0.0,
                          device=jax.devices()[0], log=lambda m: None)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] == 40 and out["failed"] == 0


@pytest.fixture(scope="module")
def hybrid():
    ds = data.make(benchpaths.TINY_HYBRID_CONFIG, DEFAULT)
    specs = [s for s in traffic.schedule(benchpaths.tiny_hybrid_mix(), ds,
                                         7, 3.0)
             if s.archetype == "vk10_vr"]
    served = reference.Reference(ds).topk(specs, extra=0)
    return ds, specs, served


@pytest.mark.parametrize("where", ["inside", "outside"])
def test_served_row_against_the_band(hybrid, where):
    """The served row farthest from the V.R centre, with the radius moved
    to just inside it (the row lies within the band) or to twice the
    band inside it (the row is outside the band)."""
    ds, specs, served = hybrid
    band = runner.band()
    i = next(j for j, s in enumerate(specs) if len(served[j]) == s.k)
    kind, p = specs[i].filter
    rows = served[i]
    d = np.sqrt(reference._d2(ds.vectors, rows, ds.pool[p["around"]]))
    far = float(d.max())
    r = far / (1 + band / 2) if where == "inside" else far / (1 + 2 * band)
    moved = dataclasses.replace(specs[i], filter=(kind, dict(p, radius=r)))
    n = runner.check(ds, [moved], [rows])
    if where == "inside":
        assert n["wrong_rows"] == 0 and n["kth_excess"] == 0.0, n
    else:
        assert n["wrong_rows"] >= 1, n


def test_bf16_control_fails_on_hybrid():
    cfg = dict(benchpaths.TINY_HYBRID_CONFIG, rows=20000, dim=32,
               clusters=16, query_pool=500)
    ds = data.make(cfg, DEFAULT)
    specs = traffic.schedule(benchpaths.tiny_hybrid_mix(100.0), ds, 1, 2.0)
    n = runner.control(ds, specs, runner.band())
    lim = runner.limits()
    assert any(n[k] > lim[k] for k in lim), n


def _traffic(name):
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("mix,has_vr", [
    ("knn10-open", False), ("filter-open", False), ("tiny", False),
    ("tiny-hybrid", True)])
def test_control_reads_the_band_ladder_only_for_vr_mixes(mix, has_vr):
    import control
    m = {"tiny": benchpaths.TINY_MIX,
         "tiny-hybrid": benchpaths.tiny_hybrid_mix()}.get(mix) \
        or _traffic(mix)
    assert control._has_vr(m["archetypes"]) is has_vr
    assert control.BANDS[0] == 0.0 and runner.band() in control.BANDS


GENERATOR = '''
"""Vectors uniform in the unit cube; columns drawn after them."""
from harness.data import Dataset


def make(cfg, column):
    import numpy as np
    rng = np.random.default_rng(int(cfg["data_seed"]))
    rows, dim = int(cfg["rows"]), int(cfg["dim"])
    vec = rng.random((rows, dim), dtype=np.float32)
    pool = rng.random((int(cfg["query_pool"]), dim), dtype=np.float32)
    numeric = {name: column(spec, rows, rng)
               for name, spec in sorted(cfg["numeric"].items())}
    return Dataset(vectors=vec, numeric=numeric, pool=pool)
'''
COLUMN = '''
"""A column of fair coin flips, 0.0 or 1.0."""


def make(spec, rows, rng):
    import numpy as np
    return rng.integers(0, 2, rows).astype(np.float32)


def add(table, name, values):
    table.add_numeric(name, values)
'''
PREDICATE = '''
"""N.E: a column equal to a value."""


def bind(body, ctx):
    return {"column": body["column"], "value": float(body["value"])}


def build(params, ctx):
    from repro.core import query as Q
    return Q.NE(params["column"], params["value"])


def mask(params, view, band, sub):
    c = view.column(params["column"])
    return abs(c[None, :] - params["value"][:, None]) <= 1e-6
'''


def test_kinds_added_as_files_run_a_cell(tmp_path):
    import jax
    cfg = dict(benchpaths.TINY_CONFIG, generator="unit_cube",
               numeric={"h": {"kind": "coin"},
                        "id": {"kind": "permutation"}})
    eq = {"eq": {"column": "h", "value": 1.0}}
    mix = dict(benchpaths.TINY_MIX, archetypes=[
        {"name": "vk5_eq", "share": 0.5, "k": 5, "filter": eq},
        {"name": "vk5_eq_and_nr", "share": 0.5, "k": 5,
         "filter": {"and": [eq, {"nr": {"column": "id",
                                        "pass_fraction": 0.5}}]}}])
    path = benchpaths.add_cell(str(tmp_path), cfg, mix)
    benchpaths.write_kinds(str(tmp_path / "bench"), {
        ("generators", "unit_cube"): GENERATOR, ("columns", "coin"): COLUMN,
        ("predicates", "eq"): PREDICATE})
    cmp = filecmp.dircmp(os.path.join(BENCH, "harness"),
                         str(tmp_path / "bench" / "harness"),
                         ignore=["__pycache__"])
    assert not (cmp.diff_files or cmp.left_only or cmp.right_only)
    cell = cells.resolve(path, "tiny.tiny-mix")
    out = runner.run_cell(cell, 2 ** 31 + 3, 1.0, trace=False, t_start=0.0,
                          device=jax.devices()[0], log=lambda m: None)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] == 20 and out["failed"] == 0
    ds = data.make(cell.config, cell.kinds)
    assert set(np.unique(ds.numeric["h"])) == {0.0, 1.0}
    assert ds.vectors.min() >= 0.0 and ds.vectors.max() < 1.0


def test_set_valued_kinds_added_as_files_run_a_cell(tmp_path):
    """A bag of tags a row, each query's own tags from the pool's own
    column and uint8 codes, all from files added beside an unchanged
    harness: a window over one tag, two tags and a tag fewer rows than
    k carry is correct."""
    import jax
    mix = benchpaths.TAG_MIX
    path = benchpaths.add_cell(str(tmp_path), benchpaths.TAG_CONFIG, mix)
    benchpaths.write_kinds(str(tmp_path / "bench"), benchpaths.TAG_KINDS)
    cmp = filecmp.dircmp(os.path.join(BENCH, "harness"),
                         str(tmp_path / "bench" / "harness"),
                         ignore=["__pycache__"])
    assert not (cmp.diff_files or cmp.left_only or cmp.right_only)
    cell = cells.resolve(path, "tiny.tiny-mix")
    seed = 2 ** 31 + 17
    out = runner.run_cell(cell, seed, 1.0, trace=False, t_start=0.0,
                          device=jax.devices()[0], log=lambda m: None)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] == 20 and out["failed"] == 0

    # the generator's uint8 codes (test_bench_reference.py), as float32
    ds = data.make(cell.config, cell.kinds)
    assert ds.vectors.dtype == np.float32 and ds.pool.dtype == np.float32
    assert (ds.vectors == np.rint(ds.vectors)).all()
    assert ds.vectors.min() >= 0 and ds.vectors.max() <= 255
    tags = ds.numeric["tags"]
    assert tags.shape == (1500, 3) and tags.min() == -1
    held = (tags >= 0).sum(axis=1)
    assert 0.2 < (held < 3).mean() < 0.9 and held.min() >= 1
    assert ds.pool_columns["qtags"].shape == (300, 2)
    specs = traffic.schedule(mix, ds, seed, 1.0)
    assert {s.archetype for s in specs} == {a["name"] for a in
                                            mix["archetypes"]}
    for s in specs:
        if s.archetype == "vk100_rare_tag":
            n = (tags == s.filter[1]["tag"]).any(axis=1).sum()
            assert 0 < n < s.k


def test_one_chip_cell_passes_no_shards(monkeypatch):
    import repro.serve.engine as serve
    seen = {}

    class Recorder:
        def __init__(self, platform, embedder, **kw):
            seen.update(kw)

    monkeypatch.setattr(serve, "RetrievalServer", Recorder)
    ds = data.make(benchpaths.TINY_CONFIG, DEFAULT)
    for chips in (1, 4):
        seen.clear()
        runner.server(runner.Setup(
            cell=type("C", (), {"mix": benchpaths.TINY_MIX, "chips": chips}),
            seed=0, ds=ds, platform=None, phase_s={}, compiles=None))
        assert ("shards" in seen) == (chips > 1)
        assert seen.get("shards", 1) == chips


FOUR_CHIPS = '''
import json, sys
sys.path.insert(0, {tests!r})
import benchpaths, jax
from harness import cells, runner
cell = cells.resolve(benchpaths.add_cell({tmp!r}, chips=4), "tiny.tiny-mix")
logs = []
out = runner.run_cell(cell, 21, 1.0, trace=False, t_start=0.0,
                      device=jax.devices()[0], log=logs.append)
print(json.dumps({{"correct": out["correct"], "devices": len(jax.devices()),
                  "warmup": [m for m in logs if m.startswith("warmup")]}}))
'''


def test_four_chip_cell_serves_sharded(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FOUR_CHIPS.format(tests=os.path.dirname(__file__),
                             tmp=str(tmp_path))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    assert out["correct"] is True
    assert "shards=4 " in out["warmup"][0]


@pytest.mark.parametrize("fault", ["answer_altered", "half_the_batch_dropped"])
def test_hybrid_run_with_broken_timed_path_is_not_correct(tmp_path,
                                                          monkeypatch, fault):
    import jax
    from repro.core import planner
    from test_bench_reference import _alter, _drop_half
    broken_rows = {"answer_altered": _alter,
                   "half_the_batch_dropped": _drop_half}[fault]
    orig = planner.ExecutablePlan.execute

    def broken(self, *a, **k):
        rows, stats = orig(self, *a, **k)
        return broken_rows(rows), stats

    monkeypatch.setattr(planner.ExecutablePlan, "execute", broken)
    out = runner.run_cell(_hybrid_cell(tmp_path), 13, 1.0, trace=False,
                          t_start=0.0, device=jax.devices()[0],
                          log=lambda m: None)
    assert out["correct"] is False
    assert out["checks"]["wrong_rows"]["value"] > 0 or \
        out["checks"]["kth_excess"]["value"] > \
        out["checks"]["kth_excess"]["limit"]
