"""The comparison that decides ``correct``: the plain reference agrees
with the served path at a tiny size, a wrong row fails it, the bfloat16
control fails it, and a run with the timed path broken underneath comes
out not correct. On a set-valued tag column: the reference agrees with a
numpy brute force past its padding, a uint8 table with its float32 copy,
and a served row that lacks the tag is a wrong row."""
import numpy as np
import pytest

import benchpaths

from harness import data, predicates, reference, runner, traffic
from harness.kinds import DEFAULT, Kinds

CFG = dict(benchpaths.TINY_CONFIG)


def _served(ds, specs):
    """The tiny table served through ``RetrievalServer``, in original
    row ids."""
    from repro.core.lake import MMOTable
    from repro.core.platform import MQRLD
    table = MMOTable("tiny").add_vector("v", ds.vectors)
    for name, col in ds.numeric.items():
        table.add_numeric(name, col)
    p = MQRLD(table, seed=3)
    p.prepare()
    srv = runner.server(runner.Setup(
        cell=type("C", (), {"mix": benchpaths.TINY_MIX, "chips": 1})(),
        seed=0, ds=ds, platform=p, phase_s={}, compiles=None))
    res = srv.serve([runner.request(s, ds) for s in specs])
    ids = p.view().row_ids
    return [np.asarray(ids)[r.rows] for r in res]


@pytest.fixture(scope="module")
def served():
    ds = data.make(CFG, DEFAULT)
    specs = traffic.schedule(benchpaths.TINY_MIX, ds, 5, 1.0)
    return ds, specs, _served(ds, specs)


def _numbers(ds, specs, got):
    return runner.check(ds, specs, got)


def _passes(numbers):
    lim = runner.limits()
    return all(numbers[k] <= lim[k] for k in lim)


def test_reference_agrees_with_served_path(served):
    ds, specs, got = served
    n = _numbers(ds, specs, got)
    assert n["unanswered"] == 0 and n["wrong_rows"] == 0
    assert n["kth_excess"] < 1e-6 and n["order_excess"] < 1e-6
    assert _passes(n)


@pytest.mark.parametrize("data_seed", [6, 2 ** 31 + 7])
def test_reference_agrees_on_other_tables(data_seed):
    # a configuration's table is fixed by its data_seed; the served path
    # must agree with the reference on tables other than the committed one
    ds = data.make(dict(CFG, data_seed=data_seed), DEFAULT)
    specs = traffic.schedule(benchpaths.TINY_MIX, ds, data_seed + 1, 1.0)
    n = _numbers(ds, specs, _served(ds, specs))
    assert n["unanswered"] == 0 and n["wrong_rows"] == 0
    assert _passes(n), n


@pytest.mark.parametrize("fault", ["far_row", "filtered_out_row",
                                   "repeated_row", "swapped_order",
                                   "missing_answer"])
def test_a_wrong_answer_fails(served, fault):
    ds, specs, got = served
    got = [g.copy() for g in got]
    i = next(j for j, s in enumerate(specs) if s.filter is not None)
    name, lo = specs[i].filter[1]["column"], specs[i].filter[1]["lo"]
    if fault == "far_row":
        q = ds.pool[specs[0].pool_idx]
        far = int(np.argmax(((ds.vectors - q) ** 2).sum(1)))
        got[0][-1] = far
    elif fault == "filtered_out_row":
        got[i][0] = int(np.nonzero(ds.numeric[name] < lo)[0][0])
    elif fault == "repeated_row":
        got[0][1] = got[0][0]
    elif fault == "swapped_order":
        got[0][[0, -1]] = got[0][[-1, 0]]
    else:
        got[0] = None
    assert not _passes(_numbers(ds, specs, got))


def test_bf16_control_fails():
    cfg = dict(CFG, rows=20000, dim=32, clusters=16, query_pool=400)
    ds = data.make(cfg, DEFAULT)
    mix = dict(benchpaths.TINY_MIX, arrival={"process": "poisson",
                                             "rate_per_s": 100.0})
    specs = traffic.schedule(mix, ds, 1, 2.0)
    n = runner.control(ds, specs, runner.band())
    assert not _passes(n), n


def _alter(rows):
    out = [np.asarray(r).copy() for r in rows]
    out[0][0] = out[0][0] + 1
    return out


def _drop_half(rows):
    h = len(rows) // 2
    return list(rows[:h]) + [np.empty(0, np.int64)] * (len(rows) - h)


@pytest.mark.parametrize("fault", [_alter, _drop_half],
                         ids=["answer_altered", "half_the_batch_dropped"])
def test_run_with_broken_timed_path_is_not_correct(tmp_path, monkeypatch,
                                                   fault):
    import jax
    from harness import cells
    from repro.core import planner
    orig = planner.ExecutablePlan.execute

    def broken(self, *a, **k):
        rows, stats = orig(self, *a, **k)
        return fault(rows), stats

    monkeypatch.setattr(planner.ExecutablePlan, "execute", broken)
    cell = cells.resolve(benchpaths.add_cell(str(tmp_path)), "tiny.tiny-mix")
    out = runner.run_cell(cell, 11, 1.0, trace=False, t_start=0.0,
                          device=jax.devices()[0], log=lambda m: None)
    assert out["correct"] is False
    assert out["checks"]["wrong_rows"]["value"] > 0 or \
        out["checks"]["kth_excess"]["value"] > \
        out["checks"]["kth_excess"]["limit"]


@pytest.fixture(scope="module")
def tag_kinds(tmp_path_factory):
    bench = tmp_path_factory.mktemp("tagkinds")
    benchpaths.write_kinds(str(bench), benchpaths.TAG_KINDS)
    return Kinds(str(bench))


def _tag_specs(ds, k, slot=0):
    """One tag request per held-out query: its own tag in ``slot``."""
    return [traffic.Spec(t=0.0, pool_idx=i, k=k, archetype="tag",
                         filter=predicates.bind(benchpaths.tag(slot), ds,
                                                {"q": i}))
            for i in range(len(ds.pool))]


def _brute(ds, spec):
    """Float64 distances of the rows carrying the request's tag, and
    those rows, nearest first."""
    tags = ds.numeric["tags"]
    rows = np.nonzero((tags == spec.filter[1]["tag"]).any(axis=1))[0]
    d = reference._d2(ds.vectors, rows, ds.pool[spec.pool_idx])
    order = np.argsort(d, kind="stable")
    return d[order], rows[order]


@pytest.mark.parametrize("rows", [1000, reference._CHUNK + 1000])
def test_tag_column_reference_agrees_with_numpy(tag_kinds, rows):
    """Tag 0 is in use, by fewer rows than k: the reference's padded
    rows, zero vectors holding tag 0 in every slot, would enter its
    answers unless ``valid`` held them out."""
    rng = np.random.default_rng(rows)
    dim, k = 8, 10
    tags = rng.integers(1, 12, (rows, 3)).astype(np.int32)
    tags[rng.random((rows, 3)) < 0.4] = -1
    tags[:, 0] = np.where(tags[:, 0] < 0, 1, tags[:, 0])
    tags[rng.choice(rows, 3, replace=False), 2] = 0
    qtags = np.stack([np.arange(40) % 4, np.full(40, 1)], axis=1)
    ds = data.Dataset(
        vectors=rng.standard_normal((rows, dim), dtype=np.float32),
        numeric={"tags": tags},
        pool=rng.standard_normal((40, dim), dtype=np.float32) * 0.1,
        pool_columns={"qtags": qtags}, kinds=tag_kinds)
    specs = _tag_specs(ds, k)
    ref = reference.Reference(ds)
    got = ref.topk(specs, extra=0)
    cands = ref.candidates(specs, extra=runner.EXTRA, band=runner.band())
    for s, g, c in zip(specs, got, cands):
        d, want = _brute(ds, s)
        assert c.n_loose == c.n_strict == len(want)
        assert np.array_equal(g, want[:k]), (s.filter, g, want[:k])
    assert any(len(g) < k for g in got)
    n = reference.compare(specs, got, cands, ds, runner.band())
    assert n == {"unanswered": 0, "wrong_rows": 0, "kth_excess": 0.0,
                 "order_excess": 0.0}


@pytest.fixture(scope="module")
def uint8_table(tag_kinds):
    cfg = dict(benchpaths.TAG_CONFIG, rows=3000, dim=192, query_pool=60)

    def column(spec, rows, rng):
        return tag_kinds.module("columns", spec["kind"]).make(spec, rows, rng)

    raw = tag_kinds.module("generators", "tag_codes").make(cfg, column)
    return raw, data.make(cfg, tag_kinds)


def test_uint8_table_agrees_with_its_float32_copy(uint8_table):
    """The source's uint8 codes, held as float32 by ``data.make``, give
    the reference the answers of the same values made float32 by hand,
    and the nearest rows by a float64 brute force."""
    raw, ds = uint8_table
    assert raw.vectors.dtype == np.uint8 and raw.pool.dtype == np.uint8
    assert ds.vectors.dtype == np.float32 and ds.pool.dtype == np.float32
    copy = data.Dataset(vectors=raw.vectors.astype(np.float32),
                        numeric=raw.numeric,
                        pool=raw.pool.astype(np.float32),
                        pool_columns=raw.pool_columns, kinds=ds.kinds)
    specs = _tag_specs(ds, 10)
    specs += [traffic.Spec(t=0.0, pool_idx=i, k=10, archetype="vk",
                           filter=None) for i in range(len(ds.pool))]
    got = reference.Reference(ds).topk(specs, extra=0)
    want = reference.Reference(copy).topk(specs, extra=0)
    assert all(np.array_equal(g, w) for g, w in zip(got, want))
    every = np.arange(len(ds.vectors))
    for s, g in zip(specs, got):
        q = ds.pool[s.pool_idx]
        d = reference._d2(ds.vectors, g, q)
        full = np.sort(reference._d2(ds.vectors, every, q)) \
            if s.filter is None else _brute(ds, s)[0]
        assert np.array_equal(np.sort(d), full[:s.k])
    n = runner.check(ds, specs, want)
    assert n["wrong_rows"] == 0 and n["kth_excess"] == 0.0, n


def test_served_row_lacking_the_tag_is_a_wrong_row(uint8_table):
    _, ds = uint8_table
    specs = _tag_specs(ds, 10)
    served = reference.Reference(ds).topk(specs, extra=0)
    assert runner.check(ds, specs, served)["wrong_rows"] == 0
    i = next(j for j, g in enumerate(served) if len(g) == specs[j].k)
    tag = specs[i].filter[1]["tag"]
    lacking = np.nonzero(~(ds.numeric["tags"] == tag).any(axis=1))[0]
    d = reference._d2(ds.vectors, lacking, ds.pool[specs[i].pool_idx])
    bad = [g.copy() for g in served]
    bad[i][-1] = lacking[np.argmin(d)]       # the nearest row without it
    n = runner.check(ds, specs, bad)
    assert n["wrong_rows"] >= 1, n
