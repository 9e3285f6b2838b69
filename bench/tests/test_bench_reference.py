"""The comparison that decides ``correct``: the plain reference agrees
with the served path at a tiny size, a wrong row fails it, the bfloat16
control fails it, and a run with the timed path broken underneath comes
out not correct."""
import numpy as np
import pytest

import benchpaths  # noqa: F401

from harness import data, runner, traffic
from harness.kinds import DEFAULT

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
