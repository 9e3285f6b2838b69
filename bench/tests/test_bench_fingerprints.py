"""What must not move when the harness changes: the first two cells'
tables and pools, their schedules and built requests on three seeds, and
the reference's candidates, control answers and compared numbers, bit
for bit against the fingerprints in ``fingerprints.json``."""
import hashlib
import json
import os

import numpy as np
import pytest

import benchpaths
from benchpaths import BENCH, ROOT

from harness import data, reference, runner, traffic
from harness.kinds import DEFAULT

with open(os.path.join(os.path.dirname(__file__), "fingerprints.json")) as f:
    FP = json.load(f)
SEEDS = FP["seeds"]


def _load(path):
    with open(path) as f:
        return json.load(f)


SPEC = _load(os.path.join(ROOT, "BENCHMARK.json"))
CONFIGS = {c["name"]: _load(os.path.join(ROOT, c["file"]))
           for c in SPEC["configs"]}
CELLS = {w["name"]: w for w in SPEC["workloads"]}


def _mix(name):
    return _load(os.path.join(BENCH, "traffic", f"{name}.json"))


def _h(b) -> str:
    return hashlib.sha256(b).hexdigest()


_TABLE = {}


def _dataset(config: str) -> data.Dataset:
    """One full-size table at a time: the last one asked for."""
    if config not in _TABLE:
        _TABLE.clear()
        _TABLE[config] = data.make(CONFIGS[config], DEFAULT)
    return _TABLE[config]


@pytest.fixture(scope="module", autouse=True)
def _free_tables():
    yield
    _TABLE.clear()


def _spec_line(s) -> str:
    f = None
    if s.filter is not None:
        kind, p = s.filter
        assert kind == "nr"
        f = [p["column"], float(p["lo"]).hex(), float(p["hi"]).hex()]
    return json.dumps([float(s.t).hex(), s.pool_idx, s.k, s.archetype, f])


def _request_line(r) -> str:
    from repro.core import query as Q
    vk = Q.VK.of(r.attr, (), int(r.k))
    q = vk if r.predicate is None else Q.And.of(r.predicate, vk)
    return json.dumps([np.asarray(r.tokens).tolist(), r.attr, int(r.k),
                       repr(r.predicate), Q.signature(Q.normalize(q))])


@pytest.mark.parametrize("config", sorted(FP["tables"]))
def test_table_and_pool_match_the_first_harness(config):
    ds = _dataset(config)
    got = {"vectors": _h(memoryview(ds.vectors)),
           "pool": _h(memoryview(ds.pool))}
    for name, col in ds.numeric.items():
        got["numeric." + name] = _h(memoryview(col))
    assert got == FP["tables"][config]


@pytest.mark.parametrize("key", sorted(FP["schedules"]))
def test_schedule_and_requests_match_the_first_harness(key):
    cell, seed = key.rsplit(":", 1)
    w = CELLS[cell]
    ds = _dataset(w["config"])
    mix = _mix(w["traffic"])
    win = traffic.schedule(mix, ds, int(seed), 30.0)
    warm = traffic.schedule(mix, ds, int(seed), float(mix["warmup_s"]),
                            skip=traffic.pool_needed(mix, 30.0))
    lines = [_spec_line(s) for s in win] + ["--"] + \
        [_spec_line(s) for s in warm]
    reqs = [_request_line(runner.request(s, ds)) for s in win + warm]
    want = FP["schedules"][key]
    assert [len(win), len(warm)] == want["n"]
    assert _h("\n".join(lines).encode()) == want["specs"]
    assert _h("\n".join(reqs).encode()) == want["requests"]
    assert sorted({json.loads(x)[4] for x in reqs}) == want["signatures"]


def _case(name):
    if name == "tiny":
        return benchpaths.TINY_CONFIG, benchpaths.TINY_MIX
    w = [c for c in CELLS.values() if c["config"] == name][0]
    return dict(CONFIGS[name], rows=40000), _mix(w["traffic"])


@pytest.mark.parametrize("key", sorted(FP["candidates"]))
def test_reference_matches_the_first_harness(key):
    name, seed = key.rsplit(":", 1)
    cfg, mix = _case(name)
    ds = data.make(cfg, DEFAULT)
    specs = traffic.schedule(mix, ds, int(seed), 3.0)
    ref = reference.Reference(ds)
    cands = ref.candidates(specs, extra=runner.EXTRA, band=runner.band())
    low = ref.topk(specs, extra=0, precision="bf16")
    exact = ref.topk(specs, extra=0)

    def joined(rows):
        return _h(b"".join(np.asarray(r, np.int64).tobytes() + b"|"
                           for r in rows))

    def hexed(numbers):
        return {k: float(v).hex() for k, v in numbers.items()}

    want = FP["candidates"][key]
    assert len(specs) == want["n"]
    assert joined(c.rows for c in cands) == want["candidates"]
    assert joined(low) == want["bf16"]
    b = runner.band()
    assert hexed(reference.compare(specs, low, cands, ds, b)) == \
        want["numbers_bf16"]
    assert hexed(reference.compare(specs, exact, cands, ds, b)) == \
        want["numbers_exact"]
