"""Puts the benchmark's harness and the program on ``sys.path`` for the
benchmark's own tests, and builds tiny cells for them on the CPU."""
import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CONFIG = {
    "name": "tiny", "source": "test", "rows": 1500, "dim": 16,
    "metric": "l2", "dtype": "float32", "query_pool": 300, "k": 5,
    "generator": "gaussian_mixture", "data_seed": 5, "clusters": 8, "normalize": False,
    "numeric": {"id": {"kind": "permutation"}}, "reduced": [], "assumed": [],
}
TINY_MIX = {
    "arrival": {"process": "poisson", "rate_per_s": 20.0},
    "archetypes": [
        {"name": "vk5", "share": 0.5, "k": 5, "filter": None},
        {"name": "vk5_pass10", "share": 0.5, "k": 5,
         "filter": {"column": "id", "pass_fraction": 0.1}}],
    "server": {"batch_size": 2, "max_delay_ms": 20.0},
    "warmup_s": 0.5,
}

# every predicate kind at a tiny size, over sift1m's two numeric columns:
# V.R around a second held-out vector ``q2``, alone, Or'ed with an N.R
# and And'ed with one
TINY_HYBRID_CONFIG = dict(
    TINY_CONFIG, name="tinyh", rows=3000,
    numeric={"a": {"kind": "uniform", "lo": 0.0, "hi": 100.0},
             "b": {"kind": "normal"}})
TINY_HYBRID_ARCHETYPES = [
    {"name": "vk10_vr", "share": 0.4, "k": 10,
     "filter": {"vr": {"column": "v", "around": "q2",
                       "pass_fraction": 0.01}}},
    {"name": "vk10_vr_or_nr", "share": 0.3, "k": 10,
     "filter": {"or": [
         {"vr": {"column": "v", "around": "q2", "pass_fraction": 0.001}},
         {"nr": {"column": "a", "pass_fraction": 0.01}}]}},
    {"name": "vk10_vr_and_nr", "share": 0.3, "k": 10,
     "filter": {"and": [
         {"vr": {"column": "v", "around": "q2", "pass_fraction": 0.1}},
         {"nr": {"column": "b", "pass_fraction": 0.1}}]}},
]


def tiny_hybrid_mix(rate: float = 20.0) -> dict:
    return {"arrival": {"process": "poisson", "rate_per_s": rate},
            "vectors": ["q", "q2"], "archetypes": TINY_HYBRID_ARCHETYPES,
            "server": {"batch_size": 4, "max_delay_ms": 30.0},
            "warmup_s": 0.5}


def add_cell(tmp: str, config: dict = None, mix: dict = None,
             chips: int = 1) -> str:
    """A copy of the benchmark in ``tmp`` with one more cell, ``tiny``
    under ``tiny-mix`` on ``chips``, added as new files and new entries
    only; returns its ``BENCHMARK.json``."""
    shutil.copytree(BENCH, os.path.join(tmp, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    with open(os.path.join(tmp, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(dict(config or TINY_CONFIG, name="tiny"), f)
    with open(os.path.join(tmp, "bench", "traffic", "tiny-mix.json"),
              "w") as f:
        json.dump(mix or TINY_MIX, f)
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny.tiny-mix", "config": "tiny",
                              "traffic": "tiny-mix", "chips": chips,
                              "why": "test"})
    path = os.path.join(tmp, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(spec, f)
    return path


# a deployment shaped like BigANN's filtered track, at a tiny size, made
# only of files the harness finds by name: uint8 codes, a bag of up to
# three tags a row (-1 where a row holds fewer), and each held-out
# query's own two tags as a pool column; the program has no set-valued
# column, so the tag column is added as one numeric column a slot and a
# tag filter is built as one N.E a slot under Or
TAG_GENERATOR = '''
"""uint8 codes around a mixture of centres, the named columns, and each
held-out query's own two tags (``qtags``): a frequent one (ids 0-2) and
one of the others from ``first_rare`` on."""
from harness.data import Dataset


def make(cfg, column):
    import numpy as np
    rng = np.random.default_rng(int(cfg["data_seed"]))
    rows, dim, k = int(cfg["rows"]), int(cfg["dim"]), int(cfg["clusters"])
    n = int(cfg["query_pool"])
    centres = rng.uniform(32.0, 224.0, (k, dim))

    def codes(m):
        x = centres[rng.integers(0, k, m)] + rng.normal(0.0, 16.0, (m, dim))
        return np.clip(np.rint(x), 0, 255).astype(np.uint8)

    vec, pool = codes(rows), codes(n)
    numeric = {name: column(spec, rows, rng)
               for name, spec in sorted(cfg["numeric"].items())}
    vocab = int(cfg["numeric"]["tags"]["vocabulary"])
    qtags = np.stack([rng.integers(0, 3, n),
                      rng.integers(int(cfg["first_rare"]), vocab, n)], axis=1)
    return Dataset(vectors=vec, numeric=numeric, pool=pool,
                   pool_columns={"qtags": qtags})
'''
TAG_COLUMN = '''
"""A bag of tags a row, ``(rows, slots)`` int32: ids of ``vocabulary``
words drawn with frequencies falling as 1 / (id + 1), each row holding
one to ``slots`` of them, distinct, and -1 in the slots it leaves."""


def make(spec, rows, rng):
    import numpy as np
    vocab, slots = int(spec["vocabulary"]), int(spec["slots"])
    p = 1.0 / np.arange(1, vocab + 1)
    tags = rng.choice(vocab, size=(rows, slots), p=p / p.sum())
    held = rng.integers(1, slots + 1, rows)
    tags[np.arange(slots)[None, :] >= held[:, None]] = -1
    for j in range(1, slots):
        tags[(tags[:, j:j + 1] == tags[:, :j]).any(axis=1), j] = -1
    return tags.astype(np.int32)


def add(table, name, values):
    for j in range(values.shape[1]):
        table.add_numeric(f"{name}_{j}", values[:, j])
'''
TAG_PREDICATE = '''
"""A row carries a tag: the request's query's own tag in slot ``slot``
of the pool column ``pool``, looked up in the set-valued ``column``."""


def bind(body, ctx):
    own = ctx.table.pool_columns[body["pool"]][ctx.vectors["q"]]
    return {"column": body["column"], "tag": int(own[int(body["slot"])])}


def build(params, ctx):
    from repro.core import query as Q
    name, tag = params["column"], float(params["tag"])
    slots = ctx.ds.numeric[name].shape[1]
    return Q.Or.of(*[Q.NE(f"{name}_{j}", tag) for j in range(slots)])


def mask(params, view, band, sub):
    c = view.column(params["column"])
    return (c[None, :, :] == params["tag"][:, None, None]).any(axis=-1)
'''
TAG_KINDS = {("generators", "tag_codes"): TAG_GENERATOR,
             ("columns", "tag_bag"): TAG_COLUMN,
             ("predicates", "tag"): TAG_PREDICATE}
TAG_CONFIG = dict(
    TINY_CONFIG, name="tinyt", generator="tag_codes", first_rare=10,
    numeric={"tags": {"kind": "tag_bag", "vocabulary": 30, "slots": 3}})


def tag(slot: int) -> dict:
    return {"tag": {"column": "tags", "pool": "qtags", "slot": slot}}


# one frequent tag; both of the query's tags; one infrequent tag, which
# fewer rows than k carry
TAG_MIX = dict(TINY_MIX, archetypes=[
    {"name": "vk5_tag", "share": 0.4, "k": 5, "filter": tag(0)},
    {"name": "vk5_tag_and_tag", "share": 0.3, "k": 5,
     "filter": {"and": [tag(0), tag(1)]}},
    {"name": "vk100_rare_tag", "share": 0.3, "k": 100, "filter": tag(1)}])


def write_kinds(bench_dir: str, files: dict) -> None:
    """Writes ``{(folder, name): source}`` as ``<bench_dir>/<folder>/
    <name>.py``."""
    for (folder, name), text in files.items():
        os.makedirs(os.path.join(bench_dir, folder), exist_ok=True)
        with open(os.path.join(bench_dir, folder, f"{name}.py"), "w") as f:
            f.write(text.lstrip("\n"))
