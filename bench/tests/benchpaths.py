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
