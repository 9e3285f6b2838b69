"""Chip benchmark of MQRLD's hybrid retrieval serving path.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` in this one process, on the machine
it is started on: makes the configuration's table and held-out queries
from the seed, builds the platform with the program's default settings,
warms the cell's shapes, offers the cell's traffic open-loop to
``RetrievalServer`` for ``--seconds`` of wall time, then checks every
served answer against the benchmark's own reference. The last line of
stdout is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``; the
compared numbers with their limits come last, under ``checks``, and are
also the last lines of stderr). ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiler
trace of the window.

Exits non-zero, printing no result, when JAX's first device is not a TPU
or there are fewer devices than the cell asks for, and when the program
(``src/repro``) is not beside this directory.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
CACHE = os.path.join(ROOT, ".jax_cache")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def fail(msg: str, code: int = 2) -> None:
    log(f"bench: {msg}")
    sys.exit(code)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the
    checkout (the path is part of the cache key), unless
    ``JAX_COMPILATION_CACHE_DIR`` names one; every program is cached,
    however quickly it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"no program at {SRC}/repro: run from a checkout")
    sys.path[:0] = [BENCH, SRC]
    from harness import cells, runner
    try:
        cell = cells.resolve(os.path.join(ROOT, "BENCHMARK.json"),
                             args.workload)
    except (KeyError, FileNotFoundError) as e:
        fail(str(e))
    cache = enable_compile_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX's first device is {devs[0].platform!r}", 3)
    if len(devs) < cell.chips:
        fail(f"{cell.name} needs {cell.chips} chips, found {len(devs)}", 3)
    log(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)} jax={jax.__version__} compile_cache={cache}")
    out = runner.run_cell(cell, args.seed, args.seconds,
                          trace=bool(args.trace), t_start=T_START,
                          device=devs[0], log=log)
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
