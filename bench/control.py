"""The control of the comparison that decides ``correct``.

    python bench/control.py --workload <cell> --seeds 11,12,13 --seconds <s>

For each seed it makes the cell's table and the requests of a run's window
(``--seconds`` at the cell's rate), answers them with the benchmark's
reference computed in bfloat16 (the next precision below the
configuration's fp32) in the program's place, and prints the compared
numbers, one JSON line per seed. The control must fail at least one
limit in ``bench/limits.json``; its readings set those limits' upper
ends (``PERF.md``). Where the mix has a V.R node, each line also holds
the numbers at each V.R band of ``BANDS`` (``vr_band`` in the limits
file): the smallest band at which the control passes is the band's
upper reading. The benchmark's own runs do not run it. Needs a TPU,
like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import run  # noqa: E402

BANDS = (0.0, 1e-6, 1e-5, 1e-4, 1e-3)


def _has_vr(desc) -> bool:
    """Whether a mix's archetypes, or a filter description, hold a V.R
    node."""
    if isinstance(desc, dict):
        return "vr" in desc or any(_has_vr(v) for v in desc.values())
    if isinstance(desc, list):
        return any(_has_vr(v) for v in desc)
    return False


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    from harness import cells, data, runner, traffic
    cell = cells.resolve(os.path.join(run.ROOT, "BENCHMARK.json"),
                         args.workload)
    run.enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        run.fail("no TPU", 3)
    lim = runner.limits()
    vr = _has_vr(cell.mix["archetypes"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.monotonic()
        ds = data.make(cell.config, cell.kinds)
        specs = traffic.schedule(cell.mix, ds, seed, args.seconds)
        numbers = runner.control(ds, specs, runner.band())
        line = {"seed": seed, "requests": len(specs), "numbers": numbers,
                "fails": [k for k in lim if numbers[k] > lim[k]]}
        if vr:
            line["bands"] = {str(b): runner.control(ds, specs, b)
                             for b in BANDS}
        line["seconds"] = time.monotonic() - t
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
