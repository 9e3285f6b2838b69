"""Find a cell's knee, or read many seeds, after one set-up.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        [--rates 20,30,40] [--seeds 11,12,13] [--window-ms auto|<ms>] \
        [--data-seed <n>] [--dump DIR]

After the cell's set-up it times full micro-batches of the cell's traffic
(``batch_size`` requests of one plan signature, run at once) and prints
their service time. Then it runs one window per seed and rate: ``--rates``
defaults to the cell's own rate and ``--seeds`` to ``--seed``. For each
window it prints the completed rate, the latency median and 95th
percentile, batch fill, chunk service time, the backlog left at the
window's close, compiles inside the window and the compared numbers, one
JSON line per window. The highest rate whose completed rate keeps up
with the offered one without a growing backlog is the knee; a cell's
fixed rate is set below it in its traffic file.

``--window-ms`` sets the batching window of the windows (``auto``: the
median full-batch service time just measured; default: the mix's own).
``--data-seed`` replaces the configuration's ``data_seed``, to read the
comparison on another table. The windows reuse the held-out pool from
the start for each window (the set-up's warm-up takes queries after the
longest window). With ``--dump DIR`` the first window is traced and the
raw trace (plane and line names, and the first events of each device
line with all their stats) is written to ``DIR/trace_raw.json`` for
reading by hand. Needs a TPU, like ``run.py``.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import run  # noqa: E402


def dump_raw(logdir: str, path: str, per_line: int = 40) -> None:
    import glob
    from jax.profiler import ProfileData
    pb = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                "*.xplane.pb"))[0]
    out = []
    for plane in ProfileData.from_file(pb).planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            lines.append({"name": line.name, "events": len(evs), "first": [
                {"name": e.name, "start_ns": e.start_ns,
                 "dur_ns": e.duration_ns,
                 "stats": [[k, str(v)[:2000]] for k, v in e.stats]}
                for e in evs[:per_line]]})
        out.append({"plane": plane.name, "lines": lines})
    with open(path, "w") as f:
        json.dump(out, f)


def full_batches(st, specs, reps: int = 3) -> dict:
    """Seconds of one full micro-batch per plan signature, ``reps`` times
    each, every batch with queries of its own."""
    from harness import runner
    srv = runner.server(st)
    size = int(st.cell.mix["server"]["batch_size"])
    by_sig: dict = {}
    for s in specs:
        sig = srv.signature(runner.request(s, st.ds))
        by_sig.setdefault(sig, []).append(s)
    out = {}
    for sig, group in by_sig.items():
        times = []
        for r in range(reps):
            batch = group[r * size:(r + 1) * size]
            if len(batch) < size:
                break
            reqs = [runner.request(s, st.ds) for s in batch]
            t = time.monotonic()    # a full group runs inside submit()
            futs = [srv.submit(q) for q in reqs]
            srv.flush()
            for f in futs:
                f.result()
            times.append(time.monotonic() - t)
        out[sig] = times
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default=None)
    ap.add_argument("--seeds", default=None)
    ap.add_argument("--window-ms", default=None)
    ap.add_argument("--data-seed", type=int, default=None)
    ap.add_argument("--dump", default=None)
    args = ap.parse_args()
    from harness import (cells, drive, predicates, runner,
                         trace as tracemod, traffic)
    cell = cells.resolve(os.path.join(run.ROOT, "BENCHMARK.json"),
                         args.workload)
    if args.data_seed is not None:
        cell = dataclasses.replace(
            cell, config=dict(cell.config, data_seed=args.data_seed))
    run.enable_compile_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        run.fail(f"no TPU: JAX's first device is {dev.platform!r}", 3)
    rates = [float(r) for r in args.rates.split(",")] if args.rates \
        else [float(cell.mix["arrival"]["rate_per_s"])]
    seeds = [int(s) for s in args.seeds.split(",")] if args.seeds \
        else [args.seed]
    mixes = [dict(cell.mix, arrival=dict(cell.mix["arrival"], rate_per_s=r))
             for r in rates]
    longest = max(traffic.pool_needed(m, args.seconds) for m in mixes)
    st = runner.setup(cell, args.seed, longest, dev, run.log)
    run.log(f"setup_s={time.monotonic() - T_START:.3f}")

    # up to a thousand requests of the mix: three full batches per signature
    per = len(st.ds.pool) // len(predicates.vectors_of(cell.mix))
    fb = full_batches(st, traffic.schedule(
        cell.mix, st.ds, args.seed + 1,
        min(1000, per) / float(cell.mix["arrival"]["rate_per_s"])))
    allfb = sorted(t for ts in fb.values() for t in ts)
    fb_ms = drive.quantile(allfb, 0.5) * 1e3 if allfb else None
    print(json.dumps({"full_batch_ms": {k: [t * 1e3 for t in v]
                                        for k, v in fb.items()},
                      "full_batch_ms_p50": fb_ms}), flush=True)
    if args.window_ms == "auto":
        win_ms = fb_ms
    elif args.window_ms is not None:
        win_ms = float(args.window_ms)
    else:
        win_ms = float(cell.mix["server"]["max_delay_ms"])
    server = dict(cell.mix["server"], max_delay_ms=win_ms)

    first = True
    for seed in seeds:
        for rate, mix in zip(rates, mixes):
            specs = traffic.schedule(mix, st.ds, seed, args.seconds)
            st.cell = dataclasses.replace(st.cell, mix=dict(mix,
                                                            server=server))
            tdir = tempfile.mkdtemp(prefix="bench-sweep-") \
                if (args.dump and first) else None
            before = dict(st.compiles.lowered)
            n_names = len(st.compiles.names.get("window", []))
            win = runner.window(st, specs, args.seconds, trace_dir=tdir)
            sm = runner.summary(st, win)
            end = win.t0 + win.seconds
            backlog = sum(1 for s, r in zip(win.specs, win.results)
                          if r is None or win.t0 + s.t + r.latency_s > end)
            t = time.monotonic()
            numbers = runner.check(st.ds, win.specs, sm["served"])
            chunk = [c.seconds / c.batches for c in win.calls
                     if c.kind != "flush"]
            line = {"seed": seed, "offered": rate, "window_ms": win_ms,
                    "qps": sm["qps"], "p50_ms": sm["p50_ms"],
                    "p95_ms": sm["p95_ms"],
                    "attempted": len(win.specs),
                    "backlog_at_close": backlog,
                    "batch_fill": win.stats["served"]
                    / max(1, win.stats["batches"]),
                    "chunk_ms_p50": drive.quantile(chunk, 0.5) * 1e3
                    if chunk else None,
                    "lateness_p99_ms": sm["lateness_p99_ms"],
                    "compiles_in_window": st.compiles.lowered.get("window", 0)
                    - before.get("window", 0),
                    "programs": st.compiles.names.get("window",
                                                      [])[n_names:],
                    "reference_s": time.monotonic() - t,
                    "error": win.error, "checks": numbers}
            if tdir:
                os.makedirs(args.dump, exist_ok=True)
                dump_raw(tdir, os.path.join(args.dump, "trace_raw.json"))
                tr = tracemod.load(tdir)
                line["busy_s"] = tracemod.busy_s(tr)
                line["window_s"] = tr.window_s
                line["top_ops"] = tracemod.top_ops(tr)
                line["idle_gaps"] = tracemod.idle_gaps(tr)
            first = False
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
