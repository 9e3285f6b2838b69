"""One run of one cell, below the check for a chip.

``run_cell`` makes the configuration's table, the traffic from the seed,
builds the platform with the program's default settings, warms exactly the cell's
shapes, drives the window, reads memory, frees the program, runs the
reference and the comparison, and reduces the trace. It returns the
contract's result line as a dict. ``bench/run.py`` calls it only on a
TPU; the tests call it on the CPU at a tiny size, and ``bench/sweep.py``
reuses its stages to find a cell's knee.
"""
from __future__ import annotations

import functools
import gc
import json
import os
import shutil
import tempfile
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

import numpy as np

from harness import data as datamod
from harness import drive as drivemod
from harness import predicates, reference, trace as tracemod, traffic
from harness.cells import Cell

EXTRA = 32          # reference candidates beyond k, for the float64 recheck
LIMITS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "limits.json")


class PoolEmbedder:
    """The client's embedding step, held out of the measurement: a
    request's first token is the index of its held-out query vector."""

    def __init__(self, pool: np.ndarray):
        self.pool = pool

    def embed(self, tokens) -> np.ndarray:
        return self.pool[np.asarray(tokens)[:, 0]]


@dataclass
class Record:
    """What a per-layer metric's reader reads (``bench/metrics``)."""
    window: drivemod.Window
    phase_s: Dict[str, float]
    device_kind: str
    trace: Optional[tracemod.Trace] = None
    served_in_trace: int = 0


@dataclass
class Setup:
    cell: Cell
    seed: int
    ds: datamod.Dataset
    platform: object
    phase_s: Dict[str, float]
    compiles: drivemod.Compiles


def request(spec: traffic.Spec, ds: datamod.Dataset):
    """The program's request for ``spec``: its filter tree built by the
    predicate kinds."""
    from repro.serve.engine import RetrievalRequest
    pred = None if spec.filter is None else predicates.build(spec.filter, ds)
    return RetrievalRequest(tokens=np.asarray([spec.pool_idx, 0], np.int32),
                            attr=reference.VECTOR, k=spec.k, predicate=pred)


def server(st: Setup):
    """A fresh server over the set-up platform; a cell on more than one
    chip is served sharded over that many."""
    from repro.serve.engine import RetrievalServer
    s = st.cell.mix["server"]
    chips = int(st.cell.chips)
    shards = {"shards": chips} if chips > 1 else {}
    return RetrievalServer(st.platform, PoolEmbedder(st.ds.pool),
                           batch_size=int(s["batch_size"]),
                           max_delay_ms=float(s["max_delay_ms"]), **shards)


def memory(dev) -> Dict[str, int]:
    st = dev.memory_stats() or {}
    return {k: int(st[k]) for k in ("bytes_in_use", "peak_bytes_in_use")
            if k in st}


def _limits_file() -> dict:
    with open(LIMITS) as f:
        return json.load(f)


def limits() -> Dict[str, float]:
    """The compared numbers' limits."""
    return {k: float(v["limit"]) for k, v in _limits_file().items()
            if isinstance(v, dict) and "limit" in v}


def band() -> float:
    """The share of a V.R radius within which rounding decides a row."""
    return float(_limits_file()["vr_band"]["band"])


def warm(srv, specs: List[traffic.Spec], batch_size: int,
         make: Callable) -> int:
    """Run each plan signature of ``specs`` once at every micro-batch
    size the server can form; returns the requests used."""
    by_sig: Dict[str, List[traffic.Spec]] = {}
    for s in specs:
        by_sig.setdefault(srv.signature(make(s)), []).append(s)
    used = 0
    for group in by_sig.values():
        for size in traffic.pow2_sizes(batch_size):
            batch = [group[(used + j) % len(group)] for j in range(size)]
            used += size
            futs = [srv.submit(make(s)) for s in batch]
            srv.flush()
            for f in futs:
                f.result()
    return used


def setup(cell: Cell, seed: int, skip: int, device,
          log: Callable[[str], None]) -> Setup:
    """Data, build and warm-up. ``skip`` pool vectors are left for the
    window; the warm-up's traffic takes the ones after them."""
    from repro.core.lake import MMOTable
    from repro.core.platform import MQRLD
    compiles = drivemod.Compiles()
    cfg, mix = cell.config, cell.mix
    t = time.monotonic()
    ds = datamod.make(cfg, cell.kinds)
    table = MMOTable(cfg["name"]).add_vector(reference.VECTOR, ds.vectors)
    for name, col in ds.numeric.items():
        kind = cfg["numeric"][name]["kind"]
        ds.kinds.module("columns", kind).add(table, name, col)
    log(f"data: rows={len(ds.vectors)} dim={ds.vectors.shape[1]} "
        f"pool={len(ds.pool)} gen_s={time.monotonic() - t:.3f}")
    platform = MQRLD(table)         # the program's default build
    t = time.monotonic()
    rep = platform.prepare()
    phase_s = {k: float(v) for k, v in rep.phase_s.items()}
    log(f"build: prepare_s={time.monotonic() - t:.3f} " + " ".join(
        f"{k}_s={v:.3f}" for k, v in phase_s.items()))
    st = Setup(cell=cell, seed=seed, ds=ds, platform=platform,
               phase_s=phase_s, compiles=compiles)
    # every signature at every micro-batch size, then an unmeasured
    # stretch of the cell's own traffic, so that QBS seeds settle
    warm_s = float(mix["warmup_s"])
    specs = traffic.schedule(mix, ds, seed, warm_s, skip=skip)
    t = time.monotonic()
    srv = server(st)
    make = functools.partial(request, ds=ds)
    n_sizes = warm(srv, specs, int(mix["server"]["batch_size"]), make)
    t_sizes = time.monotonic() - t
    ww = drivemod.drive(server(st), specs, make, warm_s)
    if ww.error:
        raise RuntimeError(f"warm-up stretch failed: {ww.error}")
    mem = memory(device)
    log(f"warmup: sizes_s={t_sizes:.3f} size_requests={n_sizes} "
        f"stretch_s={time.monotonic() - t - t_sizes:.3f} "
        f"stretch_served={ww.stats['served']} "
        f"lowered={compiles.lowered.get('setup', 0)} "
        f"cache_hits={compiles.hits.get('setup', 0)} "
        f"shards={srv.session.engine().shards or 1} "
        f"bytes_in_use_after_upload_and_warmup="
        f"{mem.get('bytes_in_use', 'n/a')}")
    return st


def window(st: Setup, specs: List[traffic.Spec], seconds: float, *,
           trace_dir: Optional[str] = None) -> drivemod.Window:
    """One measured window on a fresh server over the set-up platform;
    with ``trace_dir`` the profiler records the window there."""
    import jax
    srv = server(st)
    if trace_dir:
        # host spans (TraceAnnotation) but no Python function tracing,
        # whose cost would land on every harness and server call
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    st.compiles.phase = "window"
    closed = []

    def on_close():
        st.compiles.phase = "drain"
        if trace_dir and not closed:
            jax.profiler.stop_trace()
        closed.append(True)

    make = functools.partial(request, ds=st.ds)
    win = drivemod.drive(srv, specs, make, seconds,
                         spans=trace_dir is not None, on_close=on_close)
    on_close()
    st.compiles.phase = "after"
    return win


def summary(st: Setup, win: drivemod.Window) -> Dict:
    """End-to-end numbers of one window, and the served rows in the
    table's original row ids."""
    row_ids = st.platform.view().row_ids
    ok = [r for r in win.results if r is not None and not r.shed]
    end = win.t0 + win.seconds
    lat_ms = [r.latency_s * 1e3 for r in ok]
    done = sum(1 for s, r in zip(win.specs, win.results)
               if r is not None and not r.shed
               and win.t0 + s.t + r.latency_s <= end)
    in_window = [c for c in win.calls if c.start < end]
    return {
        "served": [None if (r is None or r.shed) else
                   (np.asarray(row_ids)[r.rows] if row_ids is not None
                    else np.asarray(r.rows)) for r in win.results],
        "qps": done / win.seconds,
        "p50_ms": drivemod.quantile(lat_ms, 0.5) if lat_ms else None,
        "p95_ms": drivemod.quantile(lat_ms, 0.95) if lat_ms else None,
        "completed_in_window": done,
        "chunks_in_window": sum(c.batches for c in in_window),
        "lateness_p99_ms": drivemod.quantile(win.lateness, 0.99) * 1e3
        if win.lateness else 0.0,
        "served_in_trace": sum(1 for c in win.resolved_by
                               if c is not None and win.calls[c].start < end),
    }


def check(ds: datamod.Dataset, specs, served) -> Dict[str, float]:
    """The numbers that decide ``correct``, from the reference."""
    b = band()
    ref = reference.Reference(ds)
    cands = ref.candidates(specs, extra=EXTRA, band=b)
    del ref
    return reference.compare(specs, served, cands, ds, b)


def control(ds: datamod.Dataset, specs, b: float) -> Dict[str, float]:
    """The control's numbers at the V.R band ``b``: the reference in
    bfloat16 put in the program's place, its top-k answers compared like
    served ones."""
    ref = reference.Reference(ds)
    cands = ref.candidates(specs, extra=EXTRA, band=b)
    low = ref.topk(specs, extra=0, precision="bf16")
    del ref
    return reference.compare(specs, low, cands, ds, b)


def run_cell(cell: Cell, seed: int, seconds: float, *, trace: bool,
             t_start: float, device,
             log: Callable[[str], None] = print) -> Dict:
    import jax
    st = setup(cell, seed, traffic.pool_needed(cell.mix, seconds), device,
               log)
    specs = traffic.schedule(cell.mix, st.ds, seed, seconds)
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    setup_s = time.monotonic() - t_start
    win = window(st, specs, seconds, trace_dir=tdir)
    mem = memory(device)
    sm = summary(st, win)
    c = st.compiles
    log(f"window: seconds={seconds} attempted={len(win.specs)} "
        f"completed_in_window={sm['completed_in_window']} "
        f"chunks_in_window={sm['chunks_in_window']} "
        f"lateness_p99_ms={sm['lateness_p99_ms']:.3f} "
        f"compiles_in_window={c.lowered.get('window', 0)} "
        f"cache_hits_in_window={c.hits.get('window', 0)} "
        f"programs_in_window={c.names.get('window', [])} "
        f"bytes_in_use_after_window={mem.get('bytes_in_use', 'n/a')} "
        f"peak_bytes_in_use={mem.get('peak_bytes_in_use', 'n/a')}")
    if win.error:
        log(f"window: error={win.error}")
    rec = Record(window=win, phase_s=st.phase_s,
                 device_kind=device.device_kind,
                 served_in_trace=sm["served_in_trace"])

    # free the program's state before the reference takes the device
    ds = st.ds
    del st
    gc.collect()
    t = time.monotonic()
    numbers = check(ds, win.specs, sm["served"])
    lim = limits()
    correct = win.error is None and all(numbers[k] <= lim[k] for k in lim)
    log(f"reference: seconds={time.monotonic() - t:.3f} "
        f"requests={len(win.specs)}")

    dev_info = {"platform": device.platform, "kind": device.device_kind,
                "count": len(jax.devices()),
                "memory_peak_bytes": mem.get("peak_bytes_in_use", 0)}
    out: Dict = {"correct": bool(correct), "attempted": len(win.specs),
                 "failed": sum(1 for s in sm["served"] if s is None)}
    if trace:
        rec.trace = tracemod.load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        dev_info["busy_s"] = tracemod.busy_s(rec.trace)
        dev_info["window_s"] = rec.trace.window_s
        metrics = {}
        for m in cell.per_layer:
            v = m.read(rec)
            if v is not None:
                metrics[m.name] = {"value": float(v), "unit": m.unit}
        out["metrics"] = metrics
        out["breakdown"] = {"device_ops": tracemod.top_ops(rec.trace),
                            "idle_gaps": tracemod.idle_gaps(rec.trace)}
    else:
        e2e = dict(sm, setup_s=setup_s,
                   hbm_peak_gb=mem["peak_bytes_in_use"] / 1e9
                   if "peak_bytes_in_use" in mem else None)
        out["metrics"] = {m.name: {"value": e2e[m.name], "unit": m.unit}
                          for m in cell.end_to_end
                          if e2e.get(m.name) is not None}
    out["device"] = dev_info
    out["checks"] = {k: {"value": numbers[k], "limit": lim[k]} for k in lim}
    return out
