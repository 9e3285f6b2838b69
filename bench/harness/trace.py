"""From a profiler trace to device busy time, kernel time and idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
nothing but JAX: the ops of each device plane's ``XLA Ops`` line, and the
harness's own ``bench.*`` spans from the host plane, on one clock. The
traced window is the ``bench.window`` span. On a TPU each op event is
named by its HLO text (``%topk_l2_masked_pallas.7 = (f32[32,128], ...)
custom-call(f32[32,128] %a, f32[32,16384,128] %b, ...)``): the op's name
is the instruction name before `` = ``, and the text is kept for its
shapes. A Pallas kernel's instruction is named after the function that
made the ``pallas_call`` (``topk_l2_masked_pallas``). Everything else here is
plain arithmetic on those events, checked on a small recorded trace in
``bench/tests``.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

_OPS_LINE = "XLA Ops"
_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
                "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "s4": 0.5, "u4": 0.5}
_BRACES = re.compile(r"\{[^{}]*\}")
_HLO_OP = re.compile(r"=\s*(?P<out>\([^()]*\)|\S+)\s+[\w\-.]+\((?P<args>.*)")
_SHAPE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    detail: str = ""      # the event's string stats, joined (HLO text)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    devices: Dict[str, List[Event]]   # device plane -> its ops
    host: List[Event]                 # the harness's bench.* spans
    t0_ns: float                      # the traced window
    t1_ns: float

    @property
    def window_s(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9


def op_event(name: str, start_ns: float, dur_ns: float) -> Event:
    """An ``XLA Ops`` event: named by its HLO instruction and first
    shape (``fusion.18 f32[8192,64,128]``), its HLO text kept."""
    if " = " not in name:
        return Event(name, start_ns, dur_ns, name)
    instr, rhs = name.split(" = ", 1)
    shape = _SHAPE.search(rhs)
    label = instr.lstrip("%") + (f" {shape.group(0)}" if shape else "")
    return Event(label, start_ns, dur_ns, name)


def instruction(e: Event) -> str:
    """The op's HLO instruction name without its ``.N`` suffix."""
    return re.sub(r"\.\d+$", "", e.name.split(" ", 1)[0])


def load(logdir: str) -> Trace:
    """The trace ``jax.profiler`` wrote under ``logdir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file, found {paths}")
    pd = ProfileData.from_file(paths[0])
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops = [op_event(e.name, e.start_ns, e.duration_ns)
                   for line in plane.lines if line.name == _OPS_LINE
                   for e in line.events]
            if ops:
                devices[plane.name] = sorted(ops, key=lambda e: e.start_ns)
        elif plane.name.startswith("/host:"):
            host.extend(Event(e.name, e.start_ns, e.duration_ns)
                        for line in plane.lines for e in line.events
                        if e.name.startswith("bench."))
    win = [e for e in host if e.name == "bench.window"]
    if not win:
        raise RuntimeError("the trace holds no bench.window span")
    host.sort(key=lambda e: e.start_ns)
    return Trace(devices=devices, host=host, t0_ns=win[0].start_ns,
                 t1_ns=win[0].end_ns)


def merged(events: Iterable[Event], t0: float, t1: float
           ) -> List[Tuple[float, float]]:
    """The union of the events' intervals, clipped to ``[t0, t1]``."""
    out: List[List[float]] = []
    for s, e in sorted((max(ev.start_ns, t0), min(ev.end_ns, t1))
                       for ev in events):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(tr: Trace) -> float:
    """Seconds of the window in which some op ran, averaged over the
    devices that ran any."""
    if not tr.devices:
        return 0.0
    tot = [sum(e - s for s, e in merged(ops, tr.t0_ns, tr.t1_ns))
           for ops in tr.devices.values()]
    return sum(tot) / len(tot) / 1e9


def self_ns(ops: Sequence[Event]) -> List[float]:
    """Each op's own time: its duration less that of the ops nested in
    it on the same line (a loop op and the ops of its body)."""
    own = [e.dur_ns for e in ops]
    stack: List[int] = []
    for i, e in enumerate(ops):
        while stack and ops[stack[-1]].end_ns <= e.start_ns:
            stack.pop()
        if stack and e.end_ns <= ops[stack[-1]].end_ns:
            own[stack[-1]] -= e.dur_ns
        stack.append(i)
    return own


def in_window(tr: Trace, ops: Sequence[Event]) -> List[Event]:
    return [e for e in ops if e.start_ns >= tr.t0_ns and e.end_ns <= tr.t1_ns]


def top_ops(tr: Trace, n: int = 10) -> List[List]:
    """The ops that took most device time in the window, by own time,
    summed by name and averaged over the devices."""
    tot: Dict[str, float] = {}
    for ops in tr.devices.values():
        ops = in_window(tr, ops)
        for e, t in zip(ops, self_ns(ops)):
            tot[e.name] = tot.get(e.name, 0.0) + t
    k = max(1, len(tr.devices))
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, t / k / 1e9] for name, t in ranked]


def idle_gaps(tr: Trace, n: int = 10) -> List[List]:
    """The longest gaps on the first device in which no op ran, each
    named by the harness span that covered most of it
    (``host.other`` where none did)."""
    if not tr.devices:
        return []
    ops = tr.devices[sorted(tr.devices)[0]]
    busy = merged(ops, tr.t0_ns, tr.t1_ns)
    edges = [tr.t0_ns] + [x for iv in busy for x in iv] + [tr.t1_ns]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = [h for h in tr.host if h.name != "bench.window"]
    out = []
    for s, e in gaps[:n]:
        best, cover = "host.other", 0.0
        for h in spans:
            c = min(e, h.end_ns) - max(s, h.start_ns)
            if c > cover:
                best, cover = h.name, c
        out.append([best, (e - s) / 1e9])
    return out


def kernel_ops(tr: Trace, kernels: Sequence[str]) -> List[Event]:
    """Ops in the window whose instruction is one of the named Pallas
    kernels (a ``tpu_custom_call``)."""
    names = set(kernels)
    return [e for ops in tr.devices.values() for e in in_window(tr, ops)
            if instruction(e) in names and "custom-call" in e.detail]


def operand_shapes(detail: str) -> Optional[Tuple[List, List]]:
    """``(outputs, operands)`` as lists of ``(dtype, dims)`` from an op's
    HLO text (``%x = (f32[8,128], ...) custom-call(f32[8,128] %a, ...)``),
    or None where the text carries no shapes."""
    text = detail
    while True:                      # layouts, tilings: {1,0:T(8,128)}
        stripped = _BRACES.sub("", text)
        if stripped == text:
            break
        text = stripped
    m = _HLO_OP.search(text)
    if m is None:
        return None
    outs = [(t, [int(x) for x in d.split(",") if x])
            for t, d in _SHAPE.findall(m.group("out"))]
    ins = [(t, [int(x) for x in d.split(",") if x])
           for t, d in _SHAPE.findall(m.group("args").split(")", 1)[0])]
    if not outs or not ins:
        return None
    return outs, ins


def nbytes(shapes: Iterable[Tuple[str, List[int]]]) -> float:
    tot = 0.0
    for t, dims in shapes:
        n = 1
        for x in dims:
            n *= x
        tot += n * _DTYPE_BYTES[t]
    return tot
