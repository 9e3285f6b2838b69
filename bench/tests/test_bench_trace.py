"""The trace reduction on small recorded traces: device busy and idle
time, ops' own time under a loop op, kernel matching by instruction,
shapes and roofline from the HLO text, and the attribution of idle gaps
to the harness's spans. ``trace_v5e_excerpt.json`` holds the first ops
of a TPU v5e trace of ``sift1m.knn10-open``."""
import json
import os

import pytest

import benchpaths  # noqa: F401

from harness import kernels, peaks
from harness import trace as tr
from harness.trace import Event, Trace

HERE = os.path.dirname(os.path.abspath(__file__))

MASKED = ("%topk_l2_masked_pallas.7 = (f32[8,128]{1,0:T(8,128)S(1)}, "
          "s32[8,128]{1,0:T(8,128)S(1)}) custom-call(f32[8,128]{1,0:T(8,128)"
          "S(1)} %p0, f32[8,512,128]{2,1,0:T(8,128)} %p1, s32[8,512]{1,0:"
          "T(8,128)S(1)} %p2), custom_call_target=\"tpu_custom_call\", "
          "operand_layout_constraints={f32[8,128]{1,0}, f32[8,512,128]"
          "{2,1,0}, s32[8,512]{1,0}}")


def small():
    ops = [tr.op_event("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a)", 0, 10),
           tr.op_event("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %a)", 5, 10),
           tr.op_event("%while.3 = (s32[]) while((s32[]) %t)", 20, 50),
           tr.op_event(MASKED, 22, 8),
           tr.op_event("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %a)", 35, 10),
           tr.op_event("%copy.5 = f32[8]{0} copy(f32[8]{0} %a)", 90, 30)]
    host = [Event("bench.window", 0, 100), Event("bench.poll", 0, 70),
            Event("bench.idle_wait", 72, 28)]
    return Trace(devices={"/device:TPU:0": ops}, host=host, t0_ns=0,
                 t1_ns=100)


def recorded():
    with open(os.path.join(HERE, "trace_v5e_excerpt.json")) as f:
        d = json.load(f)
    return Trace(devices={d["device"]: [tr.op_event(*o) for o in d["ops"]]},
                 host=[Event(*h) for h in d["host"]],
                 t0_ns=d["host"][0][1],
                 t1_ns=d["host"][0][1] + d["host"][0][2])


def test_busy_and_idle():
    t = small()
    assert tr.merged(t.devices["/device:TPU:0"], 0, 100) == [
        (0, 15), (20, 70), (90, 100)]
    assert tr.busy_s(t) == pytest.approx(75e-9)
    assert t.window_s == pytest.approx(100e-9)


def test_own_time_under_a_loop_op():
    ops = small().devices["/device:TPU:0"]
    own = dict(zip([e.name for e in ops], tr.self_ns(ops)))
    assert own["while.3 s32[]"] == 50 - 8 - 10
    assert own["fusion.1 f32[8]"] == 10
    top = tr.top_ops(small())
    assert top[0] == ["while.3 s32[]", 32e-9]
    # the copy runs past the window's end and is left out
    assert not [n for n, _ in top if n.startswith("copy.5")]


def test_idle_gaps_named_by_the_harness_span():
    gaps = tr.idle_gaps(small())
    assert gaps == [["bench.idle_wait", 20e-9], ["bench.poll", 5e-9]]


def test_kernels_matched_by_instruction():
    t = small()
    assert [e.name for e in tr.kernel_ops(t, kernels.TOPK)] == [
        "topk_l2_masked_pallas.7 f32[8,128]"]
    t.devices["/device:TPU:0"].append(tr.op_event(MASKED.replace(
        "topk_l2_masked_pallas", "topk_l2_masked_pallas_v2"), 60, 2))
    assert len(tr.kernel_ops(t, kernels.TOPK)) == 1


def test_shapes_and_roofline_of_a_call():
    outs, ins = tr.operand_shapes(MASKED)
    assert outs == [("f32", [8, 128]), ("s32", [8, 128])]
    assert ins == [("f32", [8, 128]), ("f32", [8, 512, 128]),
                   ("s32", [8, 512])]
    flops, nbytes = kernels.cost(MASKED)
    assert flops == 4.0 * 8 * 512 * 128
    assert nbytes == 4 * (8 * 128 + 8 * 512 * 128 + 8 * 512 + 2 * 8 * 128)
    least, bound = peaks.least_time(flops, nbytes, "TPU v5 lite")
    assert bound == "memory" and least == pytest.approx(nbytes / 819e9)
    assert kernels.cost("%fusion.1 = f32[8]{0} fusion()") is None


def test_recorded_trace():
    t = recorded()
    ops = t.devices["/device:TPU:0"]
    assert len(ops) == 40
    k = tr.kernel_ops(t, kernels.TOPK)
    # the prologue's pairwise kernel is not a top-k kernel
    assert [tr.instruction(e) for e in k] == ["topk_l2_masked_pallas"]
    assert k[0].dur_ns == 25247.0
    flops, nbytes = kernels.cost(k[0].detail)
    assert flops == 4.0 * 32 * 512 * 128
    assert nbytes == 4 * (32 * 128 + 32 * 512 * 128 + 32 * 512
                          + 2 * 32 * 128)
    share = peaks.least_time(flops, nbytes, "TPU v5 lite")[0] / 25247e-9
    assert 0.0 < share < 1.0
    busy = tr.busy_s(t)
    assert 0.0 < busy < t.window_s
    top = tr.top_ops(t, 3)
    assert top[0][0] == "sort.2 f32[32,17265]"
    assert tr.idle_gaps(t)[0][0] == "bench.idle_wait"
