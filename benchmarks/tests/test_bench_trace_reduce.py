"""The reduction from a profiler trace to busy time, kernel time and gaps."""

import json
import os

import pytest

from harness import manifest as mf, trace_reduce as tr

FIXTURE = os.path.join(mf.BENCH_DIR, "fixtures", "graph_dense_slice.json")


def test_the_recorded_slice_reduces_to_the_numbers_written_beside_it():
    with open(FIXTURE) as f:
        fx = json.load(f)
    red = tr.reduce(fx, r"^jit_dense_count_batch")  # the kernel the slice was recorded from
    want = fx["expected"]
    for key in ("window_s", "busy_s", "kernel_s", "gap_total_s"):
        assert red[key] == pytest.approx(want[key], rel=1e-9), key
    assert red["devices"] == want["devices"] and red["kernel_launches"] == want["kernel_launches"] == 12
    assert red["gaps"][:5] == [pytest.approx(g) for g in want["longest_gaps"]]
    assert red["device_ops"][0] == [want["top_op"][0], pytest.approx(want["top_op"][1])]
    # what can be worked out by hand: 12 launches of ~0.6217 ms are the busy time, the rest is gaps
    assert red["kernel_s"] == pytest.approx(12 * 0.6217e-3, rel=1e-3)
    assert red["busy_s"] + red["gap_total_s"] == pytest.approx(red["window_s"])
    assert red["busy_s"] <= red["kernel_s"]
    assert list(red["modules"]) == ["jit_dense_count_batch"]


def _ir():
    ms = 1e6  # ns
    dev = lambda n, ops, mods: {  # noqa: E731
        "name": f"/device:TPU:{n}",
        "lines": [{"name": "XLA Ops", "events": ops}, {"name": "XLA Modules", "events": mods}],
    }
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            [tr.BEGIN, 10 * ms, 1.0], [tr.END, 110 * ms, 1.0], ["something else", 0.0, 5.0]]}]},
        # device 0: 5..15 (clipped to 10..15), 20..30 and 25..40 overlapping, 100..120 (clipped to 110)
        dev(0, [["%a = f32[8]{0} fusion(x), kind=kLoop", 5 * ms, 10 * ms], ["%b = f32[8]{0} copy(y)", 20 * ms, 10 * ms],
                ["%a = f32[8]{0} fusion(x), kind=kLoop", 25 * ms, 15 * ms], ["%b = f32[8]{0} copy(y)", 100 * ms, 20 * ms]],
            [["jit_kernel(123)", 20 * ms, 20 * ms], ["jit_other(9)", 100 * ms, 20 * ms]]),
        # device 1: busy 50..60 only
        dev(1, [["%a = f32[8]{0} fusion(x), kind=kLoop", 50 * ms, 10 * ms]], [["jit_kernel(123)", 50 * ms, 10 * ms]]),
    ]}


def test_a_hand_worked_trace():
    red = tr.reduce(_ir(), r"^jit_kernel")
    assert red["window_s"] == pytest.approx(0.100) and red["devices"] == 2
    # device 0: 5 + 20 + 10 = 35 ms; device 1: 10 ms; the mean over the chips
    assert red["busy_s"] == pytest.approx((0.035 + 0.010) / 2)
    assert red["kernel_launches"] == 2 and red["kernel_s"] == pytest.approx((0.020 + 0.010) / 2)
    assert red["modules"]["jit_other"] == {"seconds": pytest.approx(0.010), "launches": 1}
    # gaps of the first device, longest first, seconds from the window's start
    assert red["gaps"] == [(pytest.approx(0.030), pytest.approx(0.060)), (pytest.approx(0.005), pytest.approx(0.005))]
    assert dict(map(tuple, red["device_ops"])) == {
        "a fusion f32[8]": pytest.approx(0.005 + 0.015 + 0.010), "b copy f32[8]": pytest.approx(0.020)}


def test_no_device_plane_no_device_number():
    ir = _ir()
    ir["planes"] = ir["planes"][:1]
    assert tr.reduce(ir, r"^jit_kernel") is None


def test_gaps_are_named_by_the_narrowest_tagged_span_over_their_middle():
    spans = [("ws_rpc", 0.0, 1.0), ("execute", 0.1, 0.9), ("dispatch_queue_wait", 0.4, 0.5), ("ws_rpc", 2.0, 2.2)]
    out = tr.attribute_gaps([(0.42, 0.06), (0.6, 0.1), (2.05, 0.1), (5.0, 0.5), (0.44, 0.02)], spans)
    assert out == [["no_tagged_request", pytest.approx(0.5)], ["execute", pytest.approx(0.1)],
                   ["ws_rpc", pytest.approx(0.1)], ["dispatch_queue_wait", pytest.approx(0.08)]]


def test_short_op():
    long = "%fusion.3 = f32[32]{0:T(128)} fusion(f32[32,10112]{1,0:T(8,128)S(1)} %fusion.1), kind=kOutput"
    assert tr.short_op(long) == "fusion.3 fusion f32[32]"
    tup = "%while.1 = (s32[]{:T(128)}, f32[8,1,10]{2,1,0:T(8,128)}) while((s32[], f32[8,1,10]) %tuple), body=%b"
    assert tr.short_op(tup) == "while.1 while (s32[], f32[8,1,10])"
    assert tr.short_op("no equals sign") == "no equals sign"
