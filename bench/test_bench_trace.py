"""The reduction from a profiler trace to the per-layer numbers, checked on
a hand-made trace and on a small trace recorded on a TPU v5e."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from benchlib import trace  # noqa: E402

DEV = "/device:TPU:0"


def hand_trace():
    """A 100 ns window: two programs with ops inside, one idle stretch under
    step_chunk and one under submit."""
    return {
        "ops": [
            ("fusion.1", 10, 30, DEV), ("fusion.2", 25, 40, DEV),  # overlap
            ("custom-call", 60, 80, DEV), ("fusion.1", 85, 90, DEV),
            ("fusion.1", 95, 130, DEV),  # runs past the window's end
        ],
        "modules": [("jit_a", 5, 42, DEV), ("jit_b", 58, 92, DEV),
                    ("jit_c", 94, 140, DEV)],
        "spans": [("trace_window", 0, 100), ("step_chunk", 0, 50),
                  ("submit", 45, 60), ("submit", 96, 99)],
        "devices": [DEV], "on_device": True,
    }


def test_union_and_gaps():
    merged = trace.union([(10, 30), (25, 40), (60, 80), (95, 130)], 0, 100)
    assert merged == [(10, 40), (60, 80), (95, 100)]
    assert trace.gaps(merged, 0, 100) == [(0, 10), (40, 60), (80, 95)]


def test_reduce_hand_trace():
    red = trace.reduce(hand_trace())
    # busy: [10,40] + [60,80] + [85,90] + [95,100] = 30 + 20 + 5 + 5
    assert red["busy_s"] == pytest.approx(60e-9)
    assert red["window_s"] == pytest.approx(100e-9)
    # no program: [0,5] + [42,58] + [92,94] = 5 + 16 + 2
    assert red["module_idle_s"] == pytest.approx(23e-9)
    # longest idle gaps first, labelled by the host span covering most of them
    assert red["idle_gaps"][0] == ["submit", pytest.approx(20e-9)]
    assert [g[0] for g in red["idle_gaps"]] == [
        "submit", "step_chunk", "harness", "harness"]
    names = dict((n, t) for n, t in red["device_ops"])
    assert names["fusion.1"] == pytest.approx((20 + 5 + 5) * 1e-9)
    assert names["custom-call"] == pytest.approx(20e-9)


def test_reduce_needs_a_window_and_an_op():
    tr = hand_trace()
    tr["spans"] = [s for s in tr["spans"] if s[0] != "trace_window"]
    assert trace.reduce(tr) is None
    tr = hand_trace()
    tr["ops"] = []
    assert trace.reduce(tr) is None


def test_metric_readers_on_hand_trace():
    from benchlib import registry

    class Ctx:
        chunks_in_trace = 2
        notes = []

    ctx = Ctx()
    ctx.trace = trace.reduce(hand_trace())
    read = registry._module("metrics", "device_idle_share.closed").read
    assert read(ctx) == pytest.approx(40.0)
    read = registry._module("metrics", "boundary_gap_ms.closed").read
    assert read(ctx) == pytest.approx(23e-9 / 2 * 1e3)
    ctx.trace = None
    assert read(ctx) is None


def recorded():
    with open(os.path.join(HERE, "testdata", "trace_v5e_n16.json")) as f:
        return json.load(f)


def test_reduce_recorded_v5e_trace():
    import numpy as np

    rec = recorded()
    tr = rec["trace"]
    red = trace.reduce(tr)
    lo, hi = trace.window(tr["spans"])
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9)
    # busy by a second method: occupancy of 1 us bins
    bins = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for _, s, e, _ in tr["ops"]:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            bins[int((s - lo) // 1000): int(-(-(e - lo) // 1000))] = True
    assert red["busy_s"] == pytest.approx(bins.sum() * 1e-6, rel=0.02)
    # the idle gaps are the rest of the window, and no program runs in less
    idle = sum(e - s for s, e in trace.gaps(
        trace.union([(o[1], o[2]) for o in tr["ops"]], lo, hi), lo, hi)) * 1e-9
    assert red["busy_s"] + idle == pytest.approx(red["window_s"])
    assert red["module_idle_s"] <= idle + 1e-12
    # N=16 on the fused kernel: the Pallas call is the device's main work,
    # and the host's step_chunk covers the longest idle gaps
    assert red["device_ops"][0][0].endswith("[tpu_custom_call]")
    assert red["device_ops"][0][1] > 0.5 * red["busy_s"]
    assert all(label == "step_chunk" for label, _ in red["idle_gaps"][:4])
    assert rec["chunks_in_trace"] == 2
