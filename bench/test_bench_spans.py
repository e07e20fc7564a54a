"""The engine's phase spans read from a profiler trace (benchlib.spans):
self times, idle gaps labelled by self coverage, and the three boundary
sums, on a hand-made trace, on two traces recorded on a TPU v5e, and on the
CPU rehearsal of `bench/phases.py`."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from benchlib import spans, trace  # noqa: E402
from test_bench_trace import hand_trace, recorded  # noqa: E402

SAME = ("busy_s", "window_s", "module_idle_s", "device_ops")


def hand_trace_with_phases():
    """`hand_trace` with the engine's spans inside its step_chunk (0-50)
    and inside a second harness step_chunk (62-94)."""
    tr = hand_trace()
    tr["spans"] += [
        ("engine.step_chunk", 2, 49),
        ("engine.retire", 2, 4), ("engine.admit", 4, 5),
        ("engine.assemble", 5, 9), ("engine.launch", 9, 12),
        ("engine.harvest", 12, 47),
        ("engine.fetch", 13, 44), ("engine.nan_guard", 44, 45),
        ("engine.finalize", 47, 48),
        ("step_chunk", 62, 94.5),
        ("engine.step_chunk", 63, 94),
        ("engine.retire", 63, 64), ("engine.admit", 64, 65),
        ("engine.assemble", 65, 71), ("engine.launch", 71, 81),
        ("engine.harvest", 81, 91),
        ("engine.fetch", 82, 90), ("engine.nan_guard", 90, 91),
        ("engine.finalize", 91, 93.5),
    ]
    return tr


def test_hand_trace_labels_and_numbers_unchanged():
    red, old = spans.reduce(hand_trace()), trace.reduce(hand_trace())
    for key in SAME:
        assert red[key] == old[key]
    assert red["idle_gaps"] == old["idle_gaps"]
    assert [g[0] for g in red["idle_gaps"]] == [
        "submit", "step_chunk", "harness", "harness"]
    assert red["span_self_s"] == {} and red["step_chunk_s"] == 0.0
    assert spans.boundary_ms(red, 2) == dict.fromkeys(spans.BOUNDARY)
    assert spans.notes(red, 2) == []


def test_self_times_on_hand_trace():
    red = spans.reduce(hand_trace_with_phases())
    want = {"engine.step_chunk": 1 + 0.5, "engine.retire": 2 + 1,
            "engine.admit": 1 + 1, "engine.assemble": 4 + 6,
            "engine.launch": 3 + 10, "engine.harvest": 3 + 1,
            "engine.fetch": 31 + 8, "engine.nan_guard": 1 + 1,
            "engine.finalize": 1 + 2.5}
    assert red["span_self_s"] == pytest.approx({n: t * 1e-9 for n, t in want.items()})
    # the self times partition engine.step_chunk: (49 - 2) + (94 - 63)
    assert red["step_chunk_s"] == pytest.approx(78e-9)
    assert sum(red["span_self_s"].values()) == pytest.approx(red["step_chunk_s"])
    # harness spans have no self time of their own here
    assert all(n.startswith("engine.") for n in red["span_self_s"])


def test_self_coverage_labels_on_hand_trace():
    tr = hand_trace_with_phases()
    red, old = spans.reduce(tr), trace.reduce(tr)
    for key in SAME:
        assert red[key] == old[key]
    # (40, 60) submit 14 of 20; (0, 10) straddles the end of assemble (4)
    # and the start of launch (1); (80, 85) fetch 3 against launch 1 and
    # harvest 1; (90, 95) finalize 2.5 against nan_guard 1
    assert red["idle_gaps"] == [["submit", pytest.approx(20e-9)],
                                ["engine.assemble", pytest.approx(10e-9)],
                                ["engine.fetch", pytest.approx(5e-9)],
                                ["engine.finalize", pytest.approx(5e-9)]]
    assert [g[0] for g in old["idle_gaps"]] == [
        "submit", "step_chunk", "step_chunk", "step_chunk"]


def test_idle_shared_out_by_self_coverage():
    # each gap's time goes to the spans by self coverage, the rest to
    # 'harness'; submit (45-60) overlaps step_chunk (0-50) without nesting,
    # so (45, 50) counts under both, less finalize (47-48), which nests in
    # either
    red = spans.reduce(hand_trace_with_phases())
    want = {"step_chunk": 1 + 2 + 0.5, "submit": 15 - 1,
            "engine.step_chunk": 1 + 0.5, "engine.retire": 2,
            "engine.admit": 1, "engine.assemble": 4, "engine.launch": 1 + 1,
            "engine.harvest": 2 + 1, "engine.fetch": 4 + 3,
            "engine.nan_guard": 1 + 1, "engine.finalize": 1 + 2.5,
            "harness": 0.5}
    assert red["idle_by_span_s"] == pytest.approx({n: t * 1e-9 for n, t in want.items()})
    red = spans.reduce(hand_trace())
    assert red["idle_by_span_s"] == pytest.approx(
        {"step_chunk": 20e-9, "submit": 15e-9, "harness": 10e-9})


@pytest.mark.parametrize("gap,want", [
    ((8, 12), "engine.launch"),     # assemble 1, launch 3
    ((6, 10), "engine.assemble"),   # assemble 3, launch 1
    ((5.5, 6), "engine.assemble"),
    ((1, 1.5), "step_chunk"),       # outside every engine span
    ((100, 110), "harness"),
])
def test_label_straddling_gaps(gap, want):
    sp = [("trace_window", 0, 200), ("step_chunk", 0, 20),
          ("engine.step_chunk", 2, 19), ("engine.assemble", 5, 9),
          ("engine.launch", 9, 14)]
    assert spans.label(gap, sp) == want


def test_same_bounds_one_nests_in_the_other():
    sp = [("step_chunk", 0, 10), ("engine.step_chunk", 0, 10)]
    assert spans.label((2, 4), sp) == "engine.step_chunk"


def test_boundary_sums_on_hand_trace():
    red = spans.reduce(hand_trace_with_phases())
    got = spans.boundary_ms(red, 2)
    # ms per chunk: (retire 3 + admit 2 + assemble 10) ns / 2 chunks, ...
    assert got == pytest.approx({
        "boundary_assemble_ms.closed": 15e-6 / 2,
        "boundary_launch_ms.closed": 13e-6 / 2,
        "boundary_harvest_ms.closed": (4 + 2 + 3.5) * 1e-6 / 2})
    assert spans.boundary_ms(red, 0) == dict.fromkeys(spans.BOUNDARY)
    assert spans.boundary_ms(None, 2) == dict.fromkeys(spans.BOUNDARY)
    notes = spans.notes(red, 2)
    assert notes[0].startswith("engine span self time, ms per chunk (2 chunks): "
                               "engine.fetch=0.000")
    assert notes[1].startswith("device idle by span, ms per chunk: submit=0.000")
    assert "(98.08%)" in notes[2]


def test_old_recording_reads_as_before():
    tr = recorded()["trace"]
    red, old = spans.reduce(tr), trace.reduce(tr)
    for key in SAME:
        assert red[key] == old[key]
    assert red["idle_gaps"] == old["idle_gaps"]
    assert red["busy_s"] == pytest.approx(0.070175355, abs=1e-12)
    assert red["window_s"] == pytest.approx(0.256805116, abs=1e-12)
    assert red["module_idle_s"] == pytest.approx(0.186616023, abs=1e-12)
    assert red["device_ops"][0] == ["closed_call.11 [tpu_custom_call]",
                                    pytest.approx(0.069850026, abs=1e-12)]
    assert red["span_self_s"] == {}


def recorded_spans():
    with open(os.path.join(HERE, "testdata", "trace_v5e_n1_spans.json")) as f:
        return json.load(f)


def test_recorded_v5e_trace_with_phases():
    rec = recorded_spans()
    tr = rec["trace"]
    red, old = spans.reduce(tr), trace.reduce(tr)
    for key in SAME:
        assert red[key] == old[key]
    assert red["idle_gaps"]
    assert all(g[0].startswith("engine.") for g in red["idle_gaps"])
    # nearly all device idle lies under the engine's phases
    idle = red["window_s"] - red["busy_s"]
    engine = sum(t for n, t in red["idle_by_span_s"].items() if n.startswith("engine."))
    assert engine > 0.9 * idle
    # the phases cover engine.step_chunk to within 1%
    phases = sum(t for n, t in red["span_self_s"].items() if n != spans.STEP)
    assert phases == pytest.approx(red["step_chunk_s"], rel=0.01)
    assert rec["chunks_in_trace"] >= 2
    got = spans.boundary_ms(red, rec["chunks_in_trace"])
    assert all(v > 0 for v in got.values())


@pytest.mark.parametrize("workload", ["n1.readout_closed", "n1000.readout_closed"])
def test_phases_rehearses(workload, tmp_path):
    dump = tmp_path / "trace.json"
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "phases.py"), "--workload", workload,
         "--seed", "4294967311", "--seconds", "2", "--trace-seconds", "1",
         "--rehearse", "--dump", str(dump)],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["platform"] == "cpu" and line["chunks"] > 0
    assert set(line["boundary_ms"]) == set(spans.BOUNDARY)
    assert all(v is not None and v > 0 for v in line["boundary_ms"].values())
    assert set(line["span_ms"]) == {
        "engine.step_chunk", "engine.retire", "engine.admit", "engine.assemble",
        "engine.launch", "engine.harvest", "engine.fetch", "engine.nan_guard",
        "engine.finalize"}
    assert any(g[0].startswith("engine.") for g in line["idle_gaps"])
    assert "engine phases: " in p.stderr
    rec = json.loads(dump.read_text())
    assert rec["chunks_in_trace"] == line["chunks"]
    assert spans.reduce(rec["trace"])["busy_s"] == pytest.approx(line["busy_s"])


def test_phases_needs_a_chip_without_rehearse():
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "phases.py"), "--workload",
         "n1.readout_closed", "--seed", "1", "--seconds", "1",
         "--trace-seconds", "1"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 2 and p.stdout.strip() == ""
