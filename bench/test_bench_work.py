"""The work count of a served chunk: the same whichever impl runs it, and the
hand count at the two deployments' sizes (N=1000, E=256 and N=1, E=4096)."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

from benchlib import registry  # noqa: E402

work = registry.work("tick_chunk")


def shape_of(sim, n_out=1, collect_states=False):
    """What the count reads from a compiled plan: shapes only, never its impl."""
    return {
        "n": sim.spec.n, "e": sim.plan.ensemble, "k": sim.plan.chunk_ticks,
        "hold_steps": sim.spec.hold_steps, "stages": 4, "n_in": sim.spec.n_in,
        "n_out": n_out, "itemsize": sim.spec.dtype.itemsize,
        "collect_states": collect_states,
    }


def test_count_is_the_same_whichever_impl_runs_the_chunk():
    from repro.api import ExecPlan, compile_plan, make_spec

    spec = make_spec(n=8, n_in=1, hold_steps=3)
    counts, impls = [], []
    for impl in ("scan", "ref", "fused", "tiled", "chunk"):
        sim = compile_plan(spec, ExecPlan(impl=impl, ensemble=4, chunk_ticks=2,
                                          interpret=True))
        impls.append(sim.impl)
        counts.append(work.count(shape_of(sim)))
    assert impls == ["scan", "ref", "fused", "tiled", "chunk"]
    assert all(c == counts[0] for c in counts)


@pytest.mark.parametrize("n,e,collect", [(1, 4096, False), (1000, 256, False),
                                         (1000, 256, True)])
def test_count_matches_the_hand_count(n, e, collect):
    k, hold, stages = 8, 100, 4
    steps = k * hold
    coupling = 2 * n * n * e * stages * steps  # W @ m_x per RK stage
    inputs = 2 * n * 1 * e * k  # W_in @ u once per tick
    field = 54 * n * e * stages * steps  # LLG right-hand side per stage
    update = 42 * n * e * steps  # RK4 stage inputs and weighted sum
    readout = 2 * (n + 1) * 1 * e * k  # [x, 1] @ w_out per tick
    out = k * n * e if collect else k * e
    nbytes = 4 * (n * n + n + 6 * n * e + k * e + e * (n + 1) + out) + k * e
    got = work.count({"n": n, "e": e, "k": k, "hold_steps": hold, "stages": stages,
                      "n_in": 1, "n_out": 1, "itemsize": 4,
                      "collect_states": collect})
    assert got == {"flops": coupling + inputs + field + update + readout,
                   "bytes": nbytes}


def test_the_hand_counts_in_numbers():
    base = {"k": 8, "hold_steps": 100, "stages": 4, "n_in": 1, "n_out": 1,
            "itemsize": 4, "collect_states": False}
    # N=1000, E=256: coupling 1,638,400,000,000 + input 4,096,000
    # + field 44,236,800,000 + RK4 update 8,601,600,000 + readout 4,100,096
    big = work.count(dict(base, n=1000, e=256))
    assert big["flops"] == 1_691_246_596_096
    # 4 B x (W 1,000,000 + W_in 1,000 + planes 1,536,000 + u 2,048
    # + readouts 256,256 + outputs 2,048) + mask 2,048
    assert big["bytes"] == 11_191_456
    # N=1, E=4096: coupling 26,214,400 + input 65,536 + field 707,788,800
    # + RK4 update 137,625,600 + readout 131,072
    small = work.count(dict(base, n=1, e=4096))
    assert small["flops"] == 871_825_408
    # 4 B x (W 1 + W_in 1 + planes 24,576 + u 32,768 + readouts 8,192
    # + outputs 32,768) + mask 32,768
    assert small["bytes"] == 425_992


def test_least_time_names_its_bound():
    peak = registry.peaks()["TPU v5 lite"]
    shape = {"n": 1000, "e": 256, "k": 8, "hold_steps": 100, "stages": 4,
             "n_in": 1, "n_out": 1, "itemsize": 4, "collect_states": False}
    least = work.least_seconds(shape, peak)
    assert least["bound"] == "flops"
    assert least["seconds"] == pytest.approx(1_691_246_596_096 / 197e12)
