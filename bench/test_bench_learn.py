"""The harness runs a deployment that learns its readout online (RLS).

On the CPU, at toy sizes (bench/testdata/learn/: N=16, E=8, K=4, NARMA-10
targets, washout 4): the plain reference's learner against the closed
form; the toy cell through `harness.execute` on one device and on four
virtual devices as a {"data": 4} and a (2, 2) mesh, within its toy limits;
the check failing each broken learner; and each configuration the harness
cannot run as stated refused before any run."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from benchlib import data, harness, reference, registry, traffic  # noqa: E402
from benchlib import system  # noqa: E402,F401  (puts the program on the path)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import repro.kernels.rls as krls  # noqa: E402

TESTDATA = os.path.join(HERE, "testdata", "learn")
ONE = "toy_rls.learn_closed"


def toy(name=ONE):
    with open(os.path.join(TESTDATA, "cells.json")) as f:
        return registry.Cell(name, json.load(f), TESTDATA)


def execute(cell, seed):
    import time

    return harness.execute(cell, seed, 0.6, False, False, time.perf_counter())


# ---------------------------------------------------------------------------
# the reference's learner
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("washout,seed", [(0, 3), (5, 4), (0, 4294967311)])
def test_reference_rls_is_ridge_at_lambda_one(washout, seed):
    """At lam = 1 the learned W after T updates is (X^T X + reg I)^-1 X^T Y
    over the updated ticks, solved in float64 (S = 17)."""
    cfg = toy().config
    d = data.make(cfg, seed)
    t, reg = 48, 0.05
    u = data.session_inputs(seed, 0, t, 1, 0.0, 0.5)
    y = traffic.narma10(u)
    got = reference.drive(d, [u], [t], block=1, learner={
        "lam": 1.0, "reg": reg, "targets": [y], "skip": [washout]})[0]
    x = np.concatenate([got["states"], np.ones((t, 1), np.float32)], axis=1)
    x, yy = x[washout:].astype(np.float64), y[washout:].astype(np.float64)
    ridge = np.linalg.solve(x.T @ x + reg * np.eye(x.shape[1]), x.T @ yy)
    np.testing.assert_allclose(got["final_w"], ridge, rtol=2e-3,
                               atol=2e-3 * np.max(np.abs(ridge)))
    # the prediction of each tick uses the weights before its update
    assert np.all(got["predictions"][: washout + 1] == 0.0)
    assert np.any(got["predictions"][washout + 1:] != 0.0)


def test_reference_imports_nothing_of_the_program():
    code = ("import sys; sys.path.insert(0, 'bench');"
            "from benchlib import reference, traffic, data;"
            "bad = [m for m in sys.modules if m.split('.')[0] == 'repro'];"
            "assert not bad, bad")
    p = subprocess.run([sys.executable, "-c", code], cwd=os.path.dirname(HERE),
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]


def test_narma10_targets():
    u = data.session_inputs(11, 2, 64, 1, 0.0, 0.5)
    y = traffic.narma10(u)
    want = np.zeros(65)
    x = u[:, 0].astype(np.float64)
    for t in range(64):
        lag = x[t - 9] if t >= 9 else 0.0
        want[t + 1] = (0.3 * want[t] + 0.05 * want[t] * want[max(0, t - 9): t + 1].sum()
                       + 1.5 * lag * x[t] + 0.1)
    np.testing.assert_allclose(y[:, 0], want[1:], rtol=1e-6)
    with pytest.raises(ValueError, match="not finite"):
        traffic.narma10(np.full((64, 1), 1e3, np.float32))


# ---------------------------------------------------------------------------
# the toy cell through the harness
# ---------------------------------------------------------------------------


def test_toy_learning_cell_on_one_device():
    res = execute(toy(), 4294967311)
    assert res.sample, res.notes
    assert res.line["correct"], res.checks
    assert res.line["failed"] == 0
    assert {name for name, _, _ in res.checks} == set(harness.GAPS[True]) | {"failed"}
    shape = harness.Context(res.run, None, None).shape
    assert (shape["chips"], shape["learn"], shape["s"]) == (1, "rls", 17)
    assert registry.work("tick_chunk").count(shape)["flops"] > 0


FOUR_DEVICES = """
import json, sys, time
sys.path.insert(0, {here!r})
from benchlib import harness, registry
with open({cells!r}) as f:
    cell = registry.Cell({name!r}, json.load(f), {base!r})
held = {{}}
release = harness.Run.release
def note_placement(run):
    held["p"] = len(run.eng.store.P.sharding.device_set)
    held["m"] = len(run.eng.store.m.sharding.device_set)
    release(run)
harness.Run.release = note_placement
res = harness.execute(cell, 3100000207, 0.6, False, False, time.perf_counter())
print(json.dumps({{"correct": res.line["correct"], "sample": len(res.sample),
                  "checks": res.line["checks"], "devices": held}}))
"""


@pytest.mark.parametrize("name", ["toy_rls4.learn_closed", "toy_rls22.learn_closed"])
def test_toy_learning_cell_on_four_device_mesh(name):
    """{"data": 4}, and (2, 2) with N over the model axis."""
    code = FOUR_DEVICES.format(here=HERE, cells=os.path.join(TESTDATA, "cells.json"),
                               name=name, base=TESTDATA)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["sample"] > 0
    assert line["correct"], line["checks"]
    assert set(line["checks"]) == set(harness.GAPS[True]) | {"failed"}
    assert line["devices"] == {"p": 4, "m": 4}  # P and the state span the mesh


def test_readout_cell_computes_no_targets(monkeypatch):
    def no_targets(*args, **kwargs):
        raise AssertionError("a readout session computed targets")

    monkeypatch.setattr(traffic, "targets", no_targets)
    cell = registry.Cell("n1.readout_closed")
    harness.apply_rehearsal(cell)
    assert "learn_washout" not in cell.traffic and "audit_learn_lanes" not in cell.check
    res = harness.execute(cell, 21, 0.6, False, True, 0.0)
    assert res.line["correct"], res.checks


def test_rehearsal_shrinks_a_learning_cell():
    cell = toy()
    harness.apply_rehearsal(cell)
    r = registry.rehearsal()
    assert cell.traffic["learn_washout"] == r["traffic"]["closed"]["learn_washout"]
    assert cell.check["audit_learn_lanes"] == r["check"]["audit_learn_lanes"]
    assert harness.validate(cell) is True


# ---------------------------------------------------------------------------
# a broken learner makes `correct` false
# ---------------------------------------------------------------------------


def p_not_updated(orig):
    def rls_chunk(p, w, xb, y, mask, lam):
        return (p, *orig(p, w, xb, y, mask, lam)[1:])
    return rls_chunk


def w_not_updated(orig):
    def rls_chunk(p, w, xb, y, mask, lam):
        p_new, _, preds = orig(p, w, xb, y, mask, lam)
        return p_new, w, preds
    return rls_chunk


def lam_off(orig):
    def rls_chunk(p, w, xb, y, mask, lam):
        return orig(p, w, xb, y, mask, lam - 1e-2)
    return rls_chunk


def preds_after_update(orig):
    def rls_chunk(p, w, xb, y, mask, lam):
        preds = []
        for t in range(xb.shape[0]):
            p, w, _ = orig(p, w, xb[t:t + 1], y[t:t + 1], mask[t:t + 1], lam)
            preds.append(jnp.sum(w * xb[t].astype(w.dtype)[:, :, None], axis=1))
        return p, w, jnp.stack(preds)
    return rls_chunk


def bfloat16_learner(orig):
    def rls_chunk(p, w, xb, y, mask, lam):
        bf = jnp.bfloat16
        out = orig(p.astype(bf), w.astype(bf), xb.astype(bf), y.astype(bf), mask, lam)
        return tuple(a.astype(p.dtype) for a in out)
    return rls_chunk


FAULTS = {f.__name__: f for f in (p_not_updated, w_not_updated, lam_off,
                                  preds_after_update, bfloat16_learner)}


@pytest.fixture
def fresh_programs():
    """The learner is traced into jitted programs: drop every trace before
    and after, so a patched learner is traced in and taken out again."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_learner_is_not_correct(fault, monkeypatch, fresh_programs):
    monkeypatch.setattr(krls, "rls_chunk", FAULTS[fault](krls.rls_chunk))
    res = execute(toy(), 22)
    assert res.sample, res.notes
    assert not res.line["correct"], res.checks
    assert any(v > lim for _, v, lim in res.checks)


# ---------------------------------------------------------------------------
# refused before any run
# ---------------------------------------------------------------------------


def _mesh_of_two(cell):
    cell.config["plan"]["mesh"] = {"data": 2}


def _lanes_do_not_divide(cell):
    cell.entry = dict(cell.entry, chips=4)
    cell.chips = 4
    cell.config["plan"].update(mesh={"data": 4}, ensemble=6)


def _learn_without_targets(cell):
    del cell.traffic["targets"], cell.traffic["learn_washout"]


def _targets_without_learn(cell):
    for key in ("learn", "learn_lam", "learn_reg"):
        cell.config["plan"][key] = None


def _lms(cell):
    cell.config["plan"]["learn"] = "lms"


def _autoscale(cell):
    cell.config["plan"]["autoscale"] = {"min_slots": 4}


def _no_lambda(cell):
    del cell.config["plan"]["learn_lam"]


def _limit_on_outputs(cell):
    cell.check["limits"]["chunk_outputs_gap"] = 1e-3


def _horizon_inside_washout(cell):
    cell.check["horizon_ticks"] = 4


def _no_audit_lanes(cell):
    del cell.check["audit_learn_lanes"]


REFUSED = {
    "plan.mesh": _mesh_of_two,
    "plan.ensemble": _lanes_do_not_divide,
    "plan.learn='rls' needs": _learn_without_targets,
    "traffic targets": _targets_without_learn,
    "plan.learn='lms'": _lms,
    "plan.autoscale": _autoscale,
    "plan.learn_lam": _no_lambda,
    "chunk_outputs_gap": _limit_on_outputs,
    "horizon_ticks": _horizon_inside_washout,
    "audit_learn_lanes": _no_audit_lanes,
}


@pytest.mark.parametrize("key", sorted(REFUSED))
def test_configuration_is_refused_before_any_run(key, monkeypatch):
    def no_run(*args, **kwargs):
        raise AssertionError("the run started")

    monkeypatch.setattr(data, "make", no_run)
    cell = toy()
    REFUSED[key](cell)
    with pytest.raises(ValueError, match=key.replace("(", r"\(")):
        execute(cell, 1)
