"""Sharded-ensemble correctness: run in a SUBPROCESS with 8 virtual devices
(XLA_FLAGS must not leak into other tests, which expect 1 device)."""

import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np
jax.config.update("jax_enable_x64", True)

from repro.core import (
    DT, broadcast_params, default_params, initial_magnetization,
    integrate_ensemble, integrate_ensemble_sharded, make_coupling_matrix,
    norm_error,
)

assert len(jax.devices()) == 8
mesh = jax.make_mesh((4, 2), ("data", "model"))

n, e = 16, 8
p = default_params(jnp.float64)
pe = broadcast_params(p, e, current=jnp.linspace(1e-3, 4e-3, e))
w = jnp.asarray(make_coupling_matrix(n, seed=0), jnp.float64)
m0 = jnp.broadcast_to(initial_magnetization(n, jnp.float64), (e, n, 3))

ref, _ = integrate_ensemble(pe, w, m0, DT, 50)
out = integrate_ensemble_sharded(mesh, pe, w, m0, DT, 50,
                                 ensemble_axes=("data",), model_axis="model")
err = float(jnp.max(jnp.abs(out - ref)))
cons = float(norm_error(out))

# model_axis=None variant (pure ensemble parallelism)
mesh1 = jax.make_mesh((8,), ("data",))
out2 = integrate_ensemble_sharded(mesh1, pe, w, m0, DT, 50,
                                  ensemble_axes=("data",), model_axis=None)
err2 = float(jnp.max(jnp.abs(out2 - ref)))

# bf16 coupling-path variant (SS Perf C): wire/matmul in bf16, state f32.
# The coupling is a ~1 Oe perturbation against ~600 Oe local fields, so the
# trajectory deviation stays small over short horizons and |m|=1 holds.
out3 = integrate_ensemble_sharded(mesh, pe, w, m0,
                                  DT, 50, ensemble_axes=("data",),
                                  model_axis="model",
                                  gather_dtype=jnp.bfloat16)
err3 = float(jnp.max(jnp.abs(out3.astype(jnp.float64) - ref)))
cons3 = float(norm_error(out3))

# sharded DRIVE (input on) vs the single-reservoir drive, member by member
from repro.core.ensemble import drive_ensemble_sharded, fit_ridge_ensemble
from repro.core.reservoir import Reservoir, drive as drive_single
from repro.core import make_input_matrix
from repro.core import tasks

p300 = p._replace(a_in=jnp.float64(300.0))
pe2 = broadcast_params(p300, 4, current=jnp.linspace(2e-3, 3e-3, 4))
win = jnp.asarray(make_input_matrix(n, 1, seed=1), jnp.float64)
m0d = m0[:4]
u, y = tasks.narma_series(30, order=2, seed=0)
mT, states = drive_ensemble_sharded(
    mesh, pe2, w, win, m0d, jnp.asarray(u[:, None]), DT, 10)
errs = []
for i in range(4):
    pi = p300._replace(current=jnp.float64(float(pe2.current[i, 0])))
    res = Reservoir(pi, w, win, m0d[i], float(DT), 10)
    _, st = drive_single(res, jnp.asarray(u[:, None]))
    errs.append(float(jnp.max(jnp.abs(st - states[:, i]))))
wout = fit_ridge_ensemble(states, jnp.asarray(y[:, None]), reg=1e-6, washout=5)

print(json.dumps({"err": err, "cons": cons, "err2": err2,
                  "err3": err3, "cons3": cons3,
                  "drive_err": max(errs),
                  "readout_shape": list(wout.shape)}))
"""


@pytest.mark.slow
def test_sharded_matches_batched():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    # sharded (all-gather per stage) == batched, up to f64 reduction order
    assert res["err"] < 1e-10
    assert res["err2"] < 1e-12
    assert res["cons"] < 1e-7
    # bf16 coupling path: bounded deviation, conservation intact
    assert res["err3"] < 5e-2
    assert res["cons3"] < 1e-4
    # sharded drive (input on) matches the single-reservoir reference
    assert res["drive_err"] < 1e-9
    assert res["readout_shape"] == [4, 17, 1]


_SERVE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
import jax.numpy as jnp
import numpy as np

from repro.api import ExecPlan, compile_plan, make_spec
from repro.serve.reservoir import ReservoirEngine, StreamSession

assert len(jax.devices()) == 4
# jax.make_mesh types its axes Explicit; the sharded path must serve on it
mesh = jax.make_mesh((2, 2), ("data", "model"))
spec = make_spec(n=16, n_in=1, hold_steps=4, dtype=jnp.float32)
rng = np.random.default_rng(0)
streams = rng.uniform(0.0, 0.5, size=(8, 12, 1)).astype(np.float32)

def serve(plan):
    eng = ReservoirEngine(compile_plan(spec, plan))
    res = eng.run([StreamSession(sid=i, u_seq=s) for i, s in enumerate(streams)])
    return eng, res

eng, sharded = serve(ExecPlan(mesh=mesh, ensemble=8, chunk_ticks=4))
_, ref = serve(ExecPlan(impl="ref", ensemble=8, chunk_ticks=4))
print(json.dumps({
    "sessions": len(sharded),
    "devices": len(eng.store.m.sharding.device_set),
    "dev": max(float(np.max(np.abs(sharded[i].states - ref[i].states)))
               for i in ref),
}))
"""


def test_sharded_engine_serves_on_2x2_mesh():
    """The sharded ReservoirEngine on a (data=2, model=2) mesh of 4 virtual
    devices serves the same sessions as impl="ref" on one device."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", _SERVE_SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["sessions"] == 8
    assert res["devices"] == 4
    assert res["dev"] < 1e-5
