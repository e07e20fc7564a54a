"""Distribution-layer tests (subprocess with 8 virtual devices):
sharding rules + divisibility fallbacks, compressed DP psum correctness,
elastic checkpoint restore across DIFFERENT mesh shapes, and a small
end-to-end sharded train-step lowering."""

import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, reduce_config
from repro.distributed import sharding as shd
from repro.distributed.collectives import dp_mean_grads_compressed
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.train import checkpoint as ckpt

out = {}
mesh = make_mesh((2, 4), ("data", "model"))

# --- 1. sharding rules: divisible dims shard, indivisible replicate -------
cfg = reduce_config(get_config("qwen2-moe-a2.7b"))   # moe: experts=8 % 4 == 0
m = build_model(cfg)
specs = jax.eval_shape(m.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
shardings = shd.param_shardings(mesh, specs)

flat = jax.tree_util.tree_flatten_with_path(shardings)[0]
by_path = {jax.tree_util.keystr(p): s for p, s in flat}
def spec_of(*frags):
    for k, v in by_path.items():
        if all(f in k for f in frags):
            return str(v.spec)
    raise KeyError(frags)

# embed (vocab 512 % 4 == 0) -> vocab sharded
out["embed_spec"] = str(spec_of("embed"))
# stacked attention wq kernel: leading periods axis None, out dim sharded
out["wq_spec"] = str(spec_of("wq", "kernel"))
# moe experts (8, d, f): experts sharded over model
out["moe_spec"] = str(spec_of("mlp", "w_gate"))

# --- 2. compressed psum == plain mean within int8 tolerance ----------------
grads = {"a": jnp.asarray(np.random.default_rng(0).standard_normal((8, 16)), jnp.float32),
         "b": jnp.asarray(np.random.default_rng(1).standard_normal((4,)), jnp.float32)}
dp_mesh = make_mesh((8,), ("data",))
red = dp_mean_grads_compressed(dp_mesh, grads, axis_name="data")
# all shards identical here (replicated input) -> mean == value
err = max(float(jnp.max(jnp.abs(red[k] - grads[k]))) for k in grads)
out["psum_err"] = err

# --- 3. elastic restore: save under mesh (2,4), restore under (4,2) -------
params = m.init(jax.random.PRNGKey(0))
train_step, opt, _ = steps_mod.make_train_step(cfg)
opt_state = opt.init(params)
ckpt.save_checkpoint("/tmp/elastic_ckpt", 3, params, opt_state)
mesh2 = make_mesh((4, 2), ("data", "model"))
p_t = jax.eval_shape(m.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
o_t = jax.eval_shape(opt.init, p_t)
p_sh2 = shd.param_shardings(mesh2, p_t)
o_sh2 = opt.state_shardings(mesh2, p_sh2, p_t)
p2, o2, extra, step = ckpt.restore_checkpoint(
    "/tmp/elastic_ckpt", None, p_t, o_t, shardings=(p_sh2, o_sh2))
out["elastic_step"] = step
leaf0 = jax.tree.leaves(params)[0]
leaf2 = jax.tree.leaves(p2)[0]
out["elastic_exact"] = bool(jnp.array_equal(leaf0, leaf2))
out["elastic_sharded"] = str(jax.tree.leaves(p2)[0].sharding.mesh.shape)

# --- 4. sharded train step lowers + runs on the small mesh ----------------
shd.enable_constraints(mesh)
b_batch = {
    "tokens": jnp.zeros((8, 16), jnp.int32),
    "labels": jnp.zeros((8, 16), jnp.int32),
    "loss_mask": jnp.ones((8, 16), jnp.float32),
}
b_sh = shd.batch_shardings(mesh, jax.eval_shape(lambda: b_batch))
p_sh = shd.param_shardings(mesh, params)
o_sh = opt.state_shardings(mesh, p_sh, params)
params_d = jax.tree.map(jax.device_put, params, p_sh)
opt_d = jax.tree.map(jax.device_put, opt_state, o_sh)
step_fn = jax.jit(train_step, in_shardings=(p_sh, o_sh, b_sh, None),
                  out_shardings=(p_sh, o_sh, None))
p_new, o_new, metrics = step_fn(params_d, opt_d, b_batch, jnp.asarray(0))
out["train_loss"] = float(metrics["loss"])
shd.enable_constraints(None)

# --- 5. kv-seq-shard rule flips the cache spec ------------------------------
leafK = jax.ShapeDtypeStruct((8, 32, 4, 16), jnp.float32)
spec_default = shd.cache_spec_for("caches/stack/0/self/k",
    jax.ShapeDtypeStruct((2, 8, 32, 4, 16), jnp.float32), mesh)
os.environ["REPRO_KV_SEQ_SHARD"] = "1"
spec_seq = shd.cache_spec_for("caches/stack/0/self/k",
    jax.ShapeDtypeStruct((2, 8, 32, 4, 16), jnp.float32), mesh)
os.environ["REPRO_KV_SEQ_SHARD"] = "0"
out["cache_default"] = str(spec_default)
out["cache_seq"] = str(spec_seq)

print(json.dumps(out))
"""


@pytest.mark.slow
def test_distribution_layer():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("REPRO_KV_SEQ_SHARD", None)
    res = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=1200,
    )
    assert res.returncode == 0, res.stderr[-4000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    # 1. rules
    assert "model" in out["embed_spec"]
    # stacked wq: leading periods axis unsharded, out dim on model
    assert out["wq_spec"].startswith("PartitionSpec(None,") and "model" in out["wq_spec"]
    assert "model" in out["moe_spec"]
    # 2. compressed psum: identical shards -> reconstruction within q-step
    assert out["psum_err"] < 0.05
    # 3. elastic restore
    assert out["elastic_step"] == 3
    assert out["elastic_exact"]
    assert "4" in out["elastic_sharded"]
    # 4. sharded train step executes
    assert out["train_loss"] > 0 and out["train_loss"] < 20
    # 5. cache layout knob
    assert "model" in out["cache_seq"]
    assert out["cache_seq"] != out["cache_default"]
