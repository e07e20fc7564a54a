"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a topology that is only described, and refuses what the chip would
refuse (here: kernels that need more scoped VMEM than
sto_step.VMEM_LIMIT_BYTES). These tests pin, at the serve grid's widths,
that the VMEM fit check behind impl="auto" and compile_plan agrees with
that compiler, and that the kernels the dispatch can choose compile.

The topology is described inside a module fixture: only one process at a
time may load the TPU library, so nothing here touches it while modules
are imported, and every compile runs in the test's own process.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops, sto_step
from repro.kernels.ref import NP

E = 256  # the serve grid's slot width
HOLD = 5  # the serve grid's hold window
K = 8  # the serve grid's chunk length


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """The first described chip, compiled for as the chip runs: JAX's
    persistent cache off (entries compiled for a described chip cannot be
    read back without one) and 64-bit mode off (other test modules turn it
    on for the whole process; the kernels' index maps would then be int64,
    which Mosaic does not lower)."""
    from jax.experimental.compilation_cache import compilation_cache

    saved = {
        k: getattr(jax.config, k)
        for k in ("jax_enable_compilation_cache", "jax_enable_x64")
    }
    for k in saved:
        jax.config.update(k, False)
    compilation_cache.reset_cache()
    yield topo.devices[0]
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def _shape(chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=SingleDeviceSharding(chip))


def test_tiled_step_compiles_at_1024(chip):
    n = 1024
    step = jax.jit(
        lambda m, w, p, h: sto_step.rk4_tiled_step(m, w, p, 1e-11, h_in=h)
    )
    hlo = step.lower(
        _shape(chip, (3, n, E)), _shape(chip, (n, n)),
        _shape(chip, (NP, E)), _shape(chip, (n, E)),
    ).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize(
    "impl, precision, k_ticks, admitted, refused",
    [
        # admitted = the largest padded N the check passes at the serve
        # grid's E/hold/K; refused = the next width, which the compiler
        # refuses for scoped VMEM
        ("fused", None, 1, 512, 1024),
        ("chunk", None, K, 256, 512),
        ("chunk", "bf16_coupling", K, 256, 512),
    ],
)
def test_fit_check_matches_compiler(
    chip, monkeypatch, impl, precision, k_ticks, admitted, refused
):
    # the check compiles for jax.devices()[0]: point it at the described chip
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [chip])
    kw = dict(precision=precision, k_ticks=k_ticks, n_inner=HOLD)
    assert ops.kernel_vmem_refusal(impl, admitted, E, **kw) is None
    why = ops.kernel_vmem_refusal(impl, refused, E, **kw)
    assert why is not None and "vmem" in why.lower()


def test_tick_chunk_jit_compiles_tiled_at_1024(chip):
    """The serving worker itself, impl named: code that asks
    jax.default_backend() still sees the CPU here."""
    n = 1024
    fn = ops._tick_chunk_planes_jit.lower(
        _shape(chip, (3, n, E)), _shape(chip, (n, n)), _shape(chip, (NP, E)),
        _shape(chip, (K, n, E)), _shape(chip, (K, E), jnp.bool_),
        dt=1e-11, hold_steps=HOLD, impl="tiled", n_inner=HOLD,
        block_n=ops.LANE, block_e=ops.LANE, interpret=False,
    )
    assert "tpu_custom_call" in fn.compile().as_text()
