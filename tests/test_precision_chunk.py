"""ExecPlan.precision policies + the chunk-resident "chunk" impl.

Pins the PR-5 contracts:
  - precision=None / "highest" plans are BIT-exact against plans that
    predate the field, on every impl (the acceptance bar's "bit-exact vs
    current main on the scan backend" — and stronger: also on ref/chunk).
  - impl="chunk" (the chunk-resident K x hold x 4-stage region) agrees
    with the ref oracle to the bit on CPU, masks included; the Pallas
    rk4_chunk kernel agrees in interpret mode.
  - "bf16_coupling"/"mixed" deviate only at reduced-precision scale, and
    the task-level guardrail holds: NARMA-10 NMSE under "mixed" within
    10% of f32.
  - dispatch is precision-keyed with a fallback to the bit-exact entry;
    measure_impl_latency reports failed candidates instead of swallowing
    them; the persisted dispatch table round-trips v1 -> v2 without drops
    or collisions.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import api
from repro.api import ExecPlan, compile_plan, make_spec
from repro.api.plan import PLAN_PRECISIONS
from repro.kernels import dispatch_table, ops
from repro.kernels import ref as kref
from repro.kernels import rls as krls
from repro.kernels import sto_step
from repro.core import constants

N, N_IN, HOLD, E, K = 24, 2, 4, 4, 3
DTYPE = jnp.float32


def _spec(n=N):
    return make_spec(n=n, n_in=N_IN, hold_steps=HOLD, dtype=DTYPE, seed=3)


def _chunk_inputs(spec, e=E, k=K, seed=0):
    rng = np.random.default_rng(seed)
    m0 = ops.to_planes(jnp.broadcast_to(spec.m0, (e, spec.n, 3)))
    u_block = rng.uniform(0.0, 0.5, (k, e, spec.n_in)).astype(np.float32)
    mask = np.ones((k, e), bool)
    mask[1, 1] = False  # a mid-chunk freeze, so masking is exercised
    return m0, jnp.asarray(u_block), jnp.asarray(mask)


class TestPrecisionValidation:
    def test_plan_precisions(self):
        for p in PLAN_PRECISIONS:
            assert ExecPlan(precision=p).precision == p

    def test_unknown_precision_rejected(self):
        with pytest.raises(ValueError, match="precision"):
            ExecPlan(precision="fp8")

    def test_scan_refuses_reduced_precision(self):
        with pytest.raises(ValueError, match="bit-exact oracle"):
            ExecPlan(impl="scan", precision="mixed")
        # the bit-exact aliases are fine on scan
        assert ExecPlan(impl="scan", precision="highest").effective_precision is None

    def test_effective_gather_dtype_subsumes_gather_dtype(self):
        assert ExecPlan().effective_gather_dtype is None
        assert ExecPlan(precision="bf16_coupling").effective_gather_dtype == jnp.bfloat16
        assert ExecPlan(precision="mixed").effective_gather_dtype == jnp.bfloat16
        # an explicit gather_dtype wins (backward compat)
        assert (
            ExecPlan(precision="mixed", gather_dtype=jnp.float16).effective_gather_dtype
            == jnp.float16
        )

    def test_chunk_requires_rk4(self):
        spec = make_spec(n=8, n_in=1, hold_steps=2, dtype=DTYPE, tableau="euler")
        with pytest.raises(ValueError, match="RK4 only"):
            compile_plan(spec, ExecPlan(impl="chunk"))


class TestBitExactDefault:
    """precision=None / "highest" must not perturb a single bit."""

    @pytest.mark.parametrize("impl", ["scan", "ref", "chunk"])
    def test_drive_batch_bit_exact(self, impl):
        spec = _spec()
        u = np.random.default_rng(1).uniform(0, 0.5, (6, N_IN)).astype(np.float32)
        base = compile_plan(spec, ExecPlan(impl=impl, ensemble=E)).drive_batch(u)
        for precision in (None, "highest"):
            if impl == "scan" and precision is None:
                continue  # identical object-level default; nothing to compare
            got = compile_plan(
                spec, ExecPlan(impl=impl, ensemble=E, precision=precision)
            ).drive_batch(u)
            assert np.array_equal(np.asarray(base[0]), np.asarray(got[0]))
            assert np.array_equal(np.asarray(base[1]), np.asarray(got[1]))

    def test_tick_chunk_bit_exact_scan(self):
        spec = _spec()
        m0, u_block, mask = _chunk_inputs(spec)
        base = compile_plan(
            spec, ExecPlan(impl="scan", ensemble=E, chunk_ticks=K)
        ).tick_chunk(m0, u_block, mask)
        got = compile_plan(
            spec, ExecPlan(impl="scan", ensemble=E, chunk_ticks=K, precision="highest")
        ).tick_chunk(m0, u_block, mask)
        assert np.array_equal(np.asarray(base[0]), np.asarray(got[0]))
        assert np.array_equal(np.asarray(base[1]), np.asarray(got[1]))


class TestChunkImpl:
    def test_chunk_matches_ref_tick_chunk_bitwise(self):
        spec = _spec()
        m0, u_block, mask = _chunk_inputs(spec)
        ref = compile_plan(
            spec, ExecPlan(impl="ref", ensemble=E, chunk_ticks=K)
        ).tick_chunk(m0, u_block, mask)
        chunk = compile_plan(
            spec, ExecPlan(impl="chunk", ensemble=E, chunk_ticks=K)
        ).tick_chunk(m0, u_block, mask)
        assert np.array_equal(np.asarray(ref[0]), np.asarray(chunk[0]))
        assert np.array_equal(np.asarray(ref[1]), np.asarray(chunk[1]))

    def test_chunk_frozen_lane_bit_identical(self):
        spec = _spec()
        m0, u_block, _ = _chunk_inputs(spec)
        mask = np.ones((K, E), bool)
        mask[:, 2] = False  # lane 2 frozen for the whole chunk
        sim = compile_plan(spec, ExecPlan(impl="chunk", ensemble=E, chunk_ticks=K))
        mT, _ = sim.tick_chunk(m0, u_block, jnp.asarray(mask))
        assert np.array_equal(np.asarray(mT[:, :, 2]), np.asarray(m0[:, :, 2]))

    def test_chunk_learn_matches_ref_learn_bitwise(self):
        spec = _spec()
        m0, u_block, mask = _chunk_inputs(spec)
        rng = np.random.default_rng(5)
        targets = rng.uniform(0, 0.5, (K, E, 1)).astype(np.float32)
        outs = {}
        for impl in ("ref", "chunk"):
            sim = compile_plan(
                spec,
                ExecPlan(impl=impl, ensemble=E, chunk_ticks=K, learn="rls",
                         learn_reg=1e-2),
            )
            p0, w0 = sim.init_learn_state()
            outs[impl] = sim.tick_chunk(
                m0, u_block, mask, targets=targets, learn_state=(p0, w0)
            )
        for a, b in zip(outs["ref"][:2], outs["chunk"][:2]):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(outs["ref"][2], outs["chunk"][2]):  # (P, W)
            assert np.array_equal(np.asarray(a), np.asarray(b))
        assert np.array_equal(np.asarray(outs["ref"][3]), np.asarray(outs["chunk"][3]))

    def test_chunk_per_window_pallas_path_matches_ref(self):
        """impl="chunk" is first-class at the per-hold-window entry points
        too: on TPU (here: interpret mode) it runs the Pallas rk4_chunk as
        a one-tick chunk, so a dispatch winner measured on the chunked
        shape stays a sane choice for tick()/drive()/integrate()."""
        spec = make_spec(n=128, n_in=1, hold_steps=2, dtype=DTYPE)
        u = np.random.default_rng(3).uniform(0, 0.5, (2, 1)).astype(np.float32)
        ref = compile_plan(spec, ExecPlan(impl="ref", ensemble=2)).drive_batch(u)
        chk = compile_plan(
            spec, ExecPlan(impl="chunk", ensemble=2, interpret=True)
        ).drive_batch(u)
        np.testing.assert_allclose(
            np.asarray(ref[1]), np.asarray(chk[1]), atol=1e-6
        )

    def test_pallas_rk4_chunk_interpret_matches_oracle(self):
        n, e, k, hold = 128, 128, 2, 3
        rng = np.random.default_rng(7)
        w = jnp.asarray(rng.standard_normal((n, n)) * 0.05, DTYPE)
        pv = kref.pack_params(constants.default_params(DTYPE), e, DTYPE)
        m = ops.to_planes(
            jnp.broadcast_to(constants.initial_magnetization(n, DTYPE), (e, n, 3))
        )
        h_block = jnp.asarray(rng.standard_normal((k, n, e)) * 0.1, DTYPE)
        mask = np.ones((k, e), bool)
        mask[0, 3:9] = False
        oracle = kref.rk4_chunk_planes(m, w, pv, 1e-11, hold, h_block, jnp.asarray(mask))
        kernel = sto_step.rk4_chunk(
            m, w, pv, 1e-11, hold, h_block,
            jnp.asarray(mask, DTYPE), interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(oracle[0]), np.asarray(kernel[0]), atol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(oracle[1]), np.asarray(kernel[1]), atol=1e-6
        )

    def test_tick_chunk_entry_auto_resolves_precision_key(self):
        """sto_rk4_tick_chunk_planes consults the precision-keyed table."""
        spec = _spec()
        m0, _, mask = _chunk_inputs(spec)
        h_block = jnp.zeros((K, spec.n, E), DTYPE)
        pv = kref.pack_params(constants.default_params(DTYPE), E, DTYPE)
        try:
            ops.register_impl_choice(
                spec.n, E, "chunk", platform=jax.default_backend(),
                precision="bf16_coupling",
            )
            out = ops.sto_rk4_tick_chunk_planes(
                m0, spec.w_cp, pv, float(spec.dt), HOLD, h_block, mask,
                impl="auto", precision="bf16_coupling",
            )
            assert out[0].shape == m0.shape
            assert out[1].shape == (K, spec.n, E)
        finally:
            ops._LATENCY_TABLE.clear()


class TestReducedPrecision:
    @pytest.mark.parametrize("impl", ["ref", "chunk"])
    @pytest.mark.parametrize("precision", ["bf16_coupling", "mixed"])
    def test_reduced_close_but_not_required_equal(self, impl, precision):
        spec = _spec()
        m0, u_block, mask = _chunk_inputs(spec)
        f32 = compile_plan(
            spec, ExecPlan(impl=impl, ensemble=E, chunk_ticks=K)
        ).tick_chunk(m0, u_block, mask)
        red = compile_plan(
            spec, ExecPlan(impl=impl, ensemble=E, chunk_ticks=K, precision=precision)
        ).tick_chunk(m0, u_block, mask)
        # reduced-precision coupling perturbs a ~1 Oe field against ~600 Oe
        # local terms: states stay close at bf16 scale over a few ticks
        np.testing.assert_allclose(
            np.asarray(f32[1]), np.asarray(red[1]), atol=2e-3
        )
        # the state carry stays f32
        assert red[0].dtype == DTYPE

    def test_mixed_learn_close_to_f32(self):
        spec = _spec()
        m0, u_block, mask = _chunk_inputs(spec)
        targets = np.random.default_rng(9).uniform(0, 0.5, (K, E, 1)).astype(np.float32)
        outs = {}
        for precision in (None, "mixed"):
            sim = compile_plan(
                spec,
                ExecPlan(impl="ref", ensemble=E, chunk_ticks=K, learn="rls",
                         learn_reg=1e-2, precision=precision),
            )
            outs[precision] = sim.tick_chunk(
                m0, u_block, mask, targets=targets,
                learn_state=sim.init_learn_state(),
            )
        w_f32 = np.asarray(outs[None][2][1])
        w_mix = np.asarray(outs["mixed"][2][1])
        assert np.all(np.isfinite(w_mix))
        np.testing.assert_allclose(w_f32, w_mix, atol=2e-3)

    def test_rls_update_upcasts_reduced_features(self):
        p0, w0 = krls.rls_init(2, 5, 1, 1e-2, jnp.float32)
        x = jnp.ones((2, 5), jnp.bfloat16)
        y = jnp.ones((2, 1), jnp.bfloat16)
        p1, w1, pred = krls.rls_update(p0, w0, x, y, jnp.ones(2, bool), 1.0)
        assert p1.dtype == jnp.float32 and w1.dtype == jnp.float32
        assert pred.dtype == jnp.float32

    def test_narma10_nmse_guardrail_mixed_within_10pct(self):
        """The acceptance guardrail: NARMA-10 NMSE under "mixed" within 10%
        of the f32 pipeline (same spec, same readout protocol)."""
        from repro.core.constants import default_params
        from repro.core.reservoir import fit_ridge, nmse, predict
        from repro.core import tasks

        params = default_params(DTYPE)._replace(a_in=jnp.float32(300.0))
        spec = make_spec(
            n=24, n_in=1, hold_steps=20, dtype=DTYPE, params=params
        )
        train, test, washout = 260, 80, 40
        u, y = tasks.narma_series(train + test, order=10, seed=0)
        u = u.astype(np.float32)[:, None]
        y = y.astype(np.float32)[:, None]
        scores = {}
        for precision in (None, "mixed"):
            sim = compile_plan(
                spec, ExecPlan(impl="ref", ensemble=1, precision=precision)
            )
            m_end, states = sim.drive_batch(u[:train])
            states = states[:, 0, :]
            # held-out evaluation resumes from the training endpoint
            _, test_states = sim.drive_batch(u[train:], m0=m_end)
            ro = fit_ridge(states, y[:train], washout=washout, reg=1e-2)
            pred = predict(ro._replace(washout=0), test_states[:, 0, :])
            scores[precision] = float(nmse(pred, jnp.asarray(y[train:])))
        assert scores[None] < 1.0, scores  # the task is actually learned
        assert scores["mixed"] <= scores[None] * 1.10, scores


class TestMeasureAndDispatch:
    def test_measure_impl_latency_records_failures(self):
        try:
            with pytest.warns(RuntimeWarning, match="excluded from dispatch"):
                t = ops.measure_impl_latency(
                    8, 4, n_steps=2, reps=1,
                    candidates=("ref", "fused"),  # fused cannot run on CPU
                )
            assert isinstance(t["ref"], float)
            assert "fused" in t["failed"]
            assert "ref" not in t["failed"]
            # the winner registration skipped the failed impl
            assert ops.choose_impl(8, 4) == "ref"
        finally:
            ops._LATENCY_TABLE.clear()

    def test_measure_all_failed_registers_nothing(self):
        try:
            with pytest.warns(RuntimeWarning):
                t = ops.measure_impl_latency(
                    8, 4, n_steps=2, reps=1, candidates=("fused", "tiled")
                )
            assert set(t) == {"failed"}
            assert ops.latency_table() == {}
        finally:
            ops._LATENCY_TABLE.clear()

    def test_measure_on_tpu_excludes_refused_and_raises_on_admitted_failure(
        self, monkeypatch
    ):
        """On a TPU a kernel the fit check refuses is left out up front,
        and one it admitted that then fails raises instead of quietly
        handing the table to whatever survived."""
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(
            ops, "kernel_vmem_refusal",
            lambda impl, *a, **k: "vmem" if impl == "tiled" else None,
        )
        try:
            with pytest.raises(RuntimeError, match="passed the VMEM fit check"):
                # fused is admitted, then cannot compile: no TPU behind it
                ops.measure_impl_latency(
                    8, 4, n_steps=2, reps=1, candidates=("tiled", "fused")
                )
            t = ops.measure_impl_latency(
                8, 4, n_steps=2, reps=1, candidates=("ref", "tiled"),
                register=False,
            )
            assert isinstance(t["ref"], float)
            assert t["excluded"] == {"tiled": "vmem"}
        finally:
            ops._LATENCY_TABLE.clear()

    def test_compile_plan_refuses_a_kernel_that_does_not_fit(self, monkeypatch):
        monkeypatch.setattr(
            ops, "kernel_vmem_refusal", lambda *a, **k: "Ran out of vmem"
        )
        spec = make_spec(n=8, n_in=1, hold_steps=2, dtype=jnp.float32)
        for impl in ("fused", "tiled", "chunk"):
            with pytest.raises(ValueError, match="scoped VMEM limit"):
                compile_plan(spec, ExecPlan(impl=impl, ensemble=4))
        # interpret mode compiles no Mosaic kernel: nothing to refuse
        compile_plan(spec, ExecPlan(impl="fused", ensemble=4, interpret=True))

    def test_precision_keyed_choice_with_fallback(self):
        try:
            ops.register_impl_choice(64, 8, "tiled", platform="faux")
            # unmeasured reduced precision falls back to the bit-exact entry
            assert ops.choose_impl(64, 8, platform="faux", precision="mixed") == "tiled"
            ops.register_impl_choice(64, 8, "chunk", platform="faux", precision="mixed")
            assert ops.choose_impl(64, 8, platform="faux", precision="mixed") == "chunk"
            # and the bit-exact entry is untouched
            assert ops.choose_impl(64, 8, platform="faux") == "tiled"
        finally:
            ops._LATENCY_TABLE.clear()

    def test_v1_table_migrates_without_drops_or_collisions(self, tmp_path):
        """Satellite: the old (pre-precision) table format keeps loading —
        entries land on the bit-exact default key, round-trip to v2, and
        coexist with new precision-aware entries at the same shape."""
        v1 = {
            "format": "repro-dispatch-table-v1",
            "platform": "faux",
            "entries": [
                {"n_pad": 128, "e_pad": 128, "itemsize": 4, "impl": "ref"},
                {"n_pad": 1024, "e_pad": 256, "itemsize": 4, "impl": "tiled"},
            ],
        }
        path = tmp_path / "dispatch_table.faux.json"
        path.write_text(json.dumps(v1))
        try:
            assert dispatch_table.load_table(str(path), platform="faux") == 2
            table = ops.latency_table()
            assert table[("faux", 128, 128, 4, "highest")] == "ref"
            assert table[("faux", 1024, 256, 4, "highest")] == "tiled"
            # a precision-aware entry at the same shape must NOT collide
            ops.register_impl_choice(
                1024, 256, "chunk", platform="faux", precision="mixed"
            )
            out = tmp_path / "dispatch_table.faux.v2.json"
            dispatch_table.save_table(str(out), platform="faux")
            payload = json.loads(out.read_text())
            assert payload["format"] == "repro-dispatch-table-v2"
            assert len(payload["entries"]) == 3
            ops._LATENCY_TABLE.clear()
            assert dispatch_table.load_table(str(out), platform="faux") == 3
            table = ops.latency_table()
            assert table[("faux", 1024, 256, 4, "highest")] == "tiled"
            assert table[("faux", 1024, 256, 4, "mixed")] == "chunk"
        finally:
            ops._LATENCY_TABLE.clear()

    def test_unknown_table_format_rejected(self, tmp_path):
        path = tmp_path / "dispatch_table.faux.json"
        path.write_text(json.dumps({"format": "repro-dispatch-table-v99",
                                    "platform": "faux", "entries": []}))
        with pytest.raises(ValueError, match="unknown dispatch-table format"):
            dispatch_table.load_table(str(path), platform="faux")

    def test_committed_cpu_table_still_loads(self):
        """The committed v1 dispatch_table.cpu.json (or its v2 refresh)
        keeps loading through the migration path."""
        committed = dispatch_table.table_path("cpu")
        assert os.path.exists(committed)
        try:
            ops._LATENCY_TABLE.clear()
            dispatch_table.reset_loaded()
            n = dispatch_table.ensure_loaded("cpu")
            assert n > 0
            assert all(len(k) == 5 for k in ops.latency_table())
        finally:
            ops._LATENCY_TABLE.clear()
            dispatch_table.reset_loaded()


class TestServingWithPrecision:
    def test_engine_serves_mixed_precision_sessions(self):
        from repro.serve.reservoir import ReservoirEngine, StreamSession

        spec = _spec()
        rng = np.random.default_rng(13)
        results = {}
        for precision in (None, "mixed"):
            eng = ReservoirEngine(
                compile_plan(
                    spec,
                    ExecPlan(impl="chunk", ensemble=E, chunk_ticks=K,
                             precision=precision),
                )
            )
            assert eng.precision == ("highest" if precision is None else "mixed")
            sessions = [
                StreamSession(
                    sid=i,
                    u_seq=np.random.default_rng(i).uniform(
                        0, 0.5, (6, N_IN)
                    ).astype(np.float32),
                )
                for i in range(E + 2)  # forces a retire/admit wave
            ]
            results[precision] = eng.run(sessions)
        assert set(results[None]) == set(results["mixed"])
        for sid in results[None]:
            np.testing.assert_allclose(
                results[None][sid].states, results["mixed"][sid].states,
                atol=2e-3,
            )

    def test_engine_precision_is_a_plan_decision(self):
        from repro.serve.reservoir import ReservoirEngine

        sim = compile_plan(_spec(), ExecPlan(ensemble=2))
        with pytest.raises(ValueError, match="ExecPlan decisions"):
            ReservoirEngine(sim, precision="mixed")

    def test_engine_template_route_accepts_precision(self):
        from repro.serve.reservoir import ReservoirEngine

        eng = ReservoirEngine(
            _spec(), num_slots=2, backend="ref", precision="bf16_coupling"
        )
        assert eng.precision == "bf16_coupling"
