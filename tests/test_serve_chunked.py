"""Chunked pipelined serving: parity, autoscaling, and plan validation.

The contracts this file pins:

  - `CompiledSim.tick_chunk` (K > 1) is BIT-EXACT against K sequential
    `tick` calls on the scan backend — including per-tick masks that turn a
    lane on mid-chunk (admit) or off mid-chunk (retire) — and
    tolerance-equal on the planes backends (ref, and fused/tiled in
    interpret mode).
  - `ReservoirEngine.run` (pipelined chunks) is bit-exact against the
    synchronous per-tick `step()` loop on the scan backend: states,
    readout outputs, and final_m.
  - Autoscaling migrates running sessions between bucketed plans without
    perturbing their dynamics; scheduler stats expose the load signals.
  - `pop_results` / `max_retained` bound retired-session retention.
  - The chunk readout is ONE jitted program, bit-exact against K per-tick
    readouts, so `_launch_chunk` returns while the chunk still runs
    (`EngineStats.launches_overlapped`).
  - ExecPlan rejects chunk_ticks < 1 / non-int and non-dtype gather_dtype.
"""

import dataclasses
import pickle

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExecPlan, compile_plan, make_spec
from repro.core import Readout, drive, fit_ridge, make_reservoir
from repro.kernels import ops
from repro.serve.reservoir import (
    EngineStats,
    ReservoirEngine,
    StreamSession,
    _apply_readouts,
    _apply_readouts_chunk,
    _bucket_slots,
)
from repro.serve.scheduler import AutoscalePolicy, QueueDepthPolicy, SlotScheduler

ATOL = 5e-5  # tests/test_kernels_sto.py's f32 tolerance


def _chunk_inputs(k, e, n_in, seed=0):
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.uniform(0.0, 0.5, (k, e, n_in)).astype(np.float32))
    # per-tick masks with mid-chunk admits (False -> True) and retires
    # (True -> False): lane 0 always on, lane 1 admitted at tick 2, lane 2
    # retired after tick 1, remaining lanes random
    mask = rng.uniform(size=(k, e)) > 0.4
    mask[:, 0] = True
    if e > 1:
        mask[:, 1] = [t >= 2 for t in range(k)]
    if e > 2:
        mask[:, 2] = [t < 2 for t in range(k)]
    return u, jnp.asarray(mask)


def _sequential_ticks(sim, m0, u, mask):
    m, states = m0, []
    for t in range(u.shape[0]):
        m, s = sim.tick(m, u[t], lane_mask=mask[t])
        states.append(s)
    return m, jnp.stack(states)


class TestTickChunkParity:
    def test_scan_bitexact_vs_per_tick(self):
        spec = make_spec(n=8, n_in=1, hold_steps=5, dtype=jnp.float32)
        sim = compile_plan(spec, ExecPlan(impl="scan", ensemble=4, chunk_ticks=6))
        u, mask = _chunk_inputs(6, 4, 1)
        m0 = ops.to_planes(jnp.broadcast_to(spec.m0, (4, 8, 3)))
        m_seq, s_seq = _sequential_ticks(sim, m0, u, mask)
        m_chk, s_chk = sim.tick_chunk(m0, u, mask)
        np.testing.assert_array_equal(np.asarray(m_chk), np.asarray(m_seq))
        np.testing.assert_array_equal(np.asarray(s_chk), np.asarray(s_seq))

    @pytest.mark.parametrize(
        "impl,interpret", [("ref", False), ("fused", True), ("tiled", True)]
    )
    def test_planes_impls_close_to_per_tick(self, impl, interpret):
        spec = make_spec(n=8, n_in=1, hold_steps=3, dtype=jnp.float32)
        sim = compile_plan(
            spec, ExecPlan(impl=impl, ensemble=3, chunk_ticks=4, interpret=interpret)
        )
        u, mask = _chunk_inputs(4, 3, 1, seed=1)
        m0 = ops.to_planes(jnp.broadcast_to(spec.m0, (3, 8, 3)))
        m_seq, s_seq = _sequential_ticks(sim, m0, u, mask)
        m_chk, s_chk = sim.tick_chunk(m0, u, mask)
        np.testing.assert_allclose(np.asarray(m_chk), np.asarray(m_seq), atol=ATOL)
        np.testing.assert_allclose(np.asarray(s_chk), np.asarray(s_seq), atol=ATOL)

    def test_mid_chunk_admit_equals_boundary_admit(self):
        """A lane spliced at the chunk boundary but masked until tick k
        integrates exactly as if the chunk had started at tick k — the
        masking rule mid-chunk admissions rely on."""
        spec = make_spec(n=8, n_in=1, hold_steps=4, dtype=jnp.float32)
        sim = compile_plan(spec, ExecPlan(impl="scan", ensemble=1, chunk_ticks=4))
        rng = np.random.default_rng(2)
        u = jnp.asarray(rng.uniform(0.0, 0.5, (4, 1, 1)).astype(np.float32))
        m0 = ops.to_planes(jnp.broadcast_to(spec.m0, (1, 8, 3)))
        mask = jnp.asarray([[False], [False], [True], [True]])
        m_late, s_late = sim.tick_chunk(m0, u, mask)
        m_short, s_short = sim.tick_chunk(m0, u[2:], None)
        np.testing.assert_array_equal(np.asarray(m_late), np.asarray(m_short))
        np.testing.assert_array_equal(
            np.asarray(s_late[2:]), np.asarray(s_short)
        )
        # masked-off ticks echo the frozen (admission) state
        np.testing.assert_array_equal(np.asarray(s_late[0]), np.asarray(m0[0]))

    def test_shared_mask_row_broadcasts(self):
        spec = make_spec(n=6, n_in=1, hold_steps=3, dtype=jnp.float32)
        sim = compile_plan(spec, ExecPlan(impl="scan", ensemble=2, chunk_ticks=3))
        rng = np.random.default_rng(3)
        u = jnp.asarray(rng.uniform(0.0, 0.5, (3, 2, 1)).astype(np.float32))
        m0 = ops.to_planes(jnp.broadcast_to(spec.m0, (2, 6, 3)))
        row = jnp.asarray([True, False])
        m_a, s_a = sim.tick_chunk(m0, u, row)
        m_b, s_b = sim.tick_chunk(m0, u, jnp.broadcast_to(row[None, :], (3, 2)))
        np.testing.assert_array_equal(np.asarray(m_a), np.asarray(m_b))
        np.testing.assert_array_equal(np.asarray(s_a), np.asarray(s_b))

    def test_rejects_bad_shapes(self):
        spec = make_spec(n=6, n_in=1, hold_steps=3, dtype=jnp.float32)
        sim = compile_plan(spec, ExecPlan(impl="scan", ensemble=2))
        m0 = ops.to_planes(jnp.broadcast_to(spec.m0, (2, 6, 3)))
        with pytest.raises(ValueError, match="u_block"):
            sim.tick_chunk(m0, jnp.zeros((4, 3, 1), jnp.float32))
        with pytest.raises(ValueError, match="lane_mask"):
            sim.tick_chunk(
                m0, jnp.zeros((4, 2, 1), jnp.float32), jnp.zeros((3, 2), bool)
            )


class TestEnginePipelinedParity:
    def _mk_sessions(self, res, count, rng, lengths, with_readout=True):
        sessions, clones, refs = [], [], {}
        for sid in range(count):
            t = lengths[sid % len(lengths)]
            u = rng.uniform(0.0, 0.5, size=(t, 1)).astype(np.float32)
            ro = None
            if with_readout:
                _, states = drive(res, jnp.asarray(u))
                ro = fit_ridge(states, jnp.asarray(u[:, 0]), washout=2, reg=1e-3)
                refs[sid] = states
            sessions.append(StreamSession(sid=sid, u_seq=u, readout=ro))
            clones.append(StreamSession(sid=sid, u_seq=u.copy(), readout=ro))
        return sessions, clones, refs

    def test_run_bitexact_vs_step_loop_scan(self):
        """The pipelined chunked path and the synchronous per-tick path are
        the same numbers, bit for bit, on the scan backend — states,
        outputs, final_m — across slot turnover and mid-chunk finishes."""
        res = make_reservoir(n=12, n_in=1, hold_steps=8, dtype=jnp.float32)
        rng = np.random.default_rng(0)
        sessions, clones, _ = self._mk_sessions(res, 9, rng, (5, 9, 14))
        chunked = ReservoirEngine(res, num_slots=3, backend="scan", chunk_ticks=4)
        r_chunk = chunked.run(sessions)
        sync = ReservoirEngine(res, num_slots=3, backend="scan")
        for s in clones:
            sync.submit(s)
        while sync.scheduler.has_work():
            sync.step()
        assert set(r_chunk) == set(sync.results)
        for sid, r in r_chunk.items():
            ref = sync.results[sid]
            np.testing.assert_array_equal(np.asarray(r.states), np.asarray(ref.states))
            np.testing.assert_array_equal(
                np.asarray(r.outputs), np.asarray(ref.outputs)
            )
            np.testing.assert_array_equal(
                np.asarray(r.final_m), np.asarray(ref.final_m)
            )

    def test_chunk_ticks_one_matches_step(self):
        """K=1 pipelining (bulk harvest, no per-slot slicing) is still the
        per-tick math."""
        res = make_reservoir(n=10, n_in=1, hold_steps=6, dtype=jnp.float32)
        rng = np.random.default_rng(1)
        sessions, clones, _ = self._mk_sessions(res, 5, rng, (4, 7), with_readout=False)
        a = ReservoirEngine(res, num_slots=2, backend="scan", chunk_ticks=1)
        ra = a.run(sessions)
        b = ReservoirEngine(res, num_slots=2, backend="scan")
        for s in clones:
            b.submit(s)
        while b.scheduler.has_work():
            b.step()
        for sid in ra:
            np.testing.assert_array_equal(
                np.asarray(ra[sid].states), np.asarray(b.results[sid].states)
            )

    def test_ref_backend_chunked_matches_solo(self):
        """Chunked serving on the planes default stays within kernel
        tolerance of solo drive."""
        res = make_reservoir(n=12, n_in=1, hold_steps=8, dtype=jnp.float32)
        rng = np.random.default_rng(2)
        sessions, _, refs = self._mk_sessions(res, 8, rng, (6, 9, 12))
        eng = ReservoirEngine(res, num_slots=4, backend="ref", chunk_ticks=4)
        results = eng.run(sessions)
        for sid, r in results.items():
            np.testing.assert_allclose(
                np.asarray(r.states), np.asarray(refs[sid]), atol=ATOL
            )

    def test_resume_across_engines(self):
        """final_m from a chunked run resumes bit-exactly (scan)."""
        res = make_reservoir(n=8, n_in=1, hold_steps=10, dtype=jnp.float32)
        u = np.random.default_rng(3).uniform(0, 0.5, (12, 1)).astype(np.float32)
        _, full = drive(res, jnp.asarray(u))
        eng = ReservoirEngine(res, num_slots=2, backend="scan", chunk_ticks=3)
        first = eng.run([StreamSession(sid=0, u_seq=u[:7])])[0]
        second = eng.run([StreamSession(sid=1, u_seq=u[7:], m0=first.final_m)])[1]
        stitched = np.concatenate(
            [np.asarray(first.states), np.asarray(second.states)]
        )
        np.testing.assert_allclose(stitched, np.asarray(full), atol=ATOL)


class TestAutoscale:
    def test_grow_and_shrink_preserve_dynamics(self):
        """A burst grows the batch (bucketed), the drain shrinks it; every
        session still matches its solo reference across the migrations."""
        res = make_reservoir(n=10, n_in=1, hold_steps=6, dtype=jnp.float32)
        rng = np.random.default_rng(0)
        sessions, refs = [], {}
        for sid in range(20):
            u = rng.uniform(0.0, 0.5, ((6, 10, 14)[sid % 3], 1)).astype(np.float32)
            _, states = drive(res, jnp.asarray(u))
            sessions.append(StreamSession(sid=sid, u_seq=u))
            refs[sid] = states
        eng = ReservoirEngine(
            res, num_slots=4, backend="scan", chunk_ticks=4,
            autoscale=QueueDepthPolicy(), min_slots=2, max_slots=16,
        )
        results = eng.run(sessions)
        assert len(results) == 20
        assert eng.scheduler.stats.grows >= 1
        assert eng.scheduler.stats.shrinks >= 1
        assert len(eng._sims) >= 2  # bucketed plan cache populated
        for sid, r in results.items():
            np.testing.assert_allclose(
                np.asarray(r.states), np.asarray(refs[sid]), atol=ATOL
            )

    def test_bucketing(self):
        assert _bucket_slots(1, 2, 16) == 2
        assert _bucket_slots(3, 2, 16) == 4
        assert _bucket_slots(9, 2, 16) == 16
        assert _bucket_slots(100, 2, 16) == 16
        assert _bucket_slots(5, 8, 64) == 8

    def test_autoscale_true_uses_default_policy(self):
        res = make_reservoir(n=6, n_in=1, hold_steps=4, dtype=jnp.float32)
        eng = ReservoirEngine(
            res, num_slots=2, backend="scan", autoscale=True, max_slots=8
        )
        assert isinstance(eng.autoscale, QueueDepthPolicy)

    def test_custom_policy_plugs_in(self):
        class AlwaysMax(AutoscalePolicy):
            def target_slots(self, *, active, queued, num_slots, min_slots, max_slots):
                return max_slots

        res = make_reservoir(n=6, n_in=1, hold_steps=4, dtype=jnp.float32)
        eng = ReservoirEngine(
            res, num_slots=2, backend="scan", chunk_ticks=2,
            autoscale=AlwaysMax(), min_slots=2, max_slots=8,
        )
        u = np.random.default_rng(1).uniform(0, 0.5, (4, 1)).astype(np.float32)
        eng.run([StreamSession(sid=0, u_seq=u)])
        assert eng.num_slots == 8
        assert eng.scheduler.stats.grows == 1

    def test_rejects_bad_bounds(self):
        res = make_reservoir(n=6, n_in=1, hold_steps=4, dtype=jnp.float32)
        with pytest.raises(ValueError, match="min_slots"):
            ReservoirEngine(
                res, num_slots=4, backend="scan", autoscale=True,
                min_slots=8, max_slots=16,
            )

    def test_scheduler_load_signals(self):
        sched = SlotScheduler(4)
        for sid in range(3):
            sched.submit(f"s{sid}")
        assert sched.queue_depth() == 3
        sched.admissions([0, 1])
        sched.on_ticks(4, 8)
        assert sched.stats.slot_ticks == 16
        assert sched.occupancy() == pytest.approx(0.5)
        sched.admissions([2])  # s2 waited 4 ticks
        assert sched.stats.queue_wait_ticks == 4
        assert sched.mean_queue_wait() == pytest.approx(4 / 3)
        sched.remap({0: 0, 1: 1, 2: 2}, 8)
        assert sched.num_slots == 8 and sched.stats.grows == 1


class TestResultRetention:
    def _serve(self, **kw):
        res = make_reservoir(n=6, n_in=1, hold_steps=4, dtype=jnp.float32)
        eng = ReservoirEngine(res, num_slots=2, backend="scan", chunk_ticks=2, **kw)
        rng = np.random.default_rng(0)
        sessions = [
            StreamSession(
                sid=i, u_seq=rng.uniform(0, 0.5, (4, 1)).astype(np.float32),
                collect_states=False,
            )
            for i in range(8)
        ]
        return eng, eng.run(sessions)

    def test_max_retained_bounds_results(self):
        eng, results = self._serve(max_retained=3)
        assert len(results) == 3
        assert eng.scheduler.stats.retired == 8  # all served, oldest evicted

    def test_pop_results_drains(self):
        eng, results = self._serve()
        assert len(results) == 8
        popped = eng.pop_results()
        assert set(popped) == set(range(8))
        assert eng.results == {}
        assert eng.pop_results() == {}


class TestPlanValidation:
    def test_chunk_ticks_must_be_positive_int(self):
        with pytest.raises(ValueError, match="chunk_ticks"):
            ExecPlan(chunk_ticks=0)
        with pytest.raises(ValueError, match="chunk_ticks"):
            ExecPlan(chunk_ticks=-3)
        with pytest.raises(ValueError, match="chunk_ticks"):
            ExecPlan(chunk_ticks=2.5)
        with pytest.raises(ValueError, match="chunk_ticks"):
            ExecPlan(chunk_ticks=True)
        assert ExecPlan(chunk_ticks=16).chunk_ticks == 16

    def test_gather_dtype_must_be_dtype(self):
        with pytest.raises(ValueError, match="gather_dtype"):
            ExecPlan(gather_dtype="not-a-dtype")
        with pytest.raises(ValueError, match="gather_dtype"):
            ExecPlan(gather_dtype=object())
        assert ExecPlan(gather_dtype=jnp.bfloat16).gather_dtype is jnp.bfloat16
        assert ExecPlan(gather_dtype=None).gather_dtype is None

    def test_engine_rejects_chunk_ticks_with_compiled_sim(self):
        spec = make_spec(n=6, n_in=1, hold_steps=4, dtype=jnp.float32)
        sim = compile_plan(spec, ExecPlan(impl="scan", ensemble=2))
        with pytest.raises(ValueError, match="chunk_ticks"):
            ReservoirEngine(sim, chunk_ticks=4)

    def test_engine_adopts_plan_chunk_ticks(self):
        spec = make_spec(n=6, n_in=1, hold_steps=4, dtype=jnp.float32)
        sim = compile_plan(spec, ExecPlan(impl="scan", ensemble=2, chunk_ticks=8))
        assert ReservoirEngine(sim).chunk_ticks == 8

    def test_plan_replace_keeps_chunk_ticks(self):
        plan = ExecPlan(ensemble=4, chunk_ticks=8)
        assert dataclasses.replace(plan, ensemble=16).chunk_ticks == 8


class TestChunkReadoutProgram:
    @pytest.mark.parametrize(
        "k,n,e,n_out", [(8, 1000, 256, 1), (8, 1, 4096, 1), (4, 12, 3, 1), (8, 64, 32, 3)]
    )
    def test_one_program_bitexact_vs_per_tick(self, k, n, e, n_out):
        """The chunk readout is one jitted program (one dispatch at any K)
        and the same numbers, bit for bit, as K per-tick readouts."""
        rng = np.random.default_rng(k * n + e)
        states = jnp.asarray(rng.standard_normal((k, n, e)).astype(np.float32))
        w_out = jnp.asarray(rng.standard_normal((e, n + 1, n_out)).astype(np.float32))
        assert hasattr(_apply_readouts_chunk, "lower")
        got = _apply_readouts_chunk(states, w_out)
        want = jnp.stack([_apply_readouts(states[t], w_out) for t in range(k)])
        assert got.shape == (k, e, n_out)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_launch_returns_before_chunk_ends(self):
        """Every chunk launched after warm-up is still running when
        `_launch_chunk` returns (a chunk here takes ~0.1 s on a CPU; a
        slower host only makes that more certain)."""
        res = make_reservoir(n=128, n_in=1, hold_steps=100, dtype=jnp.float32)
        eng = ReservoirEngine(res, num_slots=32, backend="ref", chunk_ticks=8)
        rng = np.random.default_rng(0)
        ro = Readout(
            w_out=jnp.asarray(0.01 * rng.standard_normal((129, 1)).astype(np.float32)),
            washout=0,
        )

        def wave(base):
            for i in range(32):
                u = rng.uniform(0.0, 0.5, (16, 1)).astype(np.float32)
                eng.submit(StreamSession(sid=base + i, u_seq=u, readout=ro))

        wave(0)
        while eng.step_chunk():  # warm-up: compiles every program of a wave
            pass
        before = eng.stats()
        wave(100)
        while eng.step_chunk():
            pass
        after = eng.stats()
        launched = after.chunks_timed - before.chunks_timed
        assert launched == 2
        assert after.launches_overlapped - before.launches_overlapped == launched
        assert len(eng.results) == 64

    def test_stats_without_counter_still_load(self):
        res = make_reservoir(n=6, n_in=1, hold_steps=4, dtype=jnp.float32)
        stats = ReservoirEngine(res, num_slots=2, backend="scan").stats()
        assert stats.launches_overlapped == 0
        old = stats.to_dict()
        del old["launches_overlapped"]
        assert EngineStats(**old).launches_overlapped == 0
        del stats.__dict__["launches_overlapped"]  # as pickled before the field
        assert pickle.loads(pickle.dumps(stats)).launches_overlapped == 0
