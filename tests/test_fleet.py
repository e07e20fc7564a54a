"""Fleet serving tier: planner model, router placement, bit-exact
migration, async front-end admission, and the replica transports.

The correctness contract extends test_serve_reservoir's one level up:
anything the fleet does to a stream — placing it on a replica, pushing
ticks through the affinity map, checkpointing it out of one engine and
restoring it into another (process boundaries included) — must leave the
served states/outputs BIT-IDENTICAL to the same stream served by a
single unmigrated engine. The planner tests pin the analytical model's
self-consistency: fit recovery on a synthetic grid, scale-invariant fit
error under host recalibration, and sanity bounds on the committed
BENCH_serve.json grid.
"""

import asyncio
import os

import numpy as np
import pytest

from repro.serve.fleet import (
    AdmissionError,
    CapacityModel,
    FleetFrontend,
    FleetRouter,
    LocalReplica,
    WorkloadClass,
    start_fleet,
)
from repro.serve.reservoir import EngineStats, StreamSession

# tiny deterministic engine config shared by the correctness tests: the
# scan backend is the bit-exactness oracle everywhere else in tests/
ENGINE_KW = dict(
    n=10, num_slots=4, hold_steps=6, seed=3, backend="scan", chunk_ticks=5
)
BENCH_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_serve.json")


def _stream(rng, t=23, n_in=1):
    return rng.uniform(0.0, 0.5, size=(t, n_in)).astype(np.float32)


def _serve_solo(u, targets=None, engine_kw=ENGINE_KW, **session_kw):
    """Reference: the same stream through one unmigrated LocalReplica."""
    rep = LocalReplica(**engine_kw)
    rep.submit(StreamSession(sid=0, u_seq=u, targets=targets, **session_kw))
    while rep.run_for(1):
        pass
    (res,) = rep.results()
    return res


# ---------------------------------------------------------------------------
# planner
# ---------------------------------------------------------------------------


def _synthetic_bench(coef, burst_slowdown=1.5, k=8, h=5):
    """A grid generated FROM the model family: fit must recover it."""
    cells = []
    for n in (16, 64, 256):
        for e in (8, 32, 128):
            t = float(CapacityModel._features(n, e, k, h) @ np.asarray(coef))
            cells.append(
                dict(
                    n=n,
                    e=e,
                    steady_chunk_s=t,
                    ticks_per_sec_burst=e * k / (t * burst_slowdown),
                    learn_overhead=1.4,
                    precision_speedup=1.2,
                )
            )
    return dict(
        cells=cells,
        chunk_ticks=k,
        hold_steps=h,
        ref_stream_ticks=7,
        backend_platform="cpu",
    )


class TestPlanner:
    COEF = np.array([2e-4, 1e-6, 3e-12, 2e-10, 5e-13])

    def test_fit_recovers_synthetic_grid(self):
        m = CapacityModel.from_bench(_synthetic_bench(self.COEF))
        err = m.prediction_error()
        assert err["max"] < 1e-6  # noise-free grid: exact recovery
        assert err["sustained_max"] < 1e-6
        # sustained family carries the churn slowdown
        ratio = m.t_chunk(64, 32, sustained=True) / m.t_chunk(64, 32)
        assert ratio == pytest.approx(1.5, rel=1e-6)

    def test_multipliers_and_capacity_shape(self):
        m = CapacityModel.from_bench(_synthetic_bench(self.COEF))
        base = m.sessions_per_sec(64, 32)
        assert m.sessions_per_sec(64, 32, learn=True) == pytest.approx(
            base / 1.4, rel=1e-6
        )
        assert m.sessions_per_sec(64, 32, precision="mixed") == pytest.approx(
            base * 1.2, rel=1e-6
        )
        # fleet scaling is min(replicas, cores): never super-linear
        assert m.fleet_sessions_per_sec(
            64, 32, replicas=4, cores=2
        ) == pytest.approx(2 * base, rel=1e-6)
        with pytest.raises(ValueError):
            m.sessions_per_sec(64, 32, platform="gpu")

    def test_recalibrate_rescales_both_families(self):
        m = CapacityModel.from_bench(_synthetic_bench(self.COEF))
        d0 = m.drain_seconds(64, 32, sessions=16, stream_ticks=40, cores=1)
        err0 = m.prediction_error()
        # probe says the host now runs at half the calibration speed
        half_rate = 0.5 * 32 * m.chunk_ticks / m.t_chunk(64, 32, sustained=True)
        scale = m.recalibrate({64: {32: half_rate}})
        assert scale == pytest.approx(0.5, rel=1e-6)
        assert m.drain_seconds(
            64, 32, sessions=16, stream_ticks=40, cores=1
        ) == pytest.approx(2 * d0, rel=1e-6)
        # fit error is evaluated at calibration scale: recalibrating must
        # not flatter or damn the model's shape
        err1 = m.prediction_error()
        assert err1["max"] == pytest.approx(err0["max"], abs=1e-12)
        with pytest.raises(ValueError):
            m.recalibrate({})

    def test_plan_fleet_covers_offered_load(self):
        m = CapacityModel.from_bench(_synthetic_bench(self.COEF))
        plan = m.plan_fleet(
            [WorkloadClass(n=16, rate=50.0), WorkloadClass(n=256, rate=5.0)],
            headroom=0.2,
            cores=64,  # enough cores that replica counts are demand math
        )
        assert len(plan.replicas) == 2
        for spec in plan.replicas:
            offered = {16: 50.0, 256: 5.0}[spec.n]
            assert spec.count * spec.sessions_per_sec >= offered * 1.2
        assert 0.0 < plan.utilization <= 1.0 / 1.2 + 1e-9

    @pytest.mark.skipif(
        not os.path.exists(BENCH_PATH), reason="no committed BENCH_serve.json"
    )
    def test_committed_grid_sanity_bounds(self):
        """Predicted-vs-measured on the committed grid: the model must sit
        within the fit-error band the planner itself publishes (the ~30%
        acceptance bound lives on the cells the model calibrated on)."""
        m = CapacityModel.from_bench(BENCH_PATH)
        err = m.prediction_error()
        assert err["max"] < 0.35, err["per_cell"]
        if "sustained_max" in err:
            assert err["sustained_max"] < 0.35, err["per_cell_sustained"]
        # sustained (churn billed) can never beat peak by more than jitter
        for c in m.cells:
            assert m.t_chunk(c["n"], c["e"], sustained=True) > 0.5 * m.t_chunk(
                c["n"], c["e"]
            )


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------


class TestRouter:
    def test_pools_are_bucketed_by_n(self):
        router = FleetRouter()
        for r in start_fleet(2, "local", **ENGINE_KW):
            router.add_replica(r)
        for r in start_fleet(1, "local", **{**ENGINE_KW, "n": 20}):
            router.add_replica(r)
        assert sorted(router.pools) == [10, 20]
        assert len(router.pool(10)) == 2
        with pytest.raises(KeyError):
            router.pool(1024)  # no cross-bucket head-of-line sharing
        router.close()

    def test_least_loaded_placement_and_affinity(self):
        rng = np.random.default_rng(0)
        router = FleetRouter()
        reps = start_fleet(2, "local", **ENGINE_KW)
        for r in reps:
            router.add_replica(r)
        owners = [
            router.submit(10, StreamSession(sid=i, u_seq=_stream(rng)))
            for i in range(4)
        ]
        # least-loaded placement alternates across the empty pool
        assert {owners.count(reps[0]), owners.count(reps[1])} == {2}
        for i, owner in enumerate(owners):
            assert router.replica_for(i) is owner
        with pytest.raises(ValueError):
            router.submit(10, StreamSession(sid=0, u_seq=_stream(rng)))
        out = router.drain()
        assert sorted(out) == [0, 1, 2, 3]
        with pytest.raises(KeyError):
            router.replica_for(0)  # affinity released on finish
        router.close()


# ---------------------------------------------------------------------------
# checkpoint -> migrate -> resume
# ---------------------------------------------------------------------------


class TestMigration:
    def test_midstream_migration_bit_exact(self):
        rng = np.random.default_rng(1)
        u = _stream(rng, t=23)
        control = _serve_solo(u)
        router = FleetRouter()
        for r in start_fleet(2, "local", **ENGINE_KW):
            router.add_replica(r)
        router.submit(10, StreamSession(sid=7, u_seq=u))
        src = router.replica_for(7)
        router.run_for(2)  # mid-stream: 10 of 23 ticks done
        dst = router.migrate(7)
        assert dst is not src and router.replica_for(7) is dst
        out = router.drain()
        np.testing.assert_array_equal(out[7].states, control.states)
        np.testing.assert_array_equal(out[7].final_m, control.final_m)
        router.close()

    def test_migration_with_inflight_rls_learner(self):
        """The hard case: P and Wl lanes of an in-progress RLS learner ride
        the checkpoint; the learned readout must finish bit-identical to
        never having moved."""
        kw = {**ENGINE_KW, "learn": "rls"}
        rng = np.random.default_rng(2)
        u, y = _stream(rng, t=23), _stream(rng, t=23)
        control = _serve_solo(u, targets=y, engine_kw=kw, learn_washout=3)
        router = FleetRouter()
        for r in start_fleet(2, "local", **kw):
            router.add_replica(r)
        router.submit(
            10, StreamSession(sid=1, u_seq=u, targets=y, learn_washout=3)
        )
        router.run_for(2)  # learner has already absorbed ticks
        router.migrate(1)
        out = router.drain()
        np.testing.assert_array_equal(
            np.asarray(out[1].learned_readout.w_out),
            np.asarray(control.learned_readout.w_out),
        )
        np.testing.assert_array_equal(out[1].predictions, control.predictions)
        np.testing.assert_array_equal(out[1].states, control.states)
        router.close()

    def test_migration_of_queued_session(self):
        """A session still waiting for a slot migrates too (checkpoint at
        t=0) and serves identically on the destination."""
        rng = np.random.default_rng(3)
        streams = [_stream(rng, t=12) for _ in range(5)]
        control = _serve_solo(streams[4])
        kw = {**ENGINE_KW, "num_slots": 2}
        router = FleetRouter()
        reps = start_fleet(2, "local", **kw)
        for r in reps:
            router.add_replica(r)
        # overload replica 0's queue by explicit submit, then migrate the
        # queued tail session to the idle replica
        for i, u in enumerate(streams):
            reps[0].submit(StreamSession(sid=i, u_seq=u))
            router._affinity[i] = reps[0]
        dst = router.migrate(4, dst=reps[1])
        assert dst is reps[1]
        out = router.drain()
        assert sorted(out) == [0, 1, 2, 3, 4]
        np.testing.assert_array_equal(out[4].states, control.states)
        router.close()


# ---------------------------------------------------------------------------
# async front-end
# ---------------------------------------------------------------------------


class TestFrontend:
    def _router(self, planner=None, replicas=2, **overrides):
        router = FleetRouter(planner=planner)
        for r in start_fleet(replicas, "local", **{**ENGINE_KW, **overrides}):
            router.add_replica(r)
        return router

    def test_submit_push_drain_round_trip(self):
        rng = np.random.default_rng(4)
        u = _stream(rng, t=23)
        control = _serve_solo(u)

        async def main():
            async with FleetFrontend(self._router()) as fleet:
                # closed streams
                sids = [
                    await fleet.submit_stream(10, _stream(rng)) for _ in range(3)
                ]
                # open stream fed in two pushes: must equal the one-shot serve
                osid = await fleet.submit_stream(10, u[:9], open=True)
                await fleet.push_ticks(osid, u[9:])
                await fleet.close_stream(osid)
                res = await fleet.result(osid)
                np.testing.assert_array_equal(res.states, control.states)
                rest = await fleet.drain_results()
                assert sorted(rest) == sorted(sids)

        asyncio.run(main())

    def test_pool_limit_and_admission_error(self):
        planner = CapacityModel.from_bench(
            _synthetic_bench(TestPlanner.COEF)
        )
        # a glacial host: the planner ceiling collapses to the slot floor
        planner.host_scale = 1e-9
        rng = np.random.default_rng(5)

        async def main():
            router = self._router(planner=None)
            async with FleetFrontend(router) as fleet:
                assert fleet.pool_limit(10) is None  # no planner: unlimited
            router = self._router(planner=planner)
            async with FleetFrontend(
                router, admit_window_s=0.01, max_waiters=0
            ) as fleet:
                limit = fleet.pool_limit(10)
                assert limit == 2 * ENGINE_KW["num_slots"]  # slot floor
                # open streams hold their slots forever -> a deterministic
                # full pool; the next submit must fail fast, not queue
                sids = [
                    await fleet.submit_stream(
                        10, _stream(rng, t=5), open=True
                    )
                    for _ in range(limit)
                ]
                with pytest.raises(AdmissionError):
                    await fleet.submit_stream(10, _stream(rng, t=5))
                for sid in sids:
                    await fleet.close_stream(sid)
                    await fleet.result(sid)

        asyncio.run(main())


# ---------------------------------------------------------------------------
# per-session n_out through the fleet
# ---------------------------------------------------------------------------


def test_per_session_n_out_round_trip():
    """Sessions with different readout widths share one replica: the
    q-column slice of the padded lane must bit-match each session served
    by an engine sized exactly to its own q."""
    from repro.core.reservoir import fit_ridge, make_reservoir
    from repro.core.reservoir import drive as res_drive

    rng = np.random.default_rng(6)
    res = make_reservoir(n=10, n_in=1, hold_steps=6, seed=3)
    u_fit = rng.uniform(0.0, 0.5, size=(40, 1)).astype(np.float32)
    _, states_fit = res_drive(res, u_fit)
    ro2 = fit_ridge(
        states_fit,
        rng.uniform(0.0, 0.5, size=(40, 2)).astype(np.float32),
        washout=4,
    )
    ro1 = fit_ridge(states_fit, u_fit[:, 0], washout=4)
    u = _stream(rng, t=17)
    narrow = _serve_solo(u, readout=ro1)  # engine n_out=1
    wide = _serve_solo(u, readout=ro2, engine_kw={**ENGINE_KW, "n_out": 2})

    router = FleetRouter()
    for r in start_fleet(1, "local", **{**ENGINE_KW, "n_out": 2}):
        router.add_replica(r)
    router.submit(10, StreamSession(sid=1, u_seq=u, readout=ro2))
    router.submit(10, StreamSession(sid=2, u_seq=u, readout=ro1))
    out = router.drain()
    # outputs are (T - readout washout, q): the q-slice never sees padding
    assert out[1].outputs.shape == (13, 2) and out[2].outputs.shape == (13, 1)
    np.testing.assert_array_equal(out[1].outputs, wide.outputs)
    np.testing.assert_array_equal(out[2].outputs, narrow.outputs)
    router.close()


# ---------------------------------------------------------------------------
# observability + process transport
# ---------------------------------------------------------------------------


def test_stats_through_replica_protocol():
    rng = np.random.default_rng(8)
    rep = LocalReplica(**ENGINE_KW)
    for i in range(3):
        rep.submit(StreamSession(sid=i, u_seq=_stream(rng, t=11)))
    rep.run_for(1)
    st = rep.stats()
    assert isinstance(st, EngineStats)
    assert st.n == 10 and st.num_slots == 4 and st.backend == "scan"
    assert st.active == 3 and 0.0 < st.occupancy <= 1.0
    assert st.chunk_median_s is not None and st.chunk_median_s > 0.0
    d = st.to_dict()
    assert d["active"] == 3 and d["ticks_per_sec"] > 0.0
    while rep.run_for(1):
        pass
    assert rep.stats().active == 0


@pytest.mark.parametrize("transport", ["process"])
def test_process_transport_end_to_end(transport):
    """One spawned replica: serve, stats, and a cross-process checkpoint
    restored into an in-process engine — all bit-exact with local."""
    rng = np.random.default_rng(9)
    u = _stream(rng, t=23)
    control = _serve_solo(u)
    (rep,) = start_fleet(1, transport, **ENGINE_KW)
    try:
        rep.submit(StreamSession(sid=5, u_seq=u))
        for _ in range(2):
            rep.run_for(1)
        st = rep.stats()
        assert st.active == 1 and st.backend == "scan"
        ckpt = rep.checkpoint_session(5)  # crosses the pipe as numpy
        local = LocalReplica(**ENGINE_KW)
        local.restore_session(ckpt)
        while local.run_for(1):
            pass
        (res,) = local.results()
        np.testing.assert_array_equal(res.states, control.states)
        np.testing.assert_array_equal(res.final_m, control.final_m)
    finally:
        rep.close()


def test_process_replicas_refused_where_this_process_holds_a_tpu(monkeypatch):
    """A spawned replica cannot reach a chip this process holds: refused up
    front, before any child is started, instead of hanging its handshake."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    spawned = []
    monkeypatch.setattr(
        "multiprocessing.context.SpawnProcess.start",
        lambda self: spawned.append(self),
    )
    with pytest.raises(ValueError, match="holds the TPU"):
        start_fleet(2, "process", **ENGINE_KW)
    assert spawned == []


def test_planner_from_another_platform_disables_pricing(capsys):
    """A CPU calibration prices nothing on a TPU: the check prints why and
    hands back no model."""
    m = CapacityModel.from_bench(_synthetic_bench(TestPlanner.COEF))
    assert m.checked_for("cpu") is m
    assert m.checked_for("tpu") is None
    assert "admission pricing disabled" in capsys.readouterr().out
