"""Benchmark harness: one module per paper table/figure + the LM roofline.

Prints ``name,us_per_call,derived`` CSV rows (shared format). Individual
modules run standalone too:  python -m benchmarks.table2_timing

``--smoke`` runs a minutes-not-hours subset for CI: a quick serving-
throughput grid (written to a scratch file, NOT BENCH_serve.json) plus a
compile-and-drive pass through every unified-API entry point — including
the chunked `tick_chunk` serving path, an autoscaling engine, an online-
learning engine bit-checked against the fit_rls oracle, and a "mixed"-
precision serve asserted against the f32 accuracy guardrail — so the CI
leg exercises plan compilation, dispatch-table loading, precision
policies, and the serving engine end-to-end without paying for the full
grids — plus the tune subsystem: an LMS engine bit-checked against the
fit_lms oracle, a washout auto-tune that serves a tuned tenant end-to-end,
and the lane-vectorized-vs-sequential search ratio. The smoke grid's
WITHIN-RUN ratio columns are the perf gate (pipelined/sync,
fleet/single-replica, planner predicted-vs-measured,
tune vectorized/sequential); absolute sessions/sec is never asserted —
the container's ±40% noise owns that axis.

``--save-dispatch-table`` persists measured dispatch choices after the
run: the fresh serving grid is seeded into the in-process table
(`kernels.dispatch_table.seed_from_bench`) alongside anything
`ExecPlan(measure=True)` recorded, then written out via
`dispatch_table.save_table()` — the workflow for committing a
GPU/TPU-measured `dispatch_table.<platform>.json`.
"""

from __future__ import annotations

import argparse
import os
import tempfile


def _save_dispatch_table(bench_json: str, print_fn=print) -> None:
    from benchmarks.common import csv_row
    from repro.kernels import dispatch_table

    if os.path.exists(bench_json):
        seeded = dispatch_table.seed_from_bench(bench_json)
        print_fn(csv_row("dispatch_table_seeded", 0.0, f"{seeded}_entries"))
    path = dispatch_table.save_table()
    print_fn(csv_row("dispatch_table_saved", 0.0, path))


def smoke(save_dispatch_table: bool = False) -> None:
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import serve_throughput
    from repro.api import ExecPlan, compile_plan, make_spec
    from repro.kernels import dispatch_table

    print("name,us_per_call,derived")

    # unified API end-to-end: compile (consults the persisted dispatch
    # table), then touch each entry point once
    spec = make_spec(n=16, n_in=1, hold_steps=5, dtype=jnp.float32)
    sim = compile_plan(spec, ensemble=4)
    u = np.random.default_rng(0).uniform(0.0, 0.5, size=(6, 1)).astype(np.float32)
    sim.drive_batch(u)
    compile_plan(spec, ExecPlan(impl="scan")).drive(u)
    sim_solo = compile_plan(spec)
    sim_solo.drive(u)
    print(f"smoke_compile_plan,0.0,impl_{sim.impl}")

    # chunked serving path: one tick_chunk dispatch + an autoscaling engine
    from repro.serve.reservoir import ReservoirEngine, StreamSession

    chunked = compile_plan(spec, ExecPlan(ensemble=4, chunk_ticks=4))
    eng = ReservoirEngine(chunked, autoscale=True, min_slots=2, max_slots=8)
    sessions = [
        StreamSession(
            sid=i,
            u_seq=np.random.default_rng(i).uniform(0, 0.5, (6, 1)).astype(np.float32),
            collect_states=False,
        )
        for i in range(6)
    ]
    results = eng.run(sessions)
    print(f"smoke_serve_chunked,0.0,served_{len(results)}_chunk_{eng.chunk_ticks}")

    # physics families + mixed-spec tenancy: a time-multiplexed and an
    # array-transient tenant ride the coupled-array engine above; each
    # stream must be bit-identical to a dedicated engine for its spec
    from repro.api import make_array_transient_spec, make_time_multiplexed_spec

    spec_tm = make_time_multiplexed_spec(6, hold_steps=4)
    spec_at = make_array_transient_spec(8, readout_window=3, hold_steps=5)
    fam_u = {
        1: np.random.default_rng(21).uniform(0, 1, 9).astype(np.float32),
        2: np.random.default_rng(22).uniform(0, 1, 9).astype(np.float32),
    }
    mixed_eng = ReservoirEngine(spec, num_slots=2, backend="scan", chunk_ticks=4)
    mixed_eng.submit(StreamSession(sid=1, u_seq=fam_u[1], spec=spec_tm))
    mixed_eng.submit(StreamSession(sid=2, u_seq=fam_u[2], spec=spec_at))
    mixed = mixed_eng.run()
    for sid, fam_spec in ((1, spec_tm), (2, spec_at)):
        solo_eng = ReservoirEngine(fam_spec, num_slots=2, backend="scan", chunk_ticks=4)
        solo_eng.submit(StreamSession(sid=sid, u_seq=fam_u[sid]))
        solo = solo_eng.run()[sid]
        assert np.array_equal(mixed[sid].states, solo.states), (
            f"smoke: mixed-spec tenant {sid} ({fam_spec.topology}) deviates "
            "from its dedicated engine"
        )
    print(
        "smoke_families_tenancy,0.0,"
        f"subengines_{mixed_eng.stats().sub_engines}_bitmatch_solo"
    )

    # online learning end-to-end: a learning engine trains per-tenant
    # readouts while streaming; the learned weights must match the offline
    # fit_rls oracle run over the harvested states (scan backend: bitwise)
    from repro.core.reservoir import fit_rls

    learn_eng = ReservoirEngine(
        compile_plan(
            spec, ExecPlan(impl="scan", ensemble=4, chunk_ticks=4, learn="rls",
                           learn_reg=1e-2)
        )
    )
    rng = np.random.default_rng(7)
    learners = [
        StreamSession(
            sid=i,
            u_seq=rng.uniform(0, 0.5, (10, 1)).astype(np.float32),
            targets=rng.uniform(0, 0.5, (10, 1)).astype(np.float32),
            learn_washout=2,
        )
        for i in range(6)
    ]
    targets = {s.sid: s.targets for s in learners}
    learned = learn_eng.run(learners)
    for sid, r in learned.items():
        oracle = fit_rls(r.states, targets[sid], washout=2, reg=1e-2, block=4)
        assert np.array_equal(
            np.asarray(r.learned_readout.w_out), np.asarray(oracle.w_out)
        ), f"smoke: session {sid} learned readout != fit_rls oracle"
    print(f"smoke_serve_learn,0.0,trained_{len(learned)}_bitmatch_oracle")

    # LMS twin of the RLS oracle check: an ExecPlan(learn="lms") engine's
    # learned weights must bit-match the offline fit_lms oracle (same
    # normalized-LMS recursion over the harvested states, scan backend)
    from repro.core.reservoir import fit_lms

    lms_eng = ReservoirEngine(
        compile_plan(
            spec, ExecPlan(impl="scan", ensemble=4, chunk_ticks=4, learn="lms",
                           learn_mu=0.5)
        )
    )
    rng_lms = np.random.default_rng(8)
    lms_learners = [
        StreamSession(
            sid=i,
            u_seq=rng_lms.uniform(0, 0.5, (10, 1)).astype(np.float32),
            targets=rng_lms.uniform(0, 0.5, (10, 1)).astype(np.float32),
            learn_washout=2,
        )
        for i in range(5)
    ]
    lms_targets = {s.sid: s.targets for s in lms_learners}
    lms_learned = lms_eng.run(lms_learners)
    for sid, r in lms_learned.items():
        oracle = fit_lms(r.states, lms_targets[sid], washout=2, mu=0.5)
        assert np.array_equal(
            np.asarray(r.learned_readout.w_out), np.asarray(oracle.w_out)
        ), f"smoke: session {sid} LMS readout != fit_lms oracle"
    print(f"smoke_serve_lms,0.0,trained_{len(lms_learned)}_bitmatch_oracle")

    # washout auto-tune end-to-end: a live learning engine probes the
    # search space on spare lanes during a tenant's washout window, then
    # serves the tenant with the winning parameters (the tune subsystem's
    # serving entry point)
    from repro.core.tasks import narma_series
    from repro.tune import Float, SearchSpace

    tune_space = SearchSpace({
        "drive_current": Float(0.5e-3, 4.5e-3),
        "spectral_radius": Float(0.2, 1.2),
    })
    at_eng = ReservoirEngine(
        compile_plan(
            spec, ExecPlan(impl="scan", ensemble=4, chunk_ticks=4, learn="rls")
        )
    )
    u_at, y_at = narma_series(60, order=10, seed=3)
    tenant = StreamSession(sid=1, u_seq=u_at, targets=y_at, learn_washout=20)
    probe = at_eng.submit_autotuned(tenant, tune_space, budget=4, seed=0)
    while at_eng.step_chunk():
        pass
    served = at_eng.pop_results()
    assert len(probe.trials) == 4, f"expected 4 probe trials, got {len(probe.trials)}"
    assert 1 in served and served[1].learn_nmse is not None
    assert np.isfinite(served[1].learn_nmse)
    assert float(tenant.params.current) == probe.best.assignment["current"], (
        "smoke: tenant was not served with the probe winner's parameters"
    )
    print(
        f"smoke_washout_autotune,0.0,probed_{len(probe.trials)}"
        f"_tenant_nmse_{served[1].learn_nmse:.3f}"
    )

    # mixed-precision serving end-to-end + the accuracy guardrail: the same
    # sessions served by a bit-exact chunk-impl engine and a "mixed" one
    # (reduced-precision coupling/input GEMMs, f32 state carry) must agree
    # to reduced-precision scale — a broken precision path shows up as a
    # blown tolerance here before it ever reaches a readout benchmark
    precision_sessions = lambda: [
        StreamSession(
            sid=i,
            u_seq=np.random.default_rng(100 + i)
            .uniform(0, 0.5, (8, 1))
            .astype(np.float32),
        )
        for i in range(4)
    ]
    exact_eng = ReservoirEngine(
        compile_plan(spec, ExecPlan(impl="chunk", ensemble=4, chunk_ticks=4))
    )
    mixed_eng = ReservoirEngine(
        compile_plan(
            spec,
            ExecPlan(impl="chunk", ensemble=4, chunk_ticks=4, precision="mixed"),
        )
    )
    exact_r = exact_eng.run(precision_sessions())
    mixed_r = mixed_eng.run(precision_sessions())
    max_dev = max(
        float(np.max(np.abs(exact_r[sid].states - mixed_r[sid].states)))
        for sid in exact_r
    )
    assert max_dev < 5e-3, (
        f"smoke: mixed-precision serve deviates {max_dev:.2e} from f32 — "
        f"the precision guardrail is blown"
    )
    assert all(np.isfinite(r.states).all() for r in mixed_r.values())
    print(f"smoke_serve_mixed,0.0,served_{len(mixed_r)}_maxdev_{max_dev:.1e}")

    loaded = dispatch_table.ensure_loaded()  # 0 if already loaded: fine
    print(f"smoke_dispatch_table,0.0,loaded_{loaded}_entries")

    # quick serving grid to a scratch path so the committed trajectory
    # (BENCH_serve.json) only changes when the full benchmark runs
    out = os.path.join(tempfile.gettempdir(), "BENCH_serve.smoke.json")
    serve_throughput.run(out_path=out, quick=True)

    # perf gates on the WITHIN-RUN ratio columns — never on absolute
    # sessions/sec, which the container's ±40% noise owns (ROADMAP
    # caveat). Both sides of each ratio were measured minutes apart in
    # the same process, so a blown gate is a real regression:
    #   pipelined/sync   >= 1.5 on the quick cells (true ratio >= 3.3;
    #                    the floor leaves the full noise band of slack)
    #   fleet/single     within [0.6, 1.67] of the predicted min(R, cores)
    #                    scaling — the UPPER gate catches measurement bugs
    #                    (e.g. compile time billed to one config only)
    #   planner          predicted-vs-measured drain within 50% after the
    #                    same-run recalibration probe
    import json

    with open(out) as f:
        smoke_bench = json.load(f)
    for c in smoke_bench["cells"]:
        r = c["pipelined_speedup"]
        assert r >= 1.5, (
            f"smoke: pipelined/sync ratio {r:.2f} at N={c['n']} E={c['e']} "
            f"below the 1.5x gate — chunked serving has regressed"
        )
    fl = smoke_bench["fleet"]
    ratio, pred = fl["fleet_speedup"], fl["predicted_speedup"]
    assert 0.6 * pred <= ratio <= 1.67 * pred, (
        f"smoke: fleet/single ratio {ratio:.2f} outside ±40% of the "
        f"predicted {pred:.1f}x (replicas={fl['replicas']}, "
        f"cores={fl['cores']})"
    )
    assert fl["planner_vs_measured_err"] <= 0.5, (
        f"smoke: planner predicted-vs-measured drain error "
        f"{fl['planner_vs_measured_err']:.0%} exceeds the 50% gate"
    )
    # tune leg, armed like the fleet gate (within-run ratios, never
    # absolutes):
    #   vectorized/sequential >= 6.0 (acceptance target is 10x; the floor
    #                         leaves the container's ±40% noise band)
    #   grid winner           identical across lane widths (stable-regime
    #                         grid — see bench_tune)
    tu = smoke_bench["tune"]
    assert tu["tune_speedup"] >= 6.0, (
        f"smoke: vectorized search only {tu['tune_speedup']:.1f}x over "
        f"sequential (budget={tu['budget']}, lanes={tu['lanes']}) — below "
        f"the 6x gate; lane-vectorized tuning has regressed"
    )
    assert tu["grid_winner_match"], (
        f"smoke: grid search winner changed with lane width "
        f"({tu['grid_winner']}) — vectorized fitness is off"
    )
    # compile-path gates (process-wide PlanCache). Unlike the throughput
    # ratios these are NOT noise-limited: the warm side of each ratio is a
    # dictionary hit and the "zero new compiles" assertions read the
    # cache's own counters, so the gates are exact.
    #   warm construction >= 5x cold   (quick grid's compile section; the
    #                                  true ratio is ~1000x — 5x leaves
    #                                  room for a pathologically slow host)
    #   warm construction compiles     exactly zero (counter, not timing)
    #   warm autoscale rescale         zero new XLA compiles after the
    #                                  adjacent buckets were pre-warmed
    from repro.api import PLAN_CACHE

    co = smoke_bench["compile"]
    assert co["cold_compiles"] >= 1, (
        "smoke: compile bench's cold probe never compiled — the probe "
        "spec collides with an earlier section's cache entry"
    )
    assert co["warm_compiles"] == 0, (
        f"smoke: warm construction recompiled ({co['warm_compiles']}x) — "
        f"the PlanCache key is unstable across identical requests"
    )
    assert co["warm_speedup"] >= 5.0, (
        f"smoke: warm engine construction only {co['warm_speedup']:.1f}x "
        f"faster than cold ({co['cold_s']:.2f}s -> {co['warm_s']:.4f}s) — "
        f"below the 5x gate; the plan cache has regressed"
    )
    spec_rs = make_spec(n=16, n_in=1, hold_steps=5, seed=81_001,
                        dtype=jnp.float32)
    eng_rs = ReservoirEngine(
        compile_plan(spec_rs, ExecPlan(ensemble=4, chunk_ticks=4)),
        autoscale=True, min_slots=2, max_slots=8,
    )
    # warm current width + adjacent buckets synchronously: 2, 4 and 8 are
    # now all warm-marked, so neither the rescale nor its trailing
    # background pre-warm round has any compile left to race the counter
    eng_rs.prewarm(block=True)
    compiles_before = PLAN_CACHE.stats.compiles
    eng_rs._rescale(8)
    rescale_compiles = PLAN_CACHE.stats.compiles - compiles_before
    st = eng_rs.stats()
    assert rescale_compiles == 0, (
        f"smoke: rescale into a pre-warmed bucket triggered "
        f"{rescale_compiles} XLA compile(s) — zero-stall autoscale is "
        f"broken"
    )
    assert st.cold_rescales == 0 and st.warm_rescales >= 1, (
        f"smoke: pre-warmed rescale accounted as cold "
        f"(cold={st.cold_rescales}, warm={st.warm_rescales})"
    )
    print(
        f"smoke_compile_gates,0.0,warm_{co['warm_speedup']:.0f}x"
        f"_rescale_compiles_{rescale_compiles}"
    )
    # revisiting-structural tune gate: the same CMA-ES search over a
    # structural knob run twice — the second run draws every per-combo
    # CompiledSim out of the shared PlanCache (zero compiles), must be
    # >= 2x faster wall-clock, and must reproduce the first run's trial
    # fitnesses bit-for-bit (cached engines are the same executables)
    import time as _time

    from repro.tune import Choice, Float, SearchSpace, narma_task, tune_spec

    tune_task = narma_task(48, order=10, seed=5)
    revisit_space = SearchSpace({
        "drive_current": Float(0.5e-3, 4.5e-3),
        "hold_steps": Choice((4, 6)),
    })
    revisit_plan = ExecPlan(impl="scan", ensemble=4, chunk_ticks=4,
                            learn="rls")

    def _revisit():
        t0 = _time.perf_counter()
        res = tune_spec(
            make_spec(n=16, n_in=1, hold_steps=5, seed=82_001,
                      dtype=jnp.float32),
            tune_task, revisit_space, budget=8, plan=revisit_plan,
            strategy="cmaes", seed=4,
        )
        return _time.perf_counter() - t0, res

    compiles_before = PLAN_CACHE.stats.compiles
    t_first, res_first = _revisit()
    first_compiles = PLAN_CACHE.stats.compiles - compiles_before
    t_second, res_second = _revisit()
    second_compiles = PLAN_CACHE.stats.compiles - compiles_before - first_compiles
    assert second_compiles == 0, (
        f"smoke: revisiting tune run recompiled {second_compiles} "
        f"structural combo(s) the first run already cached"
    )
    fits_first = [t.fitness for t in res_first.trials]
    fits_second = [t.fitness for t in res_second.trials]
    assert fits_first == fits_second, (
        "smoke: revisiting tune run's fitnesses differ from the first — "
        "cached engines are not bit-identical to fresh compiles"
    )
    tune_revisit_speedup = t_first / max(t_second, 1e-9)
    assert tune_revisit_speedup >= 2.0, (
        f"smoke: revisiting structural tune only {tune_revisit_speedup:.1f}x "
        f"faster ({t_first:.2f}s -> {t_second:.2f}s, first run compiled "
        f"{first_compiles}) — below the 2x gate"
    )
    print(
        f"smoke_tune_revisit,0.0,speedup_{tune_revisit_speedup:.1f}x"
        f"_combo_compiles_{first_compiles}_then_{second_compiles}"
    )
    # chaos gate: a fleet drain with one replica crashed mid-stream must
    # lose zero sessions and return every output — states, predictions,
    # learned readout weights — bit-identical to an unfaulted fleet. A
    # within-run correctness gate (no timings), so container noise cannot
    # touch it; the crash is injected deterministically via FaultPlan.
    from repro.serve.fleet import Fault, FaultPlan, FleetRouter, LocalReplica

    chaos_kw = dict(n=16, num_slots=4, hold_steps=5, seed=83_001,
                    backend="scan", chunk_ticks=4, learn="rls")
    chaos_rng = np.random.default_rng(9)

    def _chaos_sessions():
        out = []
        for i in range(6):
            u = chaos_rng.uniform(0, 0.5, (18, 1)).astype(np.float32)
            y = chaos_rng.uniform(0, 0.5, (18, 1)).astype(np.float32)
            out.append((i, u, y))
        return out

    chaos_streams = _chaos_sessions()

    def _chaos_drain(faulted: bool):
        router = FleetRouter(checkpoint_every=2)
        plan = FaultPlan((Fault("crash", at_chunk=3),)) if faulted else None
        router.add_replica(
            LocalReplica(faults=plan, **chaos_kw),
            respawn=lambda: LocalReplica(**chaos_kw),
        )
        router.add_replica(LocalReplica(**chaos_kw))
        for sid, u, y in chaos_streams:
            router.submit(chaos_kw["n"], StreamSession(
                sid=sid, u_seq=u.copy(), targets=y.copy(), learn_washout=2))
        try:
            results = router.drain()
            return results, router.fault_stats()
        finally:
            router.close()

    chaos_clean, _ = _chaos_drain(faulted=False)
    chaos_hit, chaos_faults = _chaos_drain(faulted=True)
    assert chaos_faults["replica_deaths"] == 1, (
        "smoke: the injected replica crash never fired"
    )
    assert chaos_faults["failovers"] == 1 and chaos_faults["sessions_lost"] == 0, (
        f"smoke: chaos drain lost sessions "
        f"(failovers={chaos_faults['failovers']}, "
        f"lost={chaos_faults['sessions_lost']})"
    )
    assert sorted(chaos_hit) == sorted(chaos_clean)
    for sid in chaos_clean:
        assert np.array_equal(chaos_hit[sid].states, chaos_clean[sid].states), (
            f"smoke: recovered session {sid} states deviate from the "
            f"unfaulted fleet — failover is not bit-exact"
        )
        assert np.array_equal(
            chaos_hit[sid].predictions, chaos_clean[sid].predictions
        ), f"smoke: recovered session {sid} predictions deviate"
        assert np.array_equal(
            np.asarray(chaos_hit[sid].learned_readout.w_out),
            np.asarray(chaos_clean[sid].learned_readout.w_out),
        ), f"smoke: recovered session {sid} learned weights deviate"
    print(
        f"smoke_chaos,0.0,crashed_1_recovered_"
        f"{chaos_faults['sessions_recovered']}_lost_"
        f"{chaos_faults['sessions_lost']}_replayed_"
        f"{chaos_faults['replayed_ticks']}_bitmatch_clean"
    )
    print(
        f"smoke_perf_gates,0.0,pipelined_min_"
        f"{min(c['pipelined_speedup'] for c in smoke_bench['cells']):.1f}x"
        f"_fleet_{ratio:.2f}x_planner_err_"
        f"{fl['planner_vs_measured_err']:.0%}"
        f"_tune_{tu['tune_speedup']:.1f}x"
        f"_revisit_{tune_revisit_speedup:.1f}x"
    )
    if save_dispatch_table:
        _save_dispatch_table(out)


def main(save_dispatch_table: bool = False) -> None:
    from benchmarks import (
        fig2_vectorfield,
        reservoir_tasks,
        roofline_lm,
        serve_throughput,
        table2_timing,
        table3_factors,
    )
    from repro.api.cache import enable_persistent_cache

    enable_persistent_cache()
    print("name,us_per_call,derived")
    fig2_vectorfield.run()
    _, per_step = table2_timing.run()
    table3_factors.run(per_step=per_step)
    reservoir_tasks.run()
    roofline_lm.run()
    # serving-perf trajectory: sessions/sec + ticks/sec over the (N, E) grid,
    # persisted to BENCH_serve.json for PR-over-PR comparison
    serve_throughput.run()
    if save_dispatch_table:
        _save_dispatch_table("BENCH_serve.json")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="fast CI subset: quick serving grid + unified-API compile/drive",
    )
    ap.add_argument(
        "--save-dispatch-table",
        action="store_true",
        help="after the run, persist measured dispatch choices for this "
        "platform via kernels.dispatch_table.save_table() (commit the "
        "resulting dispatch_table.<platform>.json from a GPU/TPU host)",
    )
    args = ap.parse_args()
    if args.smoke:
        smoke(save_dispatch_table=args.save_dispatch_table)
    else:
        main(save_dispatch_table=args.save_dispatch_table)
