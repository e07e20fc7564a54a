"""Serving throughput: pipelined chunked engine vs synchronous vs solo.

For each (N, E) cell the same workload — WAVES generations of E concurrent
length-TICKS streams, so admit/retire churn is part of the bill — runs
through three serving modes:

  sequential   one-at-a-time baseline: a single-slot engine's per-tick cost
               measured once and charged per session-tick
  sync         slot-batched per-tick serving (`engine.step()` loop): one
               `CompiledSim.tick` dispatch + per-tick harvest
  pipelined    chunked double-buffered serving (`engine.run()` with
               `ExecPlan(chunk_ticks=K)`): one dispatch and ONE bulk
               device->host transfer per K ticks, host assembly overlapped
               with device execution

plus, on every row, the PR-5 per-precision / chunk-impl twins: the same
steady workload re-served through the OTHER member of the
{default, chunk-resident} impl pair (reported as
`sessions_per_sec_chunk_impl` + the within-run `chunk_impl_speedup`
ratio; symmetric, so a previously seeded "chunk" winner keeps getting
challenged by "ref" and vice versa) and through `"mixed"` reduced
precision on the chunk impl (`sessions_per_sec_mixed` +
`precision_speedup`). Their steady reps interleave with the default
engine's so the container's ±40% noise bills every column equally —
`kernels.dispatch_table.seed_from_bench` registers a twin's entry for a
shape only when its within-run ratio beat the default;

plus, on the smaller grid rows, autoscale-vs-fixed: the same burst served
by a fixed E-slot engine and by an autoscaling engine that starts at E/4
and grows through the bucketed plan cache, and — at N <= LEARN_MAX_N —
learn-on vs learn-off: the steady workload re-served with every session
learning its readout online (`ExecPlan(learn="rls")`, per-tick fused RLS
updates + target upload + prediction harvest), reported as
`sessions_per_sec_learn` and the within-run `learn_overhead` ratio.
(Learning at N=1024 would allocate E (N+1)^2 P-matrices — ~1 GB at E=256 —
so the column stops at N=128, which is also where the acceptance bar for
the overhead lives.)

Reported per cell:

    ticks_per_sec     aggregate session-ticks per second, pipelined, from a
                      STEADY run (one wave of E long streams — boundary
                      churn amortizes to ~nothing, matching the warm-tick
                      methodology behind the earlier trajectory numbers)
    sessions_per_sec  ticks_per_sec / REF_STREAM_TICKS — completions/sec of
                      a reference 7-tick stream; 7 is the stream length
                      behind the PR-2 trajectory, so this column is
                      comparable across BENCH_serve.json history
    sessions_per_sec_sync  the same PR-2 formula from the per-tick median
    ticks_per_sec_burst / pipelined_speedup  the BURST workload (WAVES
                      generations, churn billed): pipelined vs step() wall
    speedup_vs_sequential  steady pipelined aggregate over sequential

Engines are built through the unified execution API: one SimSpec per N,
compiled against ExecPlans of different ensemble widths — so the backend
each cell reports is exactly what `repro.api.compile_plan` resolved from
the measured-latency dispatch table / platform gate for that (N, E).

plus a TUNE section (`bench_tune`): the same seeded hyperparameter search
over (drive current, spectral radius) on NARMA-10 run lane-vectorized
(candidates = ensemble lanes of one CompiledSim, fitness from the fused
online learner) and sequentially (ensemble=1) — `tune_speedup` is the
within-run wall-clock ratio and `best_match_sequential` pins that lane
width cannot change the winner.

plus a COMPILE section (`bench_compile`): cold vs warm engine spin-up
through the process-wide PlanCache, pure-AOT `lower().compile()` seconds,
and an in-process probe of the JAX persistent compilation cache
(cross-restart cold-start) — `BENCH_serve.json["compile"]`, refreshable
alone via `--compile-only`.

Emits the shared `name,us_per_call,derived` CSV rows and writes
BENCH_serve.json (benchmarks/run.py wires it into the suite) so future PRs
can track the serving-perf trajectory. `kernels.dispatch_table
.seed_from_bench` turns that JSON back into persisted dispatch entries
(`benchmarks/run.py --save-dispatch-table` commits them).

    PYTHONPATH=src python -m benchmarks.serve_throughput [--quick]
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row
from repro.api import ExecPlan, compile_plan, make_spec
from repro.serve.reservoir import ReservoirEngine, StreamSession
from repro.serve.scheduler import QueueDepthPolicy

NS = (16, 128, 1024)
ES = (8, 64, 256)
HOLD_STEPS = 5
CHUNK_TICKS = 8
TICKS = 32  # burst stream length: 4 chunks, boundary churn amortizes realistically
STEADY_TICKS = 56  # steady-median stream length: 7 chunks (warm 2 + median 3 + drain)
STEADY_REPS = 5  # best-of, like the per-tick median: noise spikes don't bill
WAVES = 2  # stream generations per burst measurement -> full-batch turnover
REF_STREAM_TICKS = 7  # PR-2 trajectory's stream length; sessions/sec anchor
WARM_TICKS = 2
MEASURED_TICKS = 3
AUTOSCALE_MAX_N = 128  # autoscale columns only where the grid row is cheap
LEARN_MAX_N = 128  # learn-on column: P is (E, N+1, N+1) — skip the 1 GB row


def _mk_sessions(num, t, n_in, rng, base_sid=0, learn=False):
    return [
        StreamSession(
            sid=base_sid + i,
            u_seq=rng.uniform(0.0, 0.5, size=(t, n_in)).astype(np.float32),
            collect_states=False,
            targets=(
                rng.uniform(0.0, 0.5, size=(t, 1)).astype(np.float32)
                if learn
                else None
            ),
        )
        for i in range(num)
    ]


def _tick_time(engine, sessions) -> float:
    """Median wall time of engine.step() once the batch is warm/compiled."""
    for s in sessions:
        engine.submit(s)
    for _ in range(WARM_TICKS):
        engine.step()
    jax.block_until_ready(engine.store.m)
    times = []
    for _ in range(MEASURED_TICKS):
        t0 = time.perf_counter()
        engine.step()
        jax.block_until_ready(engine.store.m)
        times.append(time.perf_counter() - t0)
    while engine.scheduler.has_work():  # drain
        engine.step()
    times.sort()
    return times[len(times) // 2]


def _steady_chunk_time(engine, sessions, warm=WARM_TICKS, measured=MEASURED_TICKS):
    """Median wall time of one mid-run CHUNK once the batch is warm.

    The chunked analogue of `_tick_time` — same estimator (median of a few
    warm samples, churn excluded) as the per-tick trajectory numbers this
    file has always reported, so sessions/sec stays comparable across
    BENCH_serve.json history. Each sample blocks on the chunk, which is
    the pessimistic (unpipelined) bound for steady throughput."""
    for s in sessions:
        engine.submit(s)
    times = []
    for _ in range(warm + measured):
        t0 = time.perf_counter()
        more = engine.step_chunk()
        jax.block_until_ready(engine.store.m)
        times.append(time.perf_counter() - t0)
        if not more:
            break
    engine.run([])  # drain the remainder through the public path
    times = sorted(times[warm:])
    return times[len(times) // 2]


def _drain_time(engine, sessions, pipelined: bool):
    """(wall seconds, session-ticks served) for a full drain of sessions."""
    ticks0 = engine.scheduler.stats.session_ticks
    t0 = time.perf_counter()
    if pipelined:
        engine.run(sessions)
    else:
        for s in sessions:
            engine.submit(s)
        while engine.scheduler.has_work():
            engine.step()
    jax.block_until_ready(engine.store.m)
    dt = time.perf_counter() - t0
    return dt, engine.scheduler.stats.session_ticks - ticks0


def bench_cell(n: int, e: int, print_fn=print):
    spec = make_spec(n=n, n_in=1, hold_steps=HOLD_STEPS, dtype=jnp.float32)
    rng = np.random.default_rng(0)

    # -- pipelined chunked serving (the headline path) ---------------------
    pipe_eng = ReservoirEngine(
        compile_plan(spec, ExecPlan(ensemble=e, chunk_ticks=CHUNK_TICKS)),
        max_retained=e,
    )
    backend = pipe_eng.backend
    _drain_time(pipe_eng, _mk_sessions(e, CHUNK_TICKS, 1, rng), pipelined=True)  # warm
    # per-precision / chunk-impl twin engines: same workload re-served
    # through (a) the other member of the {default, chunk-resident} impl
    # pair and (b) "mixed" precision on the chunk-resident impl. Their
    # steady reps INTERLEAVE with the default engine's below, so the ±40%
    # container noise bills all columns equally and the speedup ratios are
    # honest within-run comparisons. The twin is symmetric — when the
    # dispatch table already resolves the default to "chunk", the twin is
    # "ref" — so a once-seeded winner keeps being challenged by later
    # bench runs instead of ratcheting in place (seed_from_bench replaces
    # the default entry whenever the twin's within-run ratio beat it).
    twin_impl = "ref" if backend == "chunk" else "chunk"
    chunk_eng = ReservoirEngine(
        compile_plan(
            spec, ExecPlan(impl=twin_impl, ensemble=e, chunk_ticks=CHUNK_TICKS)
        ),
        max_retained=e,
    )
    _drain_time(
        chunk_eng, _mk_sessions(e, CHUNK_TICKS, 1, rng, base_sid=90_000),
        pipelined=True,
    )  # warm
    mixed_eng = ReservoirEngine(
        compile_plan(
            spec,
            ExecPlan(
                impl="chunk", ensemble=e, chunk_ticks=CHUNK_TICKS,
                precision="mixed",
            ),
        ),
        max_retained=e,
    )
    _drain_time(
        mixed_eng, _mk_sessions(e, CHUNK_TICKS, 1, rng, base_sid=95_000),
        pipelined=True,
    )  # warm
    # learn-on twin engine (N <= LEARN_MAX_N): same plan + learn="rls";
    # its steady reps INTERLEAVE with the learn-off reps below so a slow
    # container episode bills both sides of the overhead ratio equally
    learn_eng = None
    if n <= LEARN_MAX_N:
        learn_eng = ReservoirEngine(
            compile_plan(
                spec,
                ExecPlan(
                    impl=backend, ensemble=e, chunk_ticks=CHUNK_TICKS,
                    learn="rls", learn_reg=1e-2,
                ),
            ),
            max_retained=e,
        )
        _drain_time(
            learn_eng,
            _mk_sessions(e, CHUNK_TICKS, 1, rng, base_sid=70_000, learn=True),
            pipelined=True,
        )  # warm
    # steady chunk median: one wave of E long streams — the trajectory metric
    chunk_reps, learn_reps, chunkimpl_reps, mixed_reps = [], [], [], []
    for r in range(STEADY_REPS):
        chunk_reps.append(
            _steady_chunk_time(
                pipe_eng,
                _mk_sessions(e, STEADY_TICKS, 1, rng, base_sid=60_000 + 1000 * r),
            )
        )
        chunkimpl_reps.append(
            _steady_chunk_time(
                chunk_eng,
                _mk_sessions(e, STEADY_TICKS, 1, rng, base_sid=91_000 + 1000 * r),
            )
        )
        mixed_reps.append(
            _steady_chunk_time(
                mixed_eng,
                _mk_sessions(e, STEADY_TICKS, 1, rng, base_sid=96_000 + 1000 * r),
            )
        )
        if learn_eng is not None:
            learn_reps.append(
                _steady_chunk_time(
                    learn_eng,
                    _mk_sessions(
                        e, STEADY_TICKS, 1, rng,
                        base_sid=80_000 + 1000 * r, learn=True,
                    ),
                )
            )
    t_chunk = min(chunk_reps)
    # burst run: WAVES generations, admit/retire churn billed
    t_pipe, ticks_pipe = _drain_time(
        pipe_eng, _mk_sessions(WAVES * e, TICKS, 1, rng, base_sid=20_000), pipelined=True
    )

    # -- synchronous per-tick serving (the PR-2 path), same workload -------
    sync_eng = ReservoirEngine(
        compile_plan(spec, ExecPlan(impl=backend, ensemble=e)), max_retained=e
    )
    t_tick_sync = _tick_time(sync_eng, _mk_sessions(e, WARM_TICKS + MEASURED_TICKS + 2, 1, rng))
    t_sync, ticks_sync = _drain_time(
        sync_eng, _mk_sessions(WAVES * e, TICKS, 1, rng, base_sid=30_000), pipelined=False
    )

    # -- sequential baseline: E streams = E solo ticks per aggregate tick --
    solo = ReservoirEngine(compile_plan(spec, ExecPlan(impl=backend, ensemble=1)))
    t_solo = _tick_time(solo, _mk_sessions(1, WARM_TICKS + MEASURED_TICKS + 2, 1, rng, base_sid=10_000))

    ticks_per_sec = e * CHUNK_TICKS / t_chunk
    ticks_per_sec_burst = ticks_pipe / t_pipe
    ticks_per_sec_sync = ticks_sync / t_sync
    agg_solo = 1.0 / t_solo
    med = lambda xs: sorted(xs)[len(xs) // 2]
    cell = {
        "n": n,
        "e": e,
        "backend": backend,
        "precision": pipe_eng.precision,
        "chunk_ticks": CHUNK_TICKS,
        "stream_ticks": TICKS,
        "steady_ticks": STEADY_TICKS,
        "waves": WAVES,
        "steady_chunk_s": t_chunk,
        "pipelined_drain_s": t_pipe,
        "sync_drain_s": t_sync,
        "batched_tick_s": t_tick_sync,
        "solo_tick_s": t_solo,
        "ticks_per_sec": ticks_per_sec,
        "ticks_per_sec_burst": ticks_per_sec_burst,
        "ticks_per_sec_sync": ticks_per_sec_sync,
        "sessions_per_sec": ticks_per_sec / REF_STREAM_TICKS,
        "sessions_per_sec_sync": (e / t_tick_sync) / REF_STREAM_TICKS,
        "pipelined_speedup": t_sync / t_pipe,
        "speedup_vs_sequential": ticks_per_sec / agg_solo,
        "hold_steps": HOLD_STEPS,
    }

    # -- per-precision / chunk-impl columns (reps interleaved above) -------
    # ratios use MEDIANS of the rep samples, not mins: a single outlier-
    # fast rep on either side would otherwise swing the ratio by the
    # container's full ±40% noise band. Judge perf PRs by THESE within-run
    # ratio columns, never across-run absolutes (ROADMAP caveat).
    t_ci = min(chunkimpl_reps)
    cell.update(
        backend_chunk_impl=chunk_eng.backend,
        steady_chunk_chunkimpl_s=t_ci,
        ticks_per_sec_chunk_impl=e * CHUNK_TICKS / t_ci,
        sessions_per_sec_chunk_impl=(e * CHUNK_TICKS / t_ci) / REF_STREAM_TICKS,
        chunk_impl_speedup=med(chunk_reps) / med(chunkimpl_reps),
    )
    t_mixed = min(mixed_reps)
    cell.update(
        backend_mixed=mixed_eng.backend,
        precision_mixed=mixed_eng.precision,
        steady_chunk_mixed_s=t_mixed,
        ticks_per_sec_mixed=e * CHUNK_TICKS / t_mixed,
        sessions_per_sec_mixed=(e * CHUNK_TICKS / t_mixed) / REF_STREAM_TICKS,
        precision_speedup=med(chunk_reps) / med(mixed_reps),
    )

    # -- learn-on vs learn-off columns (reps measured interleaved above) ---
    if learn_eng is not None:
        t_chunk_learn = min(learn_reps)
        cell.update(
            steady_chunk_learn_s=t_chunk_learn,
            ticks_per_sec_learn=e * CHUNK_TICKS / t_chunk_learn,
            sessions_per_sec_learn=(e * CHUNK_TICKS / t_chunk_learn)
            / REF_STREAM_TICKS,
            # within-run ratio (the ROADMAP's ±40% container-noise caveat:
            # judge learn overhead by THIS column, not absolute numbers)
            learn_overhead=med(learn_reps) / med(chunk_reps),
        )

    # -- autoscale vs fixed: the same burst through the bucketed cache -----
    if n <= AUTOSCALE_MAX_N and e >= 16:
        start = max(8, e // 4)
        auto = ReservoirEngine(
            compile_plan(spec, ExecPlan(impl=backend, ensemble=start, chunk_ticks=CHUNK_TICKS)),
            autoscale=QueueDepthPolicy(),
            min_slots=start,
            max_slots=e,
            max_retained=e,
        )
        # warm the start-width compile out of the timed region (the fixed
        # engine got the same courtesy); growth-bucket compiles during the
        # burst stay billed — they ARE autoscale's cost
        _drain_time(auto, _mk_sessions(start, CHUNK_TICKS, 1, rng, base_sid=45_000), pipelined=True)
        t_auto, _ = _drain_time(
            auto, _mk_sessions(WAVES * e, TICKS, 1, rng, base_sid=50_000), pipelined=True
        )
        cell.update(
            autoscale_start_slots=start,
            autoscale_final_slots=auto.num_slots,
            autoscale_grows=auto.scheduler.stats.grows,
            fixed_burst_s=t_pipe,
            autoscale_burst_s=t_auto,
            autoscale_vs_fixed=t_pipe / t_auto,
        )

    print_fn(
        csv_row(
            f"serve_n{n}_e{e}",
            (t_pipe / max(1, ticks_pipe)) * e * 1e6,  # us per aggregate tick
            f"backend_{backend}_pipelined_{cell['pipelined_speedup']:.1f}x"
            f"_vs_seq_{cell['speedup_vs_sequential']:.1f}x",
        )
    )
    return cell


# ---------------------------------------------------------------------------
# tune tier: lane-vectorized hyperparameter search vs sequential
# ---------------------------------------------------------------------------

TUNE_N = 16
TUNE_BUDGET = 64  # candidates per search
TUNE_LANES = 32  # candidates per pass, vectorized config
TUNE_TICKS = 200  # NARMA ticks per candidate evaluation
TUNE_CHUNK_TICKS = 2  # small chunks: per-dispatch overhead is what E amortizes


def bench_tune(
    print_fn=print,
    budget: int = TUNE_BUDGET,
    lanes: int = TUNE_LANES,
    ticks: int = TUNE_TICKS,
) -> dict:
    """Tune columns, two measurements sharing one NARMA-10 task:

    SPEEDUP — the same seeded random search over (drive current, spectral
    radius), run lane-VECTORIZED (ExecPlan ensemble = `lanes` candidates
    per simulation pass, fitness from the fused online learner) and
    SEQUENTIAL (ensemble=1, one pass per candidate — the methodology the
    pre-tune examples/parameter_sweep.py hand-rolled). `tune_speedup` is
    the within-run wall-clock ratio; judge IT, never the absolute seconds
    (container ±40% noise, ROADMAP caveat). Both configs pay a warm-up
    search first so jit compiles stay out of the measured walls.

    WINNER MATCH — a grid search over well-separated points in the
    DYNAMICALLY STABLE regime, vectorized vs sequential; the winner must
    not depend on lane width (`grid_winner_match`). The stable-regime
    restriction is load-bearing: near the chaotic high-current edge a
    last-ulp difference between the E-wide and solo matmuls grows
    exponentially along the trajectory, so per-candidate fitness there is
    only reproducible at FIXED width (that bit-pin lives in
    tests/test_tune.py) — which is also why the random-search columns
    record best fitness per config rather than asserting equality."""
    from repro.tune import Choice, Float, SearchSpace, narma_task, tune_spec

    spec = make_spec(n=TUNE_N, n_in=1, hold_steps=HOLD_STEPS, dtype=jnp.float32)
    space = SearchSpace({
        "drive_current": Float(0.5e-3, 4.5e-3),
        "spectral_radius": Float(0.2, 1.2),
    })
    task = narma_task(t=ticks, order=10, seed=0, learn_washout=50)
    vec_plan = ExecPlan(
        impl="scan", ensemble=lanes, chunk_ticks=TUNE_CHUNK_TICKS, learn="rls"
    )
    seq_plan = ExecPlan(
        impl="scan", ensemble=1, chunk_ticks=TUNE_CHUNK_TICKS, learn="rls"
    )
    # warm both shapes' jit caches out of the measured region
    tune_spec(spec, task, space, budget=min(lanes, budget), plan=vec_plan, seed=99)
    tune_spec(spec, task, space, budget=1, plan=seq_plan, seed=99)

    vec = tune_spec(spec, task, space, budget=budget, plan=vec_plan, seed=0)
    seq = tune_spec(spec, task, space, budget=budget, plan=seq_plan, seed=0)
    speedup = seq.wall_s / vec.wall_s

    grid_space = SearchSpace({
        "drive_current": Choice([1e-3, 2e-3, 3e-3]),
        "spectral_radius": Choice([0.3, 0.6, 0.9]),
    })
    grid_budget = 9
    gv = tune_spec(spec, task, grid_space, budget=grid_budget, plan=vec_plan,
                   strategy="grid")
    gs = tune_spec(spec, task, grid_space, budget=grid_budget, plan=seq_plan,
                   strategy="grid")
    match = gv.best.assignment == gs.best.assignment

    tune = {
        "n": TUNE_N,
        "budget": budget,
        "lanes": lanes,
        "ticks": ticks,
        "chunk_ticks": TUNE_CHUNK_TICKS,
        "strategy": "random",
        "task": task.name,
        "wall_vectorized_s": vec.wall_s,
        "wall_sequential_s": seq.wall_s,
        "tune_speedup": speedup,
        "best_nmse": vec.best.fitness,
        "best_nmse_sequential": seq.best.fitness,
        "best_assignment": {k: float(v) for k, v in vec.best.assignment.items()},
        "grid_budget": grid_budget,
        "grid_winner": {k: float(v) for k, v in gv.best.assignment.items()},
        "grid_winner_match": match,
    }
    print_fn(
        csv_row(
            f"tune_b{budget}_l{lanes}",
            vec.wall_s * 1e6,
            f"speedup_{speedup:.1f}x_gridmatch_{str(match).lower()}"
            f"_nmse_{vec.best.fitness:.3f}",
        )
    )
    return tune


# ---------------------------------------------------------------------------
# fleet tier: multi-replica bursty mixed-N workload
# ---------------------------------------------------------------------------

FLEET_POOLS = ((16, 8), (128, 8))  # (N, slots per replica) per pool
FLEET_BURSTS = 2
FLEET_SESSIONS_PER_POOL_BURST = 16  # 2x replica slot width -> queueing


def _run_fleet_config(replicas: int, transport: str, pools, sessions_pp,
                      bursts: int, rng) -> tuple:
    """Serve the bursty mixed-N workload on `replicas` replicas per pool;
    returns (drain seconds, sessions served, session-ticks served).

    Bursts land mid-serve (a full wave of every pool's sessions at once,
    injected every few pump rounds) so the measurement includes the
    queueing/refill behavior the fleet exists for — not just a pre-loaded
    batch. Compile time is warmed out per pool first."""
    from repro.serve.fleet import FleetRouter, start_fleet

    router = FleetRouter()
    for n, e in pools:
        for r in start_fleet(
            replicas, transport, n=n, num_slots=e,
            hold_steps=HOLD_STEPS, chunk_ticks=CHUNK_TICKS,
        ):
            router.add_replica(r)
    # warm the full shape repertoire out of the timed region: the chunk
    # plan AND the admit/retire scatter shapes that wave turnover hits
    # (each distinct admission/retirement count is its own jit trace) —
    # same burst pattern, one-chunk streams
    sid = 900_000
    for _ in range(bursts):
        for n, _ in pools:
            for s in _mk_sessions(sessions_pp, CHUNK_TICKS, 1, rng, base_sid=sid):
                router.submit(n, s)
            sid += sessions_pp
        router.drain()

    burst_list = []
    for b in range(bursts):
        burst = []
        for n, _ in pools:
            for s in _mk_sessions(sessions_pp, TICKS, 1, rng, base_sid=sid):
                burst.append((n, s))
            sid += sessions_pp
        burst_list.append(burst)

    served = 0
    ticks0 = sum(
        st.session_ticks for pool in router.stats().values() for st in pool
    )
    t0 = time.perf_counter()
    bi = 0
    rounds = 0
    while True:
        if bi < len(burst_list) and rounds % 3 == 0:
            for n, s in burst_list[bi]:
                router.submit(n, s)
            bi += 1
        worked = router.run_for(1)
        served += len(router.results())
        rounds += 1
        if not worked and bi >= len(burst_list):
            break
    dt = time.perf_counter() - t0
    ticks = (
        sum(st.session_ticks for pool in router.stats().values() for st in pool)
        - ticks0
    )
    router.close()
    return dt, served, ticks


def bench_fleet(
    bench_payload: dict,
    replicas: int = 2,
    transport: str = None,
    print_fn=print,
) -> dict:
    """Fleet scaling column: R replicas per pool vs 1, same bursty mixed-N
    workload, plus the capacity planner's predicted-vs-measured error.

    The honest metric is the WITHIN-RUN ratio (fleet vs single replica on
    this host, minutes apart) — absolute sessions/sec moves with
    container noise. Replicas time-share cores, so the planner predicts
    the ratio as min(R, cores): near-linear on multi-core hosts, ~1.0 on
    a single-core host (where the fleet buys capacity and isolation, not
    FLOPs). Both prediction and measurement are recorded."""
    from repro.serve.fleet import CapacityModel, measure_probe_rates, usable_cores

    cores = usable_cores()
    if transport is None:
        # pipes only pay off when children get their own core
        transport = "process" if cores > 1 else "local"
    rng = np.random.default_rng(7)
    t1, m1, ticks1 = _run_fleet_config(
        1, transport, FLEET_POOLS, FLEET_SESSIONS_PER_POOL_BURST,
        FLEET_BURSTS, rng,
    )
    tr, mr, ticksr = _run_fleet_config(
        replicas, transport, FLEET_POOLS, FLEET_SESSIONS_PER_POOL_BURST,
        FLEET_BURSTS, rng,
    )
    assert m1 == mr, f"configs served different workloads: {m1} vs {mr}"
    speedup = (ticksr / tr) / (ticks1 / t1)
    predicted_speedup = float(min(replicas, cores))

    # planner absolute check: predicted drain time of the single-replica
    # config from the grid-calibrated SUSTAINED model (per pool: churn-
    # billed drain seconds; pools time-share the host, so times add). The
    # grid's absolute scale is only valid on the host state it was
    # recorded under (±40% container noise, ROADMAP caveat), so the
    # planner first recalibrates from a same-run probe: each pool cell
    # re-measured ONCE with the grid's own burst methodology on a bare
    # engine. Non-circular — the probe never touches the fleet stack the
    # measurement goes through, so the error still bills router/replica
    # overhead and the bursty-injection queueing. The probe engines draw
    # from the process-wide PlanCache (`measure_probe_rates`), so the
    # replicas that just served the workload above already paid every
    # compile the probe needs — recalibration costs pure measurement.
    planner = CapacityModel.from_bench(bench_payload)
    probe = measure_probe_rates(
        FLEET_POOLS,
        hold_steps=HOLD_STEPS,
        chunk_ticks=CHUNK_TICKS,
        stream_ticks=TICKS,
        waves=WAVES,
    )
    host_scale = planner.recalibrate(probe)
    sessions_total = FLEET_BURSTS * FLEET_SESSIONS_PER_POOL_BURST
    pred_t1 = sum(
        planner.drain_seconds(n, e, sessions_total, TICKS, replicas=1)
        for n, e in FLEET_POOLS
    )
    planner_err = abs(pred_t1 - t1) / t1
    fleet = {
        "replicas": replicas,
        "transport": transport,
        "cores": cores,
        "pools": [{"n": n, "slots": e} for n, e in FLEET_POOLS],
        "bursts": FLEET_BURSTS,
        "sessions": m1,
        "stream_ticks": TICKS,
        "single_drain_s": t1,
        "fleet_drain_s": tr,
        "sessions_per_sec_single": (ticks1 / t1) / REF_STREAM_TICKS,
        "sessions_per_sec_fleet": (ticksr / tr) / REF_STREAM_TICKS,
        "fleet_speedup": speedup,
        "predicted_speedup": predicted_speedup,
        "planner_host_scale": host_scale,
        "planner_predicted_single_drain_s": pred_t1,
        "planner_vs_measured_err": planner_err,
        "planner_fit_err": planner.prediction_error()["max"],
    }
    print_fn(
        csv_row(
            f"serve_fleet_x{replicas}",
            tr * 1e6,
            f"speedup_{speedup:.2f}x_predicted_{predicted_speedup:.1f}x"
            f"_planner_err_{planner_err:.0%}",
        )
    )
    return fleet


def fleet_smoke(replicas: int = 2, min_ratio: float = 1.5, print_fn=print) -> bool:
    """CI fleet smoke: bursty mixed-N workload through the ASYNC front-end
    (admission control in the loop), 2 replicas vs 1. Asserts the fleet
    drains cleanly everywhere; asserts the >= min_ratio session-throughput
    scaling only where the host has the cores to show it (replicas
    time-share cores, so a 1-core runner caps the honest ratio at ~1.0)."""
    import asyncio

    from repro.serve.fleet import FleetFrontend, FleetRouter, start_fleet, usable_cores

    pools = ((16, 8), (32, 8))
    sessions_pp = 12
    cores = usable_cores()
    transport = "process" if cores > 1 else "local"

    async def serve(n_replicas: int) -> tuple:
        rng = np.random.default_rng(11)
        router = FleetRouter()
        for n, e in pools:
            for r in start_fleet(
                n_replicas, transport, n=n, num_slots=e,
                hold_steps=HOLD_STEPS, chunk_ticks=CHUNK_TICKS,
            ):
                router.add_replica(r)
        async with FleetFrontend(router) as fleet:
            # warm compiles out of the timed region
            for n, _ in pools:
                await fleet.submit_stream(
                    n, rng.uniform(0.0, 0.5, (CHUNK_TICKS, 1)).astype(np.float32),
                    collect_states=False,
                )
            await fleet.drain_results()
            t0 = time.perf_counter()
            for _ in range(2):  # two bursts
                for n, _ in pools:
                    for _ in range(sessions_pp):
                        await fleet.submit_stream(
                            n,
                            rng.uniform(0.0, 0.5, (TICKS, 1)).astype(np.float32),
                            collect_states=False,
                        )
            results = await fleet.drain_results()
            dt = time.perf_counter() - t0
        return dt, len(results)

    want = 2 * sessions_pp * len(pools)
    t1, m1 = asyncio.run(serve(1))
    tr, mr = asyncio.run(serve(replicas))
    clean = m1 == want and mr == want
    ratio = (mr / tr) / (m1 / t1)
    print_fn(
        f"fleet smoke: {replicas} replicas vs 1 -> {ratio:.2f}x session "
        f"throughput ({cores} cores, transport={transport}); "
        f"drained {mr}/{want} and {m1}/{want}"
    )
    ok = clean
    if cores >= 2:
        ok = ok and ratio >= min_ratio
    else:
        print_fn(
            f"fleet smoke: single-core host — ratio gate (>= {min_ratio}x) "
            f"skipped, clean-drain gate enforced"
        )
    return ok


def bench_compile(quick: bool = False, print_fn=print) -> dict:
    """Compile-path columns: what the PlanCache and the persistent disk
    cache each buy, in seconds, on this host.

      cold_s            PLAN_CACHE.ensure_warm of a fresh structural spec:
                        XLA compile + first chunk execution
      warm_s            the identical call again — cache hit, zero compiles
      warm_speedup      cold_s / warm_s (the autoscale / fleet spin-up win;
                        benchmarks/run.py --smoke gates >= 5x)
      aot_s             lower().compile() of a second structural variant:
                        pure ahead-of-time compile seconds, no execution
      persistent_cold_s / persistent_warm_s / persistent_speedup
                        one process against the on-disk JAX compilation
                        cache (api/cache.resolve_cache_dir): AOT-compile a
                        third structural variant, drop JAX's in-memory
                        caches (jax.clear_caches), compile it again — the
                        second compile reads the executable back off disk,
                        the cold-start a restarted process pays.
                        persistent_cold_new_entries counts the files the
                        first compile wrote: 0 means an earlier run already
                        cached it and the cold column was warm too.
    Runs in one process: a child started after this process has touched a
    TPU could not reach the chip.
    """
    from repro.api import PLAN_CACHE, ExecPlan, compile_plan, make_spec
    from repro.api.cache import enable_persistent_cache, persistent_cache_dir

    # Deliberately off-grid N: the tick workers are module-level jit
    # functions, so any (shape, statics) signature another section already
    # ran would make "cold" a JAX-level jit hit instead of a real XLA
    # compile. An N no other section uses guarantees cold pays the
    # compile; the unique seed keeps the PlanCache entry fresh too.
    n, e = (19, 8) if quick else (131, 16)
    plan = ExecPlan(ensemble=e, chunk_ticks=CHUNK_TICKS)
    spec_cold = make_spec(
        n=n, n_in=1, hold_steps=HOLD_STEPS, seed=91_001, dtype=jnp.float32
    )

    compiles0 = PLAN_CACHE.stats.compiles
    t0 = time.perf_counter()
    PLAN_CACHE.ensure_warm(spec_cold, plan)
    cold_s = time.perf_counter() - t0
    cold_compiles = PLAN_CACHE.stats.compiles - compiles0
    t0 = time.perf_counter()
    PLAN_CACHE.ensure_warm(spec_cold, plan)
    warm_s = time.perf_counter() - t0
    warm_compiles = PLAN_CACHE.stats.compiles - compiles0 - cold_compiles

    # AOT column on a distinct structural variant so it pays a real lower
    spec_aot = make_spec(
        n=n, n_in=1, hold_steps=HOLD_STEPS + 2, seed=91_002, dtype=jnp.float32
    )
    t0 = time.perf_counter()
    compile_plan(spec_aot, plan).aot_compile()
    aot_s = time.perf_counter() - t0

    if not enable_persistent_cache():
        raise RuntimeError(
            "persistent compilation cache is pinned to another directory "
            f"({persistent_cache_dir()!r}); the persistent columns need "
            "the resolved one"
        )
    cache_dir = persistent_cache_dir()
    spec_p = make_spec(
        n=n, n_in=1, hold_steps=HOLD_STEPS + 4, seed=91_003, dtype=jnp.float32
    )
    entries0 = len(os.listdir(cache_dir))
    t0 = time.perf_counter()
    compile_plan(spec_p, plan).aot_compile()
    persistent_cold_s = time.perf_counter() - t0
    new_entries = len(os.listdir(cache_dir)) - entries0
    jax.clear_caches()
    t0 = time.perf_counter()
    compile_plan(spec_p, plan).aot_compile()
    persistent_warm_s = time.perf_counter() - t0

    out = {
        "n": n,
        "slots": e,
        "chunk_ticks": CHUNK_TICKS,
        "cold_s": cold_s,
        "cold_compiles": cold_compiles,
        "warm_s": warm_s,
        "warm_compiles": warm_compiles,
        "warm_speedup": cold_s / max(warm_s, 1e-9),
        "aot_s": aot_s,
        "persistent_cold_s": persistent_cold_s,
        "persistent_warm_s": persistent_warm_s,
        "persistent_speedup": persistent_cold_s / max(persistent_warm_s, 1e-9),
        "persistent_cold_new_entries": new_entries,
        "persistent_cache_dir": cache_dir,
        "cache_stats": PLAN_CACHE.stats.snapshot(),
    }
    print_fn(
        csv_row(
            "serve_compile_cold",
            cold_s * 1e6,
            f"warm_{out['warm_speedup']:.0f}x_aot_{aot_s:.2f}s",
        )
    )
    print_fn(
        csv_row(
            "serve_compile_persistent",
            persistent_warm_s * 1e6,
            f"vs_cold_{out['persistent_speedup']:.2f}x_new_{new_entries}",
        )
    )
    return out


def run(
    out_path: str = "BENCH_serve.json",
    quick: bool = False,
    fleet: bool = True,
    replicas: int = 2,
    tune: bool = True,
    print_fn=print,
):
    ns = (16, 128) if quick else NS
    es = (8, 64) if quick else ES
    cells = [bench_cell(n, e, print_fn=print_fn) for n in ns for e in es]
    payload = {
        "benchmark": "serve_throughput",
        "backend_platform": jax.default_backend(),
        "hold_steps": HOLD_STEPS,
        "chunk_ticks": CHUNK_TICKS,
        "stream_ticks": TICKS,
        "ref_stream_ticks": REF_STREAM_TICKS,
        "cells": cells,
    }
    if fleet:
        # planner calibrates from the cells just measured — same run, same
        # host, so the predicted-vs-measured column is apples to apples
        payload["fleet"] = bench_fleet(
            payload, replicas=replicas, print_fn=print_fn
        )
    if tune:
        payload["tune"] = bench_tune(print_fn=print_fn)
    payload["compile"] = bench_compile(quick=quick, print_fn=print_fn)
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    print_fn(csv_row("serve_json", 0.0, out_path))
    return cells


def run_fleet_only(
    out_path: str = "BENCH_serve.json", replicas: int = 2, print_fn=print
):
    """Re-measure ONLY the fleet section, merging into the existing grid
    file (the 9-cell grid takes minutes; the fleet column takes seconds)."""
    with open(out_path) as f:
        payload = json.load(f)
    payload["fleet"] = bench_fleet(payload, replicas=replicas, print_fn=print_fn)
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    print_fn(csv_row("serve_json", 0.0, out_path))
    return payload["fleet"]


def run_compile_only(out_path: str = "BENCH_serve.json", print_fn=print):
    """Re-measure ONLY the compile section, merging into the existing grid
    file (the compile columns take seconds, the grid takes minutes)."""
    with open(out_path) as f:
        payload = json.load(f)
    payload["compile"] = bench_compile(print_fn=print_fn)
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    print_fn(csv_row("serve_json", 0.0, out_path))
    return payload["compile"]


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--no-fleet", action="store_true",
                    help="skip the fleet scaling column")
    ap.add_argument("--no-tune", action="store_true",
                    help="skip the tune (vectorized search) columns")
    ap.add_argument("--fleet-only", action="store_true",
                    help="re-measure only the fleet column, merge into --out")
    ap.add_argument("--compile-only", action="store_true",
                    help="re-measure only the cold/warm/persistent compile "
                         "columns, merge into --out")
    ap.add_argument("--fleet-smoke", action="store_true",
                    help="CI gate: 2-replica bursty mixed-N smoke through "
                         "the async front-end; exits nonzero on failure")
    args = ap.parse_args()
    from repro.api.cache import enable_persistent_cache

    enable_persistent_cache()
    if args.fleet_smoke:
        raise SystemExit(0 if fleet_smoke(replicas=args.replicas) else 1)
    elif args.fleet_only:
        run_fleet_only(out_path=args.out, replicas=args.replicas)
    elif args.compile_only:
        run_compile_only(out_path=args.out)
    else:
        run(out_path=args.out, quick=args.quick, fleet=not args.no_fleet,
            replicas=args.replicas, tune=not args.no_tune)
