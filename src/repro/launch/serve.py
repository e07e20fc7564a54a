"""Batched serving launchers.

Two engines share the slot-batching idea:

LM mode (default) — continuous batching on the transformer engine
(repro/serve/engine.py): requests stream through a fixed slot pool;
finished slots refill immediately via prefill + cache splice.

    PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-1.8b \
        --reduced --requests 8 --slots 4 --gen 16

Reservoir mode — the multi-tenant streaming reservoir engine
(repro/serve/reservoir.py): client streams are slot-batched onto the
ensemble axis so one batched RK4 integrate advances every session per
tick. `--chunk-ticks K` serves K ticks per dispatch through the pipelined
chunked path (one bulk transfer per chunk); `--autoscale` grows/shrinks
the slot count under load through the bucketed plan cache.

    PYTHONPATH=src python -m repro.launch.serve --mode reservoir \
        --n 128 --slots 64 --sessions 96 --ticks 50 --backend auto \
        --chunk-ticks 8 --autoscale --max-slots 256

`--learn rls|lms` turns the tenants into online-learning NARMA streams
(per-tenant readouts train on device while serving), and
`--autotune-budget B` washout-auto-tunes the first tenant's physical
parameters on the live engine before it streams (repro/tune):

    PYTHONPATH=src python -m repro.launch.serve --mode reservoir \
        --n 64 --slots 8 --sessions 12 --ticks 120 --learn rls \
        --autotune-budget 8

Fleet mode — `--fleet` lifts reservoir serving onto the fleet tier
(repro/serve/fleet/): `--replicas R` engine replicas per N-pool behind
the asyncio front-end, with sessions placed least-loaded, capacity
planned from BENCH_serve.json when present, and `--transport process`
putting each replica in its own OS process.

    PYTHONPATH=src python -m repro.launch.serve --mode reservoir --fleet \
        --replicas 2 --n 16 --slots 8 --sessions 48 --ticks 50 \
        --transport local
"""

import argparse
import time


def main_lm(args):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, reduce_config
    from repro.models import build_model
    from repro.serve.engine import Engine, Request

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))

    key = jax.random.PRNGKey(1)
    reqs = []
    for i in range(args.requests):
        key, k = jax.random.split(key)
        # ragged prompt lengths exercise the scheduler
        length = args.prompt_len - (i % 4)
        reqs.append(
            Request(
                i,
                jax.random.randint(k, (length,), 0, cfg.vocab_size).astype(jnp.int32),
                args.gen,
            )
        )

    capacity = args.prompt_len + args.gen
    eng = Engine(cfg, params, num_slots=args.slots, capacity=capacity)
    t0 = time.time()
    results = eng.run(reqs)
    dt = time.time() - t0
    total_toks = sum(len(v) for v in results.values())
    for rid in sorted(results):
        print(f"  req{rid}: {results[rid]}")
    print(f"served {len(results)} requests / {total_toks} tokens in {dt:.2f}s "
          f"({total_toks / dt:.1f} tok/s incl. compile) with {args.slots} slots")


#: default search ranges for --autotune-budget knobs (lane knobs only — a
#: live engine cannot recompile; see repro.tune.washout_autotune)
AUTOTUNE_RANGES = {
    "drive_current": (0.5e-3, 4.5e-3),
    "spectral_radius": (0.2, 1.2),
    "input_gain": (0.1, 2.0),
}


def main_reservoir(args):
    import jax.numpy as jnp
    import numpy as np

    from repro.api import ExecPlan, compile_plan, make_spec
    from repro.core import fit_ridge, tasks
    from repro.serve.reservoir import ReservoirEngine, StreamSession

    spec = make_spec(
        n=args.n, n_in=1, hold_steps=args.hold_steps, dtype=jnp.float32
    )
    rng = np.random.default_rng(1)
    if args.learn:
        # online-learning tenants: every session trains its readout on
        # device against its own NARMA-2 targets while it streams
        sessions = []
        for i in range(args.sessions):
            u_i, y_i = tasks.narma_series(args.ticks, order=2, seed=i)
            sessions.append(
                StreamSession(
                    sid=i,
                    u_seq=u_i[:, None].astype(np.float32),
                    targets=y_i[:, None].astype(np.float32),
                    learn_washout=args.learn_washout,
                    collect_states=False,
                )
            )
    else:
        # one shared trained readout per task flavor (NARMA here); tenants
        # could each bring their own — see examples/serve_reservoir.py
        u_tr, y_tr = tasks.narma_series(args.ticks * 4, order=2, seed=0)
        _, states_tr = compile_plan(spec, impl="scan").drive(
            jnp.asarray(u_tr[:, None], jnp.float32)
        )
        readout = fit_ridge(
            states_tr, jnp.asarray(y_tr[:, None], jnp.float32), washout=10,
            reg=1e-6,
        )
        sessions = [
            StreamSession(
                sid=i,
                u_seq=rng.uniform(0.0, 0.5, size=(args.ticks, 1)).astype(np.float32),
                readout=readout,
                collect_states=False,
            )
            for i in range(args.sessions)
        ]

    autoscale_kw = {}
    if args.autoscale:
        autoscale_kw = dict(
            autoscale=True,
            min_slots=args.min_slots or args.slots,
            max_slots=args.max_slots or args.slots,
        )
    eng = ReservoirEngine(
        compile_plan(
            spec,
            ExecPlan(
                impl=args.backend,
                ensemble=args.slots,
                measure=args.measure,
                chunk_ticks=args.chunk_ticks,
                precision=args.precision,
                learn=args.learn,
                compilation_cache_dir=args.compilation_cache_dir,
            ),
        ),
        **autoscale_kw,
    )

    probe = None
    if args.autotune_budget:
        # washout auto-tune the FIRST tenant on the live engine: probes
        # stream its washout prefix on spare lanes, the winner's knobs are
        # frozen into the session, and it queues tuned (repro.tune)
        from repro.tune import Float, SearchSpace

        knobs = [k.strip() for k in args.autotune_knobs.split(",") if k.strip()]
        bad = [k for k in knobs if k not in AUTOTUNE_RANGES]
        if bad:
            raise SystemExit(
                f"--autotune-knobs: unknown {bad}; choose from "
                f"{sorted(AUTOTUNE_RANGES)}"
            )
        space = SearchSpace({k: Float(*AUTOTUNE_RANGES[k]) for k in knobs})
        tuned, rest = sessions[0], sessions[1:]
        probe = eng.submit_autotuned(
            tuned, space, budget=args.autotune_budget, seed=0
        )
        sessions = rest

    t0 = time.time()
    results = eng.run(sessions)
    dt = time.time() - t0
    st = eng.scheduler.stats
    print(f"backend={eng.backend} precision={eng.precision} "
          f"slots={eng.num_slots} N={args.n} "
          f"hold_steps={args.hold_steps} chunk_ticks={eng.chunk_ticks}"
          + (f" learn={eng.learn}" if eng.learn else ""))
    print(f"served {len(results)} sessions / {st.session_ticks} session-ticks "
          f"in {dt:.2f}s ({st.session_ticks / dt:.1f} ticks/s incl. compile; "
          f"{st.ticks} wall ticks, occupancy {eng.scheduler.occupancy():.2f}, "
          f"mean queue wait {eng.scheduler.mean_queue_wait():.1f} ticks"
          + (f", grows {st.grows} shrinks {st.shrinks}" if args.autoscale else "")
          + ")")
    if args.learn:
        nmses = [r.learn_nmse for r in results.values() if r.learn_nmse is not None]
        print(f"online learning: mean nmse {float(np.mean(nmses)):.4f} "
              f"over {len(nmses)} tenants")
    if probe is not None:
        best = probe.best
        print(f"washout autotune: {len(probe.trials)} probes on the live "
              f"engine; tenant 0 served with "
              + ", ".join(f"{k}={v:.4g}" for k, v in best.assignment.items())
              + f" (probe nmse {best.fitness:.4f}, "
                f"full-stream nmse {results[0].learn_nmse:.4f})")


def main_fleet(args):
    import asyncio
    import os

    import jax
    import numpy as np

    from repro.serve.fleet import (
        CapacityModel,
        FleetFrontend,
        FleetRouter,
        start_fleet,
        usable_cores,
    )

    planner = None
    bench = args.bench or "BENCH_serve.json"
    if os.path.exists(bench):
        planner = CapacityModel.from_bench(bench).checked_for(
            jax.default_backend()
        )
    if planner is not None:
        err = planner.prediction_error()
        print(
            f"planner: calibrated from {bench} "
            f"(fit err median {err['median']:.0%} max {err['max']:.0%})"
        )
    elif not os.path.exists(bench):
        print(f"planner: {bench} not found — admission control disabled")

    router = FleetRouter(
        planner=planner,
        checkpoint_every=args.checkpoint_every or None,
    )
    fleet_kw = dict(
        transport=args.transport,
        rpc_timeout_s=args.rpc_timeout,
        rpc_retries=args.rpc_retries,
        n=args.n,
        num_slots=args.slots,
        hold_steps=args.hold_steps,
        backend=args.backend,
        chunk_ticks=args.chunk_ticks,
        precision=args.precision,
        compilation_cache_dir=args.compilation_cache_dir,
    )
    replicas = start_fleet(args.replicas, **fleet_kw)

    def respawn():
        # failover replacement: same config, drawn warm through the
        # process-wide plan cache (or the persistent compile cache for
        # process transports pointed at --compilation-cache-dir)
        (r,) = start_fleet(1, **fleet_kw)
        return r

    for r in replicas:
        router.add_replica(r, respawn=respawn if args.checkpoint_every else None)

    rng = np.random.default_rng(1)
    streams = [
        rng.uniform(0.0, 0.5, size=(args.ticks, 1)).astype(np.float32)
        for _ in range(args.sessions)
    ]

    async def serve():
        async with FleetFrontend(router) as fleet:
            t0 = time.time()
            sids = [
                await fleet.submit_stream(args.n, u, collect_states=False)
                for u in streams
            ]
            results = await fleet.drain_results()
            dt = time.time() - t0
            stats = fleet.stats()[args.n]
            return sids, results, dt, stats, fleet.fault_stats()

    sids, results, dt, stats, faults = asyncio.run(serve())
    ticks = sum(s.session_ticks for s in stats)
    print(
        f"fleet: {args.replicas}x(N={args.n}, E={args.slots}) "
        f"transport={args.transport} cores={usable_cores()}"
    )
    if planner is not None:
        pred = planner.fleet_sessions_per_sec(
            args.n, args.slots, replicas=args.replicas
        )
        print(f"planner-predicted capacity: {pred:.1f} ref-sessions/s")
    print(
        f"served {len(results)} sessions / {ticks} session-ticks in "
        f"{dt:.2f}s ({ticks / dt:.1f} ticks/s incl. compile; per-replica "
        f"occupancy {[round(s.occupancy, 2) for s in stats]})"
    )
    if args.checkpoint_every or any(faults.values()):
        print(
            "fault tolerance: "
            + ", ".join(f"{k}={v}" for k, v in sorted(faults.items()))
        )
    return {sid: (u, results.get(sid)) for sid, u in zip(sids, streams)}


def main(argv=None):
    """Parse argv and serve. Fleet mode returns {sid: (input stream,
    SessionResult or None if it never drained)}."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["lm", "reservoir"], default="lm")
    # lm mode
    ap.add_argument("--arch")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8)
    # reservoir mode
    ap.add_argument("--n", type=int, default=128)
    ap.add_argument("--sessions", type=int, default=16)
    ap.add_argument("--ticks", type=int, default=50)
    ap.add_argument("--hold-steps", type=int, default=20)
    ap.add_argument("--backend", default="auto")
    ap.add_argument("--precision", default=None,
                    choices=["highest", "bf16_coupling", "mixed"],
                    help="numerical policy for the compute-bound GEMMs "
                    "(default: bit-exact; see ExecPlan.precision)")
    ap.add_argument("--measure", action="store_true",
                    help="time backend candidates for this (N, E) first")
    ap.add_argument("--chunk-ticks", type=int, default=8,
                    help="input ticks per serving dispatch (pipelined chunks)")
    ap.add_argument("--learn", default=None, choices=["rls", "lms"],
                    help="online per-tenant readout learning: sessions "
                         "stream NARMA-2 targets and train on device "
                         "(ExecPlan.learn)")
    ap.add_argument("--learn-washout", type=int, default=20,
                    help="ticks before the first on-device learner update")
    ap.add_argument("--autotune-budget", type=int, default=0,
                    help="washout auto-tune the first tenant on the live "
                         "engine with this many probe candidates "
                         "(requires --learn; repro.tune)")
    ap.add_argument("--autotune-knobs", default="drive_current,spectral_radius",
                    help="comma-separated lane knobs to search "
                         f"(from {sorted(AUTOTUNE_RANGES)})")
    ap.add_argument("--autoscale", action="store_true",
                    help="grow/shrink the slot count under load "
                         "(bucketed plan cache, QueueDepthPolicy)")
    ap.add_argument("--min-slots", type=int, default=None,
                    help="autoscale floor (default: --slots)")
    ap.add_argument("--max-slots", type=int, default=None,
                    help="autoscale ceiling (default: --slots)")
    # fleet tier (reservoir mode only)
    ap.add_argument("--fleet", action="store_true",
                    help="serve through the fleet tier (replicated engines "
                         "behind the asyncio front-end)")
    ap.add_argument("--replicas", type=int, default=2,
                    help="engine replicas in the N-pool (fleet mode)")
    ap.add_argument("--transport", choices=["local", "process"],
                    default="local",
                    help="replica transport: in-process event-loop tasks or "
                         "one OS process per replica (pipe, chunk batches)")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="auto-checkpoint live fleet sessions every K "
                         "router rounds (0: failover off); a crashed "
                         "replica's sessions then restore bit-identically "
                         "onto a respawned replacement")
    ap.add_argument("--rpc-timeout", type=float, default=120.0,
                    help="per-RPC reply deadline for process replicas; a "
                         "hung child trips it and is treated as dead")
    ap.add_argument("--rpc-retries", type=int, default=3,
                    help="send-side RPC retries (exponential backoff) "
                         "before a process replica is declared dead")
    ap.add_argument("--bench", default=None,
                    help="BENCH_serve.json to calibrate the capacity planner "
                         "from (default: ./BENCH_serve.json if present)")
    ap.add_argument("--compilation-cache-dir", default=None,
                    help="directory for JAX's persistent compilation cache "
                         "(default: .jax_cache/ in the checkout; "
                         "JAX_COMPILATION_CACHE_DIR, when set, wins): XLA "
                         "executables round-trip through disk, so a "
                         "restarted server (and every process replica) "
                         "skips its cold-start compiles")
    args = ap.parse_args(argv)

    if args.autotune_budget and not args.learn:
        ap.error("--autotune-budget requires --learn (probe fitness is the "
                 "on-device learner's nmse)")
    if args.mode == "reservoir":
        from repro.api.cache import enable_persistent_cache, persistent_cache_dir

        enable_persistent_cache(args.compilation_cache_dir)
        # process replicas inherit the resolved directory
        args.compilation_cache_dir = persistent_cache_dir()
        if args.fleet:
            return main_fleet(args)
        main_reservoir(args)
    elif args.fleet:
        ap.error("--fleet requires --mode reservoir")
    else:
        if not args.arch:
            ap.error("--arch is required in lm mode")
        main_lm(args)


if __name__ == "__main__":
    main()
