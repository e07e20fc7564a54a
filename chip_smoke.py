#!/usr/bin/env python3
"""Smoke run of the served reservoir engine on a TPU chip.

A smoke run, not a measurement: it proves that the main serving path starts
on the chip through the entry points a user calls, at the width of the
serve grid's heaviest row, and that what comes out agrees with a reference
computed on the same device. The seconds and rates it prints include
first-call effects and come from one run; they are not benchmark numbers.

    python chip_smoke.py                 # one TPU chip: serve, learn, kernels, fleet
    python chip_smoke.py --four-chips    # only the sharded engine on 4 chips,
                                         # against the same sessions on one chip
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny [--four-chips]
                                         # CPU rehearsal at toy sizes, Pallas in
                                         # interpret mode; never prints ok

Phases (one chip):
  serve    ReservoirEngine over compile_plan(make_spec(n=1024, n_in=1,
           hold_steps=5), ExecPlan(impl="auto", ensemble=256, chunk_ticks=8)):
           512 sessions x 64 ticks (two waves through 256 slots); 8 sampled
           sessions' states against compile_plan(spec, impl="scan").drive.
  learn    the same spec with learn="rls", 64 slots: 64 NARMA-2 sessions x
           128 ticks; each learned readout against core.fit_rls(states,
           targets, block=8) on the served states.
  kernels  each Pallas impl (fused, tiled, chunk) x precision (highest,
           bf16_coupling), one tick_chunk at N in {128, 1024}, E=256: runs,
           matches impl="ref" at the same precision and holds a
           tpu_custom_call in its compiled HLO, or is refused up front by
           compile_plan (the VMEM fit check).
  fleet    launch/serve.py --mode reservoir --fleet --transport local, 2
           replicas, N=16, 8 slots, 48 sessions, in this process; every
           session drains and its final state matches a solo scan drive.

Tolerances (TOL below). Each is the largest deviation the same check gives
on the CPU between a float32 run and a float64 run of the reference, times
a factor of 10. The chip compares two float32 computations that differ in
operation order; float32 rounding, amplified by the dynamics, is the scale
such a difference can reach. The CPU deviations, for the record:
  serve     scan drive, N=1024, 64 ticks x 5 steps            4.1e-05
  learn     fit_rls(block=8, reg=1e-2) on N=1024 states,
            128 NARMA-2 ticks, relative to max |W|             9.9e-04
  kernels   ref tick_chunk, K=8 x 5 steps, N=128 and 1024     1.6e-06
            the same under precision="bf16_coupling"          1.6e-06
  fleet     final m of a scan drive, N=16, 23 ticks x 10      1.7e-05
  sharded   ref tick_chunks, N=4096, 16 ticks x 5 steps       4.1e-06

The last line of standard output is one JSON object, {"ok": true, "device":
{"platform", "kind", "count"}}, printed only after every phase passed on a
TPU. Any failure raises and exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

TOL = {
    "serve": 4.1e-4,
    "learn": 9.9e-3,
    "kernels/highest": 1.6e-5,
    "kernels/bf16_coupling": 1.6e-5,
    "fleet": 1.7e-4,
    "sharded": 4.1e-5,
}

FULL = dict(
    n=1024, slots=256, sessions=512, ticks=64, sample=8, hold=5, chunk=8,
    learn_slots=64, learn_ticks=128, kernel_ns=(128, 1024),
    kernel_e=256, fleet_sessions=48,
    shard_n=4096, shard_e=128, shard_sessions=64, shard_ticks=16,
)
TINY = dict(
    n=16, slots=8, sessions=16, ticks=16, sample=4, hold=2, chunk=4,
    learn_slots=4, learn_ticks=16, kernel_ns=(16,),
    kernel_e=8, fleet_sessions=12,
    shard_n=16, shard_e=8, shard_sessions=8, shard_ticks=8,
)
LEARN_REG = 1e-2


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def report(phase, impl, compile_s, wall_s, session_ticks, dev, tol, extra=""):
    check(dev <= tol, f"{phase}: max deviation {dev:.3e} exceeds {tol:.1e}")
    rate = session_ticks / wall_s if wall_s > 0 else float("nan")
    print(
        f"[smoke run, not a measurement] {phase}: impl={impl} "
        f"compile_s={compile_s:.2f} wall_s={wall_s:.2f} "
        f"session_ticks_per_s={rate:.1f} max_dev={dev:.3e} tol={tol:.1e}"
        + (f" {extra}" if extra else ""),
        flush=True,
    )


def narma(ticks, seed):
    import numpy as np

    from repro.core import tasks

    u, y = tasks.narma_series(ticks, order=2, seed=seed)
    return u.astype(np.float32)[:, None], y.astype(np.float32)[:, None]


def phase_serve(z, interpret):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import ExecPlan, compile_plan, make_spec
    from repro.serve.reservoir import ReservoirEngine, StreamSession

    spec = make_spec(n=z["n"], n_in=1, hold_steps=z["hold"], dtype=jnp.float32)
    t0 = time.perf_counter()
    sim = compile_plan(
        spec,
        ExecPlan(impl="auto", ensemble=z["slots"], chunk_ticks=z["chunk"],
                 interpret=interpret),
    )
    sim.warmup()
    compile_s = time.perf_counter() - t0
    rng = np.random.default_rng(0)
    streams = rng.uniform(0.0, 0.5, size=(z["sessions"], z["ticks"], 1))
    streams = streams.astype(np.float32)
    sample = set(np.linspace(0, z["sessions"] - 1, z["sample"]).astype(int))
    eng = ReservoirEngine(sim)
    t0 = time.perf_counter()
    results = eng.run([
        StreamSession(sid=i, u_seq=streams[i], collect_states=i in sample)
        for i in range(z["sessions"])
    ])
    wall_s = time.perf_counter() - t0
    check(len(results) == z["sessions"], "serve: not every session finished")
    check(all(r.error is None for r in results.values()), "serve: a lane failed")
    oracle = compile_plan(spec, impl="scan")
    dev = 0.0
    for i in sorted(sample):
        _, ref = oracle.drive(jnp.asarray(streams[i]))
        got = results[i].states
        check(got is not None and got.shape == ref.shape, "serve: states shape")
        check(bool(np.isfinite(got).all()), "serve: non-finite states")
        dev = max(dev, float(np.max(np.abs(got - np.asarray(ref)))))
    jax.block_until_ready(eng.store.m)
    report("serve", sim.impl, compile_s, wall_s,
           z["sessions"] * z["ticks"], dev, TOL["serve"],
           f"sessions={z['sessions']} slots={z['slots']} N={z['n']}")


def phase_learn(z, interpret):
    import jax.numpy as jnp
    import numpy as np

    from repro.api import ExecPlan, compile_plan, make_spec
    from repro.core import fit_rls
    from repro.serve.reservoir import ReservoirEngine, StreamSession

    spec = make_spec(n=z["n"], n_in=1, hold_steps=z["hold"], dtype=jnp.float32)
    t0 = time.perf_counter()
    sim = compile_plan(
        spec,
        ExecPlan(impl="auto", ensemble=z["learn_slots"], chunk_ticks=z["chunk"],
                 learn="rls", learn_reg=LEARN_REG, interpret=interpret),
    )
    sim.warmup()
    compile_s = time.perf_counter() - t0
    series = {i: narma(z["learn_ticks"], seed=i) for i in range(z["learn_slots"])}
    eng = ReservoirEngine(sim)
    t0 = time.perf_counter()
    results = eng.run([
        StreamSession(sid=i, u_seq=u, targets=y) for i, (u, y) in series.items()
    ])
    wall_s = time.perf_counter() - t0
    check(len(results) == len(series), "learn: not every session finished")
    dev = 0.0
    for i, r in results.items():
        check(r.error is None and r.learned_readout is not None,
              f"learn: session {i} has no learned readout")
        w = np.asarray(r.learned_readout.w_out)
        check(bool(np.isfinite(w).all()), f"learn: session {i} non-finite W")
        ref = np.asarray(
            fit_rls(r.states, series[i][1], reg=LEARN_REG, block=z["chunk"]).w_out
        )
        dev = max(dev, float(np.max(np.abs(w - ref)) / np.max(np.abs(ref))))
    report("learn", sim.impl, compile_s, wall_s,
           len(series) * z["learn_ticks"], dev, TOL["learn"],
           f"sessions={len(series)} slots={z['learn_slots']} N={z['n']} "
           "(deviation relative to max |W|)")


def phase_kernels(z, interpret):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import ExecPlan, compile_plan, make_spec
    from repro.kernels import ops

    e, k = z["kernel_e"], z["chunk"]
    rng = np.random.default_rng(1)
    u = jnp.asarray(rng.uniform(0.0, 0.5, size=(k, e, 1)), jnp.float32)
    mask = jnp.ones((k, e), bool)
    for n in z["kernel_ns"]:
        spec = make_spec(n=n, n_in=1, hold_steps=z["hold"], dtype=jnp.float32)
        m = ops.to_planes(jnp.broadcast_to(spec.m0, (e, n, 3)))
        for precision in ("highest", "bf16_coupling"):
            ref = compile_plan(spec, ExecPlan(
                impl="ref", ensemble=e, chunk_ticks=k, precision=precision))
            ref_states = np.asarray(ref.tick_chunk(m, u, lane_mask=mask)[1])
            for impl in ("fused", "tiled", "chunk"):
                name = f"kernels/{impl}/{precision}/N={n}"
                plan = ExecPlan(impl=impl, ensemble=e, chunk_ticks=k,
                                precision=precision, interpret=interpret)
                try:
                    sim = compile_plan(spec, plan)
                except ValueError as exc:
                    check("VMEM" in str(exc), f"{name}: refused for {exc}")
                    print(f"[smoke run] {name}: refused up front by the VMEM "
                          f"fit check: {str(exc).splitlines()[0][:160]}",
                          flush=True)
                    continue
                t0 = time.perf_counter()
                hlo = sim.lower_tick_chunk().compile().as_text()
                compile_s = time.perf_counter() - t0
                if not interpret:
                    check("tpu_custom_call" in hlo,
                          f"{name}: no Pallas kernel in the compiled HLO")
                t0 = time.perf_counter()
                out = sim.tick_chunk(m, u, lane_mask=mask)
                jax.block_until_ready(out)
                wall_s = time.perf_counter() - t0
                got = np.asarray(out[1])
                check(bool(np.isfinite(got).all()), f"{name}: non-finite")
                dev = float(np.max(np.abs(got - ref_states)))
                report(name, sim.impl, compile_s, wall_s, e * k, dev,
                       TOL[f"kernels/{precision}"],
                       "runs" + ("" if interpret else "; HLO has tpu_custom_call"))


def phase_fleet(z):
    import jax.numpy as jnp
    import numpy as np

    from repro.api import SimSpec, compile_plan
    from repro.core.reservoir import make_reservoir
    from repro.launch.serve import main as serve_main
    from repro.serve.fleet.replica import make_engine

    n, slots, hold, ticks = 16, 8, 10, 23
    t0 = time.perf_counter()
    eng = make_engine(n=n, num_slots=slots, hold_steps=hold, chunk_ticks=8)
    eng.sim.warmup()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = serve_main([
        "--mode", "reservoir", "--fleet", "--transport", "local",
        "--replicas", "2", "--n", str(n), "--slots", str(slots),
        "--sessions", str(z["fleet_sessions"]), "--ticks", str(ticks),
        "--hold-steps", str(hold), "--bench", os.path.join(HERE, "BENCH_serve.json"),
    ])
    wall_s = time.perf_counter() - t0
    check(len(out) == z["fleet_sessions"], "fleet: sessions were lost")
    check(all(r is not None and r.error is None for _, r in out.values()),
          "fleet: not every session drained")
    oracle = compile_plan(
        SimSpec.from_reservoir(make_reservoir(n=n, hold_steps=hold, seed=0)),
        impl="scan",
    )
    dev = 0.0
    for u, r in out.values():
        m_ref, _ = oracle.drive(jnp.asarray(u, jnp.float32))
        dev = max(dev, float(np.max(np.abs(np.asarray(r.final_m) - m_ref))))
    report("fleet", eng.backend, compile_s, wall_s,
           z["fleet_sessions"] * ticks, dev, TOL["fleet"],
           f"replicas=2 sessions={z['fleet_sessions']} drained=all")


def phase_sharded(z, devices):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from repro.api import ExecPlan, compile_plan, make_spec
    from repro.distributed.sharding import reservoir_specs
    from repro.serve.reservoir import ReservoirEngine, StreamSession

    mesh = Mesh(np.asarray(devices[:4]).reshape(2, 2), ("data", "model"))
    spec = make_spec(n=z["shard_n"], n_in=1, hold_steps=z["hold"],
                     dtype=jnp.float32)
    w = jax.device_put(spec.w_cp, NamedSharding(mesh, reservoir_specs()["w"]))
    spec = spec._replace(w_cp=w)
    rng = np.random.default_rng(2)
    streams = rng.uniform(0.0, 0.5, size=(z["shard_sessions"], z["shard_ticks"], 1))
    streams = streams.astype(np.float32)

    def serve(plan):
        t0 = time.perf_counter()
        sim = compile_plan(spec, plan)
        sim.warmup()
        compile_s = time.perf_counter() - t0
        eng = ReservoirEngine(sim)
        t0 = time.perf_counter()
        res = eng.run([StreamSession(sid=i, u_seq=s) for i, s in enumerate(streams)])
        wall_s = time.perf_counter() - t0
        check(len(res) == len(streams), "sharded: sessions were lost")
        return sim, eng, res, compile_s, wall_s

    sim, eng, res, compile_s, wall_s = serve(ExecPlan(
        mesh=mesh, ensemble=z["shard_e"], chunk_ticks=z["chunk"]))
    check(len(spec.w_cp.sharding.device_set) == 4, "sharded: W is not on 4 devices")
    check(len(eng.store.m.sharding.device_set) == 4,
          "sharded: the slot state is not on 4 devices")
    _, _, ref, _, _ = serve(ExecPlan(
        impl="ref", ensemble=z["shard_e"], chunk_ticks=z["chunk"]))
    dev = max(
        float(np.max(np.abs(res[i].states - ref[i].states))) for i in res
    )
    report("sharded", f"{sim.impl} on mesh (data=2, model=2)", compile_s,
           wall_s, len(streams) * z["shard_ticks"], dev, TOL["sharded"],
           f"N={z['shard_n']} E={z['shard_e']} vs impl=ref on one chip; "
           "W and state span 4 devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at toy sizes (Pallas interpreted); "
                         "never prints ok")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded engine on four chips and the "
                         "one-chip reference it is compared with")
    args = ap.parse_args(argv)

    try:
        import repro  # noqa: F401
    except ImportError:
        print("chip_smoke: the repro package (src/repro next to this "
              "script) is missing", file=sys.stderr)
        return 2

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if not args.tiny and platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {platform!r}); this "
              "script needs a TPU chip (use --tiny for a CPU rehearsal)",
              file=sys.stderr)
        return 1
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    from repro.api.cache import enable_persistent_cache, persistent_cache_dir

    enable_persistent_cache()
    print(f"chip_smoke: {platform} {devices[0].device_kind} x{len(devices)}; "
          f"compile cache {persistent_cache_dir()}", flush=True)
    z = TINY if args.tiny else FULL
    if args.four_chips:
        phase_sharded(z, devices)
    else:
        phase_serve(z, interpret=args.tiny)
        phase_learn(z, interpret=args.tiny)
        phase_kernels(z, interpret=args.tiny)
        phase_fleet(z)
    if args.tiny:
        print("chip_smoke: tiny rehearsal passed on the CPU (not a chip run)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
