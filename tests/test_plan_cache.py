"""Process-wide plan cache: key anatomy, hit/rebind/evict semantics,
background pre-warm thread safety, and the compile-once contracts the
serving and tune layers rely on.

Key invariants pinned here (docs/ARCHITECTURE.md "Compile cache"):

- `spec_structural_hash` covers only what changes the compiled program
  (shape/dtype/topology/dt/hold_steps/tableau) — scalar param VALUES ride
  in lanes at call time, so specs differing only in values share a hash.
- `plan_cache_key` separates every executable-changing ExecPlan axis
  (impl/ensemble/precision/learn family/chunk_ticks/mesh decomposition),
  while `aot` and `compilation_cache_dir` — pure policy, same executable —
  are excluded.
- A cache hit is the SAME CompiledSim object (bit-exactness by
  construction); a hit under different param values is a near-free rebind
  of the requested spec onto the cached executable.
- One compile per key even under concurrency: a miss in flight parks
  later requesters on an event instead of duplicating the XLA work.

The module-level PLAN_CACHE is shared by the whole pytest process, so
tests against it assert stat DELTAS and use unique spec seeds (9xx_xxx
range) — never absolute counts.
"""

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (
    PLAN_CACHE,
    ExecPlan,
    PlanCache,
    compile_plan,
    make_spec,
    plan_cache_key,
    spec_structural_hash,
)
from repro.serve.reservoir import ReservoirEngine, StreamSession


def _scaled_params(spec, factor):
    """Same structure, different scalar values (lane-resident at runtime)."""
    return jax.tree_util.tree_map(lambda x: x * factor, spec.params)


def _one_device_mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


def _sessions(rng, count, ticks=6, base_sid=0):
    return [
        StreamSession(
            sid=base_sid + i,
            u_seq=rng.uniform(0, 0.5, (ticks, 1)).astype(np.float32),
            collect_states=False,
        )
        for i in range(count)
    ]


# ---------------------------------------------------------------------------
# key anatomy
# ---------------------------------------------------------------------------


def test_structural_hash_ignores_param_values():
    spec = make_spec(n=12, n_in=1, hold_steps=4, seed=900_001, dtype=jnp.float32)
    revalued = spec._replace(params=_scaled_params(spec, 1.5))
    assert spec_structural_hash(spec) == spec_structural_hash(revalued)


def test_structural_hash_sees_structure():
    base = make_spec(n=12, n_in=1, hold_steps=4, seed=900_002, dtype=jnp.float32)
    variants = [
        base._replace(dt=base.dt * 2.0),
        base._replace(hold_steps=5),
        base._replace(tableau="heun"),
        make_spec(n=12, n_in=1, hold_steps=4, seed=900_003, dtype=jnp.float32),
        make_spec(n=14, n_in=1, hold_steps=4, seed=900_002, dtype=jnp.float32),
    ]
    h0 = spec_structural_hash(base)
    hashes = [spec_structural_hash(v) for v in variants]
    assert all(h != h0 for h in hashes), hashes
    assert len(set(hashes)) == len(hashes)


def test_plan_key_separates_executable_axes():
    plans = [
        ExecPlan(impl="scan"),
        ExecPlan(impl="chunk"),
        ExecPlan(impl="chunk", ensemble=8),
        ExecPlan(impl="chunk", ensemble=8, chunk_ticks=4),
        ExecPlan(impl="chunk", ensemble=8, precision="mixed"),
        ExecPlan(impl="chunk", ensemble=8, learn="rls"),
        ExecPlan(impl="chunk", ensemble=8, learn="rls", learn_lam=0.99),
        ExecPlan(impl="chunk", ensemble=8, learn="lms"),
        ExecPlan(impl="chunk", ensemble=8, interpret=True),
        ExecPlan(impl="scan", ensemble=8, mesh=_one_device_mesh()),
    ]
    keys = [plan_cache_key(p) for p in plans]
    assert len(set(keys)) == len(keys), "plan-key collision across variants"


def test_plan_key_excludes_pure_policy_fields():
    base = ExecPlan(impl="chunk", ensemble=4, chunk_ticks=4)
    assert plan_cache_key(base) == plan_cache_key(
        dataclasses.replace(base, aot=True)
    )
    # compilation_cache_dir changes WHERE executables persist, never what
    # they compute — key-equal by design (it is honored at compile time)
    assert plan_cache_key(base) == plan_cache_key(
        dataclasses.replace(base, compilation_cache_dir="/tmp/nonexistent-pc")
    )


def test_auto_impl_key_tracks_dispatch_generation(monkeypatch):
    from repro.kernels import ops

    k0 = plan_cache_key(ExecPlan(impl="auto", ensemble=2))
    ops.register_impl_choice(997, 3, "chunk")
    try:
        k1 = plan_cache_key(ExecPlan(impl="auto", ensemble=2))
        assert k0 != k1, (
            "a new dispatch measurement must invalidate cached auto plans"
        )
    finally:
        # the table entry is in-process only; the bumped generation makes
        # it invisible to every earlier cached key
        ops.register_impl_choice(997, 3, "ref")


# ---------------------------------------------------------------------------
# hit / rebind / evict semantics (local caches: no global interference)
# ---------------------------------------------------------------------------


def test_hit_returns_same_object():
    cache = PlanCache()
    spec = make_spec(n=12, n_in=1, hold_steps=4, seed=900_010, dtype=jnp.float32)
    plan = ExecPlan(impl="scan", ensemble=2, chunk_ticks=2)
    a = cache.get_or_compile(spec, plan)
    b = cache.get_or_compile(spec, plan)
    assert a is b
    s = cache.stats
    assert (s.misses, s.hits, s.compiles, s.rebinds) == (1, 1, 1, 0)
    assert len(cache) == 1


def test_rebind_on_param_value_change_matches_fresh_compile():
    cache = PlanCache()
    spec = make_spec(n=12, n_in=1, hold_steps=4, seed=900_011, dtype=jnp.float32)
    plan = ExecPlan(impl="scan")
    cache.get_or_compile(spec, plan)
    revalued = spec._replace(params=_scaled_params(spec, 1.3))
    rebound = cache.get_or_compile(revalued, plan)
    assert cache.stats.rebinds == 1 and cache.stats.compiles == 1
    assert rebound.spec is revalued

    u = np.random.default_rng(0).uniform(0, 0.5, (5, 1)).astype(np.float32)
    _, states_cached = rebound.drive(u)
    _, states_fresh = compile_plan(revalued, plan).drive(u)
    np.testing.assert_array_equal(
        np.asarray(states_cached), np.asarray(states_fresh)
    ), "rebound executable is not bit-identical to a fresh compile"


def test_eviction_roundtrip_bit_exact():
    cache = PlanCache(capacity=2)
    plan = ExecPlan(impl="scan")
    specs = [
        make_spec(n=12, n_in=1, hold_steps=4, seed=900_020 + i, dtype=jnp.float32)
        for i in range(3)
    ]
    for s in specs:
        cache.get_or_compile(s, plan)
    assert cache.stats.evictions == 1 and len(cache) == 2
    assert not cache.contains(specs[0], plan)  # LRU victim

    u = np.random.default_rng(1).uniform(0, 0.5, (5, 1)).astype(np.float32)
    recompiled = cache.get_or_compile(specs[0], plan)
    assert cache.stats.compiles == 4  # paid the compile again
    _, states_re = recompiled.drive(u)
    _, states_fresh = compile_plan(specs[0], plan).drive(u)
    np.testing.assert_array_equal(np.asarray(states_re), np.asarray(states_fresh))


def test_single_compile_under_concurrent_requests():
    cache = PlanCache()
    spec = make_spec(n=12, n_in=1, hold_steps=4, seed=900_030, dtype=jnp.float32)
    plan = ExecPlan(impl="scan", ensemble=2, chunk_ticks=2)
    sims, errs = [], []

    def work():
        try:
            sims.append(cache.get_or_compile(spec, plan))
        except BaseException as e:  # noqa: BLE001 — surface in main thread
            errs.append(e)

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert len(sims) == 4 and all(s is sims[0] for s in sims)
    assert cache.stats.compiles == 1, "in-flight event failed to dedupe"


def test_measure_memo():
    cache = PlanCache()
    kw = dict(dt=1.0e-11, n_steps=2, reps=1, candidates=("ref",))
    first = cache.measure(8, 2, **kw)
    second = cache.measure(8, 2, **kw)
    assert second is first
    assert cache.stats.measure_misses == 1 and cache.stats.measure_hits == 1
    # a different shape is a fresh measurement
    cache.measure(8, 4, **kw)
    assert cache.stats.measure_misses == 2


# ---------------------------------------------------------------------------
# serving integration (global PLAN_CACHE: deltas only, unique seeds)
# ---------------------------------------------------------------------------


def test_engine_template_route_shares_compiled_sim():
    spec = make_spec(n=12, n_in=1, hold_steps=4, seed=900_040, dtype=jnp.float32)
    kw = dict(num_slots=2, chunk_ticks=2)
    eng_a = ReservoirEngine(spec, **kw)
    hits0 = PLAN_CACHE.stats.hits
    eng_b = ReservoirEngine(spec, **kw)
    assert eng_b.sim is eng_a.sim
    assert PLAN_CACHE.stats.hits == hits0 + 1


def test_prewarmed_rescale_compiles_nothing():
    spec = make_spec(n=12, n_in=1, hold_steps=4, seed=900_041, dtype=jnp.float32)
    eng = ReservoirEngine(
        PLAN_CACHE.get_or_compile(spec, ExecPlan(ensemble=4, chunk_ticks=2)),
        autoscale=True,
        min_slots=2,
        max_slots=8,
    )
    eng.prewarm(block=True)
    compiles0 = PLAN_CACHE.stats.compiles
    eng._rescale(8)
    eng._rescale(2)
    assert PLAN_CACHE.stats.compiles == compiles0
    st = eng.stats()
    assert st.cold_rescales == 0 and st.warm_rescales == 2
    assert st.rescale_stall_s == 0.0

    # and the engine still serves correctly at the rescaled width
    rng = np.random.default_rng(3)
    results = eng.run(_sessions(rng, 5))
    assert len(results) == 5


def test_concurrent_rescale_during_prewarm():
    """A _rescale racing the background pre-warm must wait on the in-flight
    compile (one compile per key), never crash, and leave a serving-correct
    engine behind."""
    spec = make_spec(n=12, n_in=1, hold_steps=4, seed=900_042, dtype=jnp.float32)
    eng = ReservoirEngine(
        PLAN_CACHE.get_or_compile(spec, ExecPlan(ensemble=4, chunk_ticks=2)),
        autoscale=True,
        min_slots=2,
        max_slots=8,
        prewarm=False,
    )
    misses0 = PLAN_CACHE.stats.misses
    compiles0 = PLAN_CACHE.stats.compiles
    eng.prewarm_buckets(block=False)  # daemon thread compiles 2 and 8
    eng._rescale(8)  # races the thread on the ensemble=8 key
    if eng._prewarm_thread is not None:
        eng._prewarm_thread.join(timeout=60)
    d_miss = PLAN_CACHE.stats.misses - misses0
    d_comp = PLAN_CACHE.stats.compiles - compiles0
    assert d_comp == d_miss, (
        f"{d_comp} compiles for {d_miss} misses — the in-flight event "
        f"duplicated XLA work under the race"
    )
    assert eng.num_slots == 8
    rng = np.random.default_rng(4)
    results = eng.run(_sessions(rng, 6))
    assert len(results) == 6


def test_compile_plan_measure_memoized():
    spec = make_spec(n=13, n_in=1, hold_steps=4, seed=900_043, dtype=jnp.float32)
    plan = ExecPlan(ensemble=2, chunk_ticks=2, measure=True)
    m0 = PLAN_CACHE.stats.measure_misses
    h0 = PLAN_CACHE.stats.measure_hits
    compile_plan(spec, plan)
    assert PLAN_CACHE.stats.measure_misses == m0 + 1
    compile_plan(spec, plan)
    assert PLAN_CACHE.stats.measure_hits == h0 + 1, (
        "repeat measure=True compile re-ran the latency probe"
    )


# ---------------------------------------------------------------------------
# sharded autoscale (lifted restriction)
# ---------------------------------------------------------------------------


def test_sharded_autoscale_allowed_when_widths_divide():
    spec = make_spec(n=12, n_in=1, hold_steps=4, seed=900_050, dtype=jnp.float32)
    sim = PLAN_CACHE.get_or_compile(
        spec,
        ExecPlan(impl="scan", ensemble=4, chunk_ticks=2,
                 mesh=_one_device_mesh()),
    )
    eng = ReservoirEngine(
        sim, autoscale=True, min_slots=2, max_slots=8, prewarm=False
    )
    assert eng.autoscale is not None
    rng = np.random.default_rng(5)
    results = eng.run(_sessions(rng, 3))
    assert len(results) == 3


def test_sharded_autoscale_rejects_indivisible_widths(monkeypatch):
    import repro.serve.reservoir as reservoir_mod

    spec = make_spec(n=12, n_in=1, hold_steps=4, seed=900_050, dtype=jnp.float32)
    sim = PLAN_CACHE.get_or_compile(
        spec,
        ExecPlan(impl="scan", ensemble=4, chunk_ticks=2,
                 mesh=_one_device_mesh()),
    )
    # a single-host CPU run cannot build a >1-device mesh, so emulate the
    # multi-device decomposition at the validation seam
    monkeypatch.setattr(reservoir_mod, "_ensemble_axis_size", lambda plan: 3)
    with pytest.raises(ValueError, match="incompatible widths"):
        ReservoirEngine(
            sim, autoscale=True, min_slots=2, max_slots=8, prewarm=False
        )


def test_bucket_ladder_and_axis_size_helpers():
    from repro.serve.reservoir import _bucket_ladder, _ensemble_axis_size

    assert _bucket_ladder(2, 8) == [2, 4, 8]
    assert _bucket_ladder(2, 12) == [2, 4, 8, 12]  # non-power-of-two clamp
    assert _bucket_ladder(4, 4) == [4]
    assert _ensemble_axis_size(ExecPlan(impl="chunk")) == 1
    sharded = ExecPlan(impl="scan", mesh=_one_device_mesh())
    assert _ensemble_axis_size(sharded) == 1  # ("data",) axis on 1 device


# ---------------------------------------------------------------------------
# tune integration: one compile per structural combo, across calls
# ---------------------------------------------------------------------------


def test_tune_compiles_each_structural_combo_once():
    from repro.tune import Choice, Float, SearchSpace, narma_task, tune_spec

    task = narma_task(32, order=10, seed=9, learn_washout=8)
    space = SearchSpace({
        "drive_current": Float(0.5e-3, 4.5e-3),
        "hold_steps": Choice((3, 5)),
    })
    plan = ExecPlan(impl="scan", ensemble=4, chunk_ticks=2, learn="rls")
    spec = make_spec(n=12, n_in=1, hold_steps=4, seed=900_060, dtype=jnp.float32)

    def run_once():
        return tune_spec(
            spec, task, space, budget=8, plan=plan, strategy="cmaes", seed=2
        )

    c0 = PLAN_CACHE.stats.compiles
    first = run_once()
    combos = {t.assignment["hold_steps"] for t in first.trials}
    assert PLAN_CACHE.stats.compiles - c0 == len(combos), (
        "a 2-generation CMA-ES run must compile each structural combo "
        "exactly once"
    )
    second = run_once()
    assert PLAN_CACHE.stats.compiles - c0 == len(combos), (
        "revisiting the same structural combos recompiled them"
    )
    assert [t.fitness for t in first.trials] == [
        t.fitness for t in second.trials
    ], "cached engines changed the search's numerics"


class TestCompileCacheDirectory:
    """api/cache.resolve_cache_dir: the environment's directory wins, else
    the requested one, else a fixed path inside the checkout."""

    def test_environment_wins_and_warns_once(self, monkeypatch):
        from repro.api import cache as cache_mod

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/srv/jax-cache")
        monkeypatch.setattr(cache_mod, "_WARNED_OVERRIDE", False)
        assert cache_mod.resolve_cache_dir() == "/srv/jax-cache"
        assert cache_mod.resolve_cache_dir("/srv/jax-cache") == "/srv/jax-cache"
        with pytest.warns(RuntimeWarning, match="ignoring"):
            assert cache_mod.resolve_cache_dir("/elsewhere") == "/srv/jax-cache"
        import warnings

        with warnings.catch_warnings():
            warnings.simplefilter("error")
            assert cache_mod.resolve_cache_dir("/elsewhere") == "/srv/jax-cache"

    def test_default_is_fixed_inside_the_checkout(self, monkeypatch):
        import os

        from repro.api import cache as cache_mod

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert cache_mod.resolve_cache_dir() == os.path.join(root, ".jax_cache")
        assert cache_mod.resolve_cache_dir("/data/c") == "/data/c"
