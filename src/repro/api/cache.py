"""Process-wide compile cache: (spec_structural_hash, plan_key) -> CompiledSim.

XLA compilation — not the RK4 GEMMs — is the slowest path in this stack:
every autoscale bucket, fleet replica spin-up, and structural tune combo
used to call `compile_plan` from scratch. `PlanCache` makes compilation a
shared, memoized resource:

  spec_structural_hash   covers only the shape/dtype/topology-determining
                         SimSpec fields (n, n_in, dtype, dt, hold_steps,
                         tableau, and the *contents* of w_cp / w_in / m0).
                         Scalar STOParams VALUES are deliberately excluded:
                         they are lane-resident runtime inputs of every
                         backend ((E, 1) columns), so two specs differing
                         only in e.g. `a_cp` share one compiled simulator —
                         exactly the grouping the tune driver assumes.
                         Ensemble-leaved params contribute their shape
                         (the executable specializes on it), not values.
  plan_key               covers every ExecPlan field that changes the
                         compiled executable: impl, ensemble bucket,
                         padding/blocking, mesh decomposition (device ids +
                         axis layout), gather dtype, precision, chunk_ticks,
                         learn family + its static knobs, interpret, and
                         measure. Non-structural conveniences (aot,
                         compilation_cache_dir) are excluded — they change
                         *when* compilation happens, never its result.

Bit-exactness is guaranteed by construction: a cache hit returns the SAME
`CompiledSim` object a fresh `compile_plan` would rebuild (pinned by
tests/test_plan_cache.py against fresh compiles). The one exception is a
hit whose requested scalar param values differ from the cached sim's —
there the cache returns a cheap rebind (`CompiledSim(spec, plan, impl)`
around the requested spec) so callers always see their own values; the
rebind shares the module-level jit'd workers, so it costs no XLA work.

Thread safety: lookups and stats take one RLock; compilation itself runs
OUTSIDE the lock with a per-key in-flight `threading.Event`, so a serving
thread hitting `_rescale` while the background pre-warm thread is already
compiling that bucket WAITS for that one compile instead of duplicating it
— and compiles of other keys proceed concurrently.

The JAX persistent compilation cache rides along (`enable_persistent_cache`
/ `ExecPlan.compilation_cache_dir`): once enabled, the XLA executables the
workers compile are spilled to disk, so cold-start survives process
restarts. `resolve_cache_dir` decides where: JAX_COMPILATION_CACHE_DIR when
set, else the requested directory, else DEFAULT_CACHE_DIR in the checkout.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import pathlib
import threading
import time
import warnings
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from repro.api.compiled import CompiledSim, compile_plan
from repro.api.plan import ExecPlan
from repro.api.spec import SimSpec

__all__ = [
    "CacheStats",
    "PlanCache",
    "PLAN_CACHE",
    "enable_persistent_cache",
    "plan_cache_key",
    "resolve_cache_dir",
    "spec_structural_hash",
]

_HASH_VERSION = b"spec-structural-v2"  # v2: physics-family fields joined the hash

#: Every SimSpec field spec_structural_hash accounts for. This is a FENCE:
#: the hash refuses to run on a spec whose field set it does not cover, so
#: adding a SimSpec field without deciding its hash treatment is a loud
#: TypeError at the first cache lookup, never a silent cross-physics cache
#: collision (pinned by tests/conformance/test_hash_guard.py).
_STRUCTURAL_FIELDS = (
    "params",
    "w_cp",
    "w_in",
    "m0",
    "dt",
    "hold_steps",
    "tableau",
    "topology",
    "readout_window",
)


def spec_structural_hash(spec: SimSpec) -> str:
    """Canonical hash of the compilation-relevant SimSpec fields.

    Two specs with the same hash compile to the same executable: same
    shapes, dtypes, topology contents, timestep, hold window, tableau, and
    physics family (topology tag + readout window — different families
    trace different workers, so they must never share a cache line).
    Scalar param values are excluded (lane-resident inputs); ensemble-leaved
    params contribute shape only.
    """
    unknown = set(spec._fields) - set(_STRUCTURAL_FIELDS)
    if unknown:
        raise TypeError(
            "spec_structural_hash does not cover SimSpec field(s) "
            f"{sorted(unknown)}; extend _STRUCTURAL_FIELDS in "
            "repro/api/cache.py (and bump _HASH_VERSION) so new physics "
            "fields key the cache instead of colliding"
        )
    h = hashlib.blake2b(digest_size=16)
    h.update(_HASH_VERSION)
    h.update(
        f"|{spec.n}|{spec.n_in}|{np.dtype(spec.dtype).name}"
        f"|{float(spec.dt)!r}|{int(spec.hold_steps)}|{spec.tableau}"
        f"|{spec.topology}|{int(spec.readout_window)}".encode()
    )
    for name in ("w_cp", "w_in", "m0"):
        a = np.asarray(getattr(spec, name))
        h.update(f"|{name}:{a.shape}:{a.dtype.name}:".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    leaf = np.asarray(spec.params.gamma)
    h.update(f"|params:{leaf.shape}".encode())
    return h.hexdigest()


def _mesh_key(plan: ExecPlan):
    """Hashable description of the mesh decomposition (None when unsharded)."""
    if plan.mesh is None:
        return None
    mesh = plan.mesh
    shape = mesh.shape  # axis name -> size mapping
    return (
        tuple((str(k), int(v)) for k, v in shape.items()),
        tuple(str(d) for d in np.asarray(mesh.devices).flat),
        tuple(plan.ensemble_axes),
        plan.model_axis,
    )


def plan_cache_key(plan: ExecPlan) -> Tuple:
    """Canonical key over the ExecPlan fields that shape the executable.

    impl="auto" plans additionally carry the dispatch-table generation, so
    a cached auto-resolution is invalidated the moment a new measurement
    registers a different winner for its (N, E) cell.
    """
    from repro.kernels import dispatch_table, ops

    gd = plan.effective_gather_dtype
    if plan.impl == "auto" and not plan.sharded:
        # settle the lazy persisted-table load BEFORE reading the
        # generation, so the key only moves on genuinely new measurements
        dispatch_table.ensure_loaded()
        gen = ops.dispatch_generation()
    else:
        gen = None
    return (
        plan.impl,
        gen,
        int(plan.ensemble),
        plan.block_n,
        plan.block_e,
        plan.n_inner,
        _mesh_key(plan),
        None if gd is None else np.dtype(gd).name,
        ops.normalize_precision(plan.precision),
        int(plan.chunk_ticks),
        plan.learn,
        float(plan.learn_lam),
        float(plan.learn_reg),
        float(plan.learn_mu),
        bool(plan.interpret),
        bool(plan.measure),
    )


def _params_equal(a, b) -> bool:
    """Leaf-wise equality of two STOParams pytrees (shape + values)."""
    if a is b:
        return True
    for la, lb in zip(a, b):
        if la is lb:
            continue
        xa, xb = np.asarray(la), np.asarray(lb)
        if xa.shape != xb.shape or not np.array_equal(xa, xb):
            return False
    return True


# ---------------------------------------------------------------------------
# JAX persistent compilation cache (process restart survival)
# ---------------------------------------------------------------------------

_PERSISTENT_LOCK = threading.Lock()
_PERSISTENT_DIR: Optional[str] = None
_WARNED_OVERRIDE = False

#: Where the cache lives when JAX_COMPILATION_CACHE_DIR is unset: a fixed
#: path inside the checkout (gitignored). JAX keys entries by path among
#: other things, so a directory that moves between runs never hits.
DEFAULT_CACHE_DIR = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")


def resolve_cache_dir(requested: Optional[str] = None) -> str:
    """The one place that decides where compiled executables persist.

    JAX_COMPILATION_CACHE_DIR, when set, wins over everything: JAX reads it
    itself and no code here points the cache elsewhere (a `requested`
    directory that differs is ignored, with one warning per process).
    Otherwise `requested` (ExecPlan.compilation_cache_dir, the launcher's
    --compilation-cache-dir), else DEFAULT_CACHE_DIR.
    """
    global _WARNED_OVERRIDE
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        if (
            requested
            and os.path.abspath(requested) != os.path.abspath(env)
            and not _WARNED_OVERRIDE
        ):
            _WARNED_OVERRIDE = True
            warnings.warn(
                f"JAX_COMPILATION_CACHE_DIR={env!r} is set; ignoring the "
                f"requested compilation cache directory {requested!r}",
                RuntimeWarning,
                stacklevel=3,
            )
        return env
    return str(requested) if requested else DEFAULT_CACHE_DIR


def enable_persistent_cache(directory: Optional[str] = None) -> bool:
    """Turn on JAX's persistent compilation cache (idempotent).

    The directory comes from `resolve_cache_dir(directory)`. First resolved
    directory wins for the process — JAX reads the config at compile time
    and re-pointing mid-flight would split the cache; a later call that
    resolves elsewhere warns and is ignored. Returns True when the cache
    is (now) active in the resolved directory.
    """
    global _PERSISTENT_DIR
    from jax.experimental.compilation_cache import compilation_cache

    directory = resolve_cache_dir(directory)
    with _PERSISTENT_LOCK:
        if _PERSISTENT_DIR is not None:
            if _PERSISTENT_DIR != directory:
                warnings.warn(
                    "JAX persistent compilation cache already pinned to "
                    f"{_PERSISTENT_DIR!r}; ignoring {directory!r} (first "
                    "directory wins for the process)",
                    RuntimeWarning,
                    stacklevel=2,
                )
                return False
            return True
        os.makedirs(directory, exist_ok=True)
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", directory)
        # cache every executable, however small/fast the compile — this
        # stack's hot paths are many medium-sized modules, not one giant one
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        # JAX initializes its disk cache lazily at the FIRST compile and
        # never re-reads the config: any compile before this call (spec
        # construction, dispatch probing) would freeze it disabled. Reset
        # so the next compile re-checks jax_compilation_cache_dir.
        compilation_cache.reset_cache()
        _PERSISTENT_DIR = directory
        return True


def persistent_cache_dir() -> Optional[str]:
    """The directory the persistent cache is pinned to (None = disabled)."""
    return _PERSISTENT_DIR


# ---------------------------------------------------------------------------
# PlanCache
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CacheStats:
    """Counters for the compile cache (see PlanCache.stats).

    hits/misses count `get_or_compile` lookups; compiles / compile_seconds
    cover the `compile_plan` calls misses triggered (compile_seconds is
    trace+bind time — the XLA work itself lands at first dispatch, which
    `warm` forces and times into warmups / warmup_seconds). rebinds counts
    hits that re-wrapped the cached executable around different scalar
    param values. measure_hits/measure_misses cover the memoized
    `measure_impl_latency` results (the `--save-dispatch-table` path).
    """

    hits: int = 0
    misses: int = 0
    compiles: int = 0
    compile_seconds: float = 0.0
    evictions: int = 0
    warmups: int = 0
    warmup_seconds: float = 0.0
    rebinds: int = 0
    measure_hits: int = 0
    measure_misses: int = 0

    def snapshot(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


class PlanCache:
    """LRU cache of CompiledSims keyed (spec_structural_hash, plan_key)."""

    def __init__(self, capacity: int = 64):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1; got {capacity}")
        self.capacity = int(capacity)
        self._lock = threading.RLock()
        self._entries: "OrderedDict[Tuple, CompiledSim]" = OrderedDict()
        self._warmed: set = set()
        self._inflight: Dict[Tuple, threading.Event] = {}
        self._measurements: Dict[Tuple, dict] = {}
        self.stats = CacheStats()

    # -- keys --------------------------------------------------------------

    def key(self, spec: SimSpec, plan: ExecPlan) -> Tuple:
        return (spec_structural_hash(spec), plan_cache_key(plan))

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def contains(self, spec: SimSpec, plan: Optional[ExecPlan] = None, **overrides) -> bool:
        """True when get_or_compile would hit (no stats mutation)."""
        plan = _resolve_plan(plan, overrides)
        key = self.key(spec, plan)
        with self._lock:
            return key in self._entries

    def is_warm(self, spec: SimSpec, plan: Optional[ExecPlan] = None, *, n_out: int = 1, **overrides) -> bool:
        """True when the (key, n_out) hot path has already been executed once."""
        plan = _resolve_plan(plan, overrides)
        key = self.key(spec, plan)
        with self._lock:
            return (key, int(n_out)) in self._warmed

    # -- the cache proper --------------------------------------------------

    def get_or_compile(
        self, spec: SimSpec, plan: Optional[ExecPlan] = None, **overrides
    ) -> CompiledSim:
        """The cached analogue of `compile_plan(spec, plan, **overrides)`.

        Hit: the cached CompiledSim (the same object), rebound to the
        requested spec when its scalar param values differ. Miss: compiles
        outside the lock (one in-flight compile per key — concurrent
        requesters wait on it) and inserts with LRU eviction.
        """
        plan = _resolve_plan(plan, overrides)
        if plan.compilation_cache_dir:
            enable_persistent_cache(plan.compilation_cache_dir)
        key = self.key(spec, plan)
        while True:
            with self._lock:
                sim = self._entries.get(key)
                if sim is not None:
                    self._entries.move_to_end(key)
                    self.stats.hits += 1
                    return self._rebind(sim, spec, plan)
                event = self._inflight.get(key)
                if event is None:
                    self._inflight[key] = threading.Event()
                    self.stats.misses += 1
                    break
            # another thread is compiling this key — wait, then re-check
            event.wait()
        try:
            t0 = time.perf_counter()
            sim = compile_plan(spec, plan)
            elapsed = time.perf_counter() - t0
        except BaseException:
            with self._lock:
                self._inflight.pop(key).set()  # waiters retry and re-raise
            raise
        with self._lock:
            self._entries[key] = sim
            self._entries.move_to_end(key)
            self.stats.compiles += 1
            self.stats.compile_seconds += elapsed
            while len(self._entries) > self.capacity:
                old_key, _ = self._entries.popitem(last=False)
                self._warmed = {w for w in self._warmed if w[0] != old_key}
                self.stats.evictions += 1
            self._inflight.pop(key).set()
        return sim

    def _rebind(self, sim: CompiledSim, spec: SimSpec, plan: ExecPlan) -> CompiledSim:
        """Hits always reflect the CALLER's param values: same structural
        hash + different scalar values -> cheap rewrap of the cached
        executable (module-level jit workers stay warm; zero XLA work)."""
        if _params_equal(sim.spec.params, spec.params):
            return sim
        with self._lock:
            self.stats.rebinds += 1
        return CompiledSim(spec, sim.plan, sim.impl)

    def warm(self, sim: CompiledSim, *, n_out: int = 1, aot: bool = False) -> float:
        """Force XLA compilation of `sim`'s chunked hot path, once per
        (key, n_out). Returns seconds spent (0.0 when already warm).

        aot=True lowers + compiles without executing (`lower().compile()`)
        — it populates the persistent disk cache and measures pure compile
        seconds, but the in-process jit fast path still pays one dispatch;
        the default executes one masked zero chunk, which warms the exact
        executable the serving loop dispatches.
        """
        key = (self.key(sim.spec, sim.plan), int(n_out))
        with self._lock:
            if key in self._warmed:
                return 0.0
        t0 = time.perf_counter()
        if aot:
            try:
                sim.aot_compile(n_out=n_out)
            except NotImplementedError:
                sim.warmup(n_out=n_out)
        else:
            sim.warmup(n_out=n_out)
        elapsed = time.perf_counter() - t0
        with self._lock:
            if key not in self._warmed:
                self._warmed.add(key)
                self.stats.warmups += 1
                self.stats.warmup_seconds += elapsed
        return elapsed

    def ensure_warm(
        self,
        spec: SimSpec,
        plan: Optional[ExecPlan] = None,
        *,
        n_out: int = 1,
        aot: bool = False,
        **overrides,
    ) -> CompiledSim:
        """get_or_compile + warm in one call (the pre-warm entry point)."""
        sim = self.get_or_compile(spec, plan, **overrides)
        self.warm(sim, n_out=n_out, aot=aot)
        return sim

    # -- measurement memo (compile_plan(measure=True)) ---------------------

    def measure(
        self,
        n: int,
        e: int,
        *,
        dt: float,
        n_steps: int = 8,
        candidates: Optional[Tuple[str, ...]] = None,
        dtype=None,
        reps: int = 3,
        precision: Optional[str] = None,
        chunk_ticks: int = 4,
    ) -> dict:
        """Memoized `ops.measure_impl_latency`: identical keys in one
        process are timed once — repeated `compile_plan(measure=True)` /
        `--save-dispatch-table` runs stop paying duplicate candidate
        timing. The first call still registers its winner in the dispatch
        table (register=True), so resolution is unchanged."""
        import jax.numpy as jnp

        from repro.kernels import ops

        dtype = jnp.float32 if dtype is None else dtype
        key = (
            jax.default_backend(),
            int(n),
            int(e),
            np.dtype(dtype).name,
            ops.normalize_precision(precision),
            int(chunk_ticks),
            int(n_steps),
            int(reps),
            None if candidates is None else tuple(candidates),
        )
        with self._lock:
            memo = self._measurements.get(key)
            if memo is not None:
                self.stats.measure_hits += 1
                return memo
        timings = ops.measure_impl_latency(
            n, e, dt=dt, n_steps=n_steps, candidates=candidates,
            dtype=dtype, reps=reps, precision=precision,
            chunk_ticks=chunk_ticks,
        )
        with self._lock:
            self.stats.measure_misses += 1
            self._measurements[key] = timings
        return timings

    # -- maintenance -------------------------------------------------------

    def clear(self) -> None:
        """Drop every entry, warm mark, and measurement memo (stats kept)."""
        with self._lock:
            self._entries.clear()
            self._warmed.clear()
            self._measurements.clear()


def _resolve_plan(plan: Optional[ExecPlan], overrides: dict) -> ExecPlan:
    if plan is None:
        return ExecPlan(**overrides)
    if overrides:
        return dataclasses.replace(plan, **overrides)
    return plan


#: The process-wide cache every compile hot path shares: ReservoirEngine
#: autoscale buckets, fleet replica spin-up / migration warm-start, the
#: capacity planner's recalibration probe, and tune_spec's per-structural-
#: combo engines all draw from here.
PLAN_CACHE = PlanCache()
