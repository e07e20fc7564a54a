"""Multi-tenant streaming reservoir inference engine.

The reservoir analogue of continuous batching (serve/engine.py): concurrent
client streams map onto slots of the ensemble axis E, so ONE batched
integrate — `rk4_fused` / `field_tiled` on TPU, a jit'd `lax.scan` on CPU —
advances every active session per input tick. Admitting a session splices
its magnetization state m (N, 3) and per-tenant STOParams lane into the
batched (3, N, E) planes (serve/state_store.py); finished or idle sessions
free their slot without stalling the batch (serve/scheduler.py). Each
session carries its own trained Readout and input stream (NARMA, parity,
sine-approx, ... — anything the reservoir was trained for); readout
application is itself slot-batched (one einsum over E).

Execution rides on the unified API (repro/api): the engine holds a
CompiledSim and its hot path is `CompiledSim.tick_chunk` — a lax.scan over
`ExecPlan.chunk_ticks` input ticks whose states stay in a device-side
buffer until ONE bulk transfer per chunk. `run()` is a double-buffered
pipeline: while the device executes the current chunk (JAX async
dispatch), the host harvests the previous chunk and assembles the next
K-tick u block, applying admissions/retirements to the staging slot store
at chunk boundaries. `step()` keeps the synchronous per-tick path (one
`CompiledSim.tick` + per-slot harvest per call) for externally-clocked
callers and as the pipelined path's baseline.

Under load the engine AUTOSCALES the slot count: a bucketed plan cache
(one `compile_plan` per power-of-two ensemble width between min_slots and
max_slots) lets a chunk boundary grow or shrink the batch by migrating the
occupied SlotStore columns between cached CompiledSims. The decision rule
is a pluggable `serve.scheduler.AutoscalePolicy` fed by the scheduler's
occupancy / queue-depth / queue-wait stats (default: `QueueDepthPolicy`,
grow-on-demand + hysteretic shrink).

With `ExecPlan(learn="rls")` the engine also LEARNS: a session that
submits `targets` next to its inputs gets its readout trained on device
while it streams — per-slot RLS inverse-Gram/weight lanes live in the
SlotStore next to the magnetization, the chunked update rides the same
`tick_chunk` dispatch as the integration (kernels/rls.py), and the
finished session's `SessionResult` carries the trained Readout, the
per-tick a-priori predictions, and the online NMSE. Learning state
migrates through admit/retire and autoscale resizes with the other slot
columns; `core.reservoir.fit_rls(states, targets, block=chunk_ticks)` is
the offline oracle the streamed result bit-matches on the scan backend
(tests/test_rls_learning.py).

Construct from a Reservoir/SimSpec (the engine compiles an ExecPlan for
you; backend="auto" consults the measured-latency dispatch table, persisted
per-platform JSON included, then the VMEM heuristic) or hand the engine an
already-compiled sim — including a sharded one (`ExecPlan(mesh=...)`),
which serves the slot batch across the device mesh with E on the data axes
and N on the model axis. The extra "scan" backend integrates in the core
(E, N, 3) layout with exactly `reservoir.drive`'s math, so per-session
streamed states are numerically indistinguishable from running the stream
alone — chunked or per-tick (tests/test_serve_chunked.py pins the K>1 /
K=1 bit-equality); every other backend agrees with solo runs to the kernel
test suite's tolerance (tests/test_serve_reservoir.py pins all of them).

Tenancy is SPEC-LEVEL, not just params-level: a StreamSession may carry
its own SimSpec. Sessions whose spec structurally matches the engine's
template (same `repro.api.spec_structural_hash` — shapes, dtype, topology
contents, physics family; scalar param values excluded) serve in a primary
lane with the spec's params riding the lane. Sessions whose spec hashes
differently — another physics family (`topology="time_multiplexed"` /
"array_transient"), another N, dt, hold window, coupling matrix — land on
an internal per-hash sub-engine compiled through the shared PLAN_CACHE, so
a coupled-array tenant and a time-multiplexed tenant stream through ONE
engine concurrently, each bit-identical to a solo run of its own spec
(tests/conformance/test_mixed_tenants.py). Sub-engine sessions ride the
same results map, push/append, checkpoint/restore, and stats surface.

This is the serving front for time-multiplexed STO reservoir hardware
(Riou et al., arXiv:1904.11236; Kanao et al., arXiv:1905.07937): each
tenant's device parameters ride in a params lane, the shared simulator
advances all of them in lockstep.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import (
    FAMILY_IMPLS,
    PLAN_CACHE,
    CompiledSim,
    ExecPlan,
    SimSpec,
    compile_plan,
    spec_structural_hash,
)
from repro.api.cache import _params_equal
from repro.core.constants import EXACT_MATMUL, STOParams
from repro.core.reservoir import Readout, Reservoir, coerce_input_series
from repro.serve.scheduler import AutoscalePolicy, QueueDepthPolicy, SlotScheduler
from repro.serve.state_store import SlotStore

BACKENDS = ("auto", "scan", "ref", "fused", "tiled", "chunk")

# Host spans on the profiler's clock, one per phase of a chunk boundary
# (`step_chunk`): a trace then puts each device idle gap down to the phase
# the host was in. With no profiler running, each costs about a microsecond.
_span = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class StreamSession:
    """One tenant's streaming request.

    u_seq follows drive()'s explicit (T, N_in) contract ((T,) for
    n_in == 1). params overrides the engine reservoir's physical parameters
    for this tenant's lane; readout is the tenant's trained linear readout
    (None = state-collection only, e.g. to fit a readout afterwards); m0
    resumes from a previous session's final state.

    On a learning engine (`ExecPlan.learn="rls"`), `targets` turns the
    session into an ONLINE-LEARNING stream: one (T, n_out) target row per
    input row ((T,) for n_out == 1), and the engine trains this tenant's
    readout on device while it streams — every tick's RLS update rides the
    same `tick_chunk` dispatch as the integration. `learn_washout` skips
    the update for the first ticks (reservoir warm-up; predictions are
    still recorded). If `readout` is also set, it WARM-STARTS the learned
    weights (and still drives the static `outputs` column). The trained
    readout, per-tick a-priori predictions, and online NMSE come back on
    the SessionResult.

    Per-session output width: a session's n_out is inferred from its
    readout / targets column count and may be anything in
    [1, engine n_out] — the engine pads the narrow session onto its
    store-width readout lanes with zero columns (RLS weight columns evolve
    independently given the shared gain, so padding is exact) and slices
    results back to the session's own width.

    `open=True` marks a PUSH stream: the session stays resident after its
    current input is exhausted (its lane idles, state frozen) until
    `engine.append_ticks(sid, ...)` supplies more rows or
    `engine.close_session(sid)` lets it finish. The fleet front-end's
    `push_ticks` rides this.

    `learn_w0` / `learn_P0` resume an RLS recursion mid-stream (weights +
    inverse-Gram) — the checkpoint/migration path; fresh sessions leave
    them None (`readout` alone warm-starts weights with a fresh P).
    """

    sid: int
    u_seq: np.ndarray
    params: Optional[STOParams] = None
    readout: Optional[Readout] = None
    m0: Optional[jnp.ndarray] = None
    collect_states: bool = True
    targets: Optional[np.ndarray] = None  # (T, n_out) online-learning targets
    learn_washout: int = 0  # ticks before the first RLS update
    open: bool = False  # True: idle (don't finish) when input runs dry
    learn_w0: Optional[np.ndarray] = None  # (N+1, n_out) RLS weight resume
    learn_P0: Optional[np.ndarray] = None  # (N+1, N+1) inverse-Gram resume
    # Spec-level multi-tenancy: a session that carries its OWN SimSpec is
    # routed by structural hash — same hash as the engine's template means
    # same compiled physics (the spec's scalar params become the session's
    # lane values, unless `params` was set explicitly); a different hash
    # (other topology family, other N/dt/hold_steps/w_cp/...) lands on an
    # internal sub-engine compiled for that spec through the shared
    # PLAN_CACHE. None = classic behavior: the engine's template spec.
    spec: Optional[SimSpec] = None

    # engine-internal bookkeeping (set on admit)
    _slot: int = dataclasses.field(default=-1, repr=False)
    _t: int = dataclasses.field(default=0, repr=False)
    _states: list = dataclasses.field(default_factory=list, repr=False)
    _outs: list = dataclasses.field(default_factory=list, repr=False)
    _preds: list = dataclasses.field(default_factory=list, repr=False)
    _admitted_tick: int = dataclasses.field(default=-1, repr=False)
    _finished_tick: int = dataclasses.field(default=-1, repr=False)
    _n_out: int = dataclasses.field(default=1, repr=False)  # session width
    _restored: bool = dataclasses.field(default=False, repr=False)
    # set by the nan guard when this tenant's lane went non-finite; the
    # session is force-retired at the next boundary with the message on
    # its SessionResult.error
    _error: Optional[str] = dataclasses.field(default=None, repr=False)


@dataclasses.dataclass
class SessionResult:
    sid: int
    # states/outputs are host (numpy) arrays — harvested device->host once;
    # final_m resumes a stream via StreamSession.m0 / drive(m0=) (host on
    # the chunked path, device on the per-tick path; both coerce on use)
    states: Optional[np.ndarray]  # (T, N) streamed node states
    outputs: Optional[np.ndarray]  # (T - washout, n_out) readout outputs
    final_m: np.ndarray  # (N, 3)
    admitted_tick: int
    finished_tick: int
    slot: int
    # online learning (sessions submitted with targets on a learning engine)
    predictions: Optional[np.ndarray] = None  # (T, n_out) a-priori per tick
    learned_readout: Optional[Readout] = None  # final trained W (washout=0)
    learn_nmse: Optional[float] = None  # online NMSE after learn_washout
    # structured failure: set when the engine quarantined this tenant's
    # lane (non-finite state/outputs detected). The harvested arrays above
    # then hold the clean prefix BEFORE the offending chunk; co-tenant
    # lanes are untouched (tests/test_fleet_faults.py pins bit-equality).
    error: Optional[str] = None


@dataclasses.dataclass
class SessionCheckpoint:
    """A mid-stream session frozen for migration between engines/replicas.

    Every field is a host (numpy) array or plain scalar, so a checkpoint
    pickles across a process-transport pipe unchanged. `u_seq`/`targets`
    carry the FULL stream (targets at the session's own n_out width, not
    the source store's padded width); `t` marks how far the source engine
    got; `states`/`outs`/`preds` are the already-harvested prefix. `m` is
    the magnetization at tick t, and `P`/`Wl` the in-flight RLS learner
    (None for inference sessions) — restoring injects them back into the
    destination SlotStore columns, so the resumed stream is bit-identical
    to one that never moved (tests/test_fleet.py pins this)."""

    sid: int
    u_seq: np.ndarray  # (T, N_in) full input stream
    t: int  # ticks already served by the source engine
    m: Optional[np.ndarray]  # (N, 3) at tick t (None: queued, never ran)
    params: Optional[STOParams]
    readout_w: Optional[np.ndarray]  # (N+1, q) static readout, unpadded
    readout_washout: int
    collect_states: bool
    targets: Optional[np.ndarray]  # (T, q) full targets, unpadded
    learn_washout: int
    open: bool
    n_out: int  # the session's own output width q
    states: Optional[np.ndarray]  # (t, N) harvested prefix
    outs: Optional[np.ndarray]  # (t, q) harvested prefix
    preds: Optional[np.ndarray]  # (t, q) harvested prefix
    P: Optional[np.ndarray]  # (S, S) in-flight RLS inverse-Gram
    Wl: Optional[np.ndarray]  # (S, q) in-flight learned weights, unpadded
    # mixed-spec tenants: the session's own SimSpec (host-numpy leaves so
    # the checkpoint still pickles); restore_session re-routes from it
    spec: Optional[SimSpec] = None


@dataclasses.dataclass
class EngineStats:
    """One engine's load/latency snapshot — plain scalars only, so it
    pickles across the replica transport. The fleet router compares these
    live measurements against the capacity planner's predictions."""

    n: int
    num_slots: int
    active: int
    queued: int
    backend: str
    precision: Optional[str]
    learn: Optional[str]
    chunk_ticks: int
    ticks: int
    session_ticks: int
    occupancy: float
    queue_depth: int
    mean_queue_wait: float
    grows: int
    shrinks: int
    detached: int
    # rescale compile behavior (see SchedulerStats): cold = bucket had to
    # compile at the boundary, stalling rescale_stall_s total seconds
    cold_rescales: int
    warm_rescales: int
    rescale_stall_s: float
    chunk_median_s: Optional[float]  # median wall time of recent chunks
    chunks_timed: int
    ticks_per_sec: Optional[float]  # E * K / chunk_median_s
    # spec-level multi-tenancy: internal sub-engines serving sessions whose
    # SimSpec hash differs from the template's (appended with a default so
    # stats pickled by older replicas still unpickle)
    sub_engines: int = 0
    # fault tolerance: tenant lanes the nan guard quarantined (sub-engines
    # included), and the owning replica's health (`healthy | degraded |
    # dead` — stamped by the replica transport, "healthy" for a bare
    # engine). Defaults keep older pickled stats loadable.
    quarantined_lanes: int = 0
    health: str = "healthy"
    # chunks whose launch returned before the launched chunk's states were
    # ready: the host got on with the boundary while the kernel ran
    launches_overlapped: int = 0

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class _ChunkPlan:
    """One launched chunk's host-side record: who occupied which slot for
    how many of the K ticks, plus the device handles to harvest."""

    # (session, slot, n_ticks served in rows [0, n_ticks) of the chunk)
    entries: List[Tuple[StreamSession, int, int]]
    u: np.ndarray  # (K, E, N_in) assembled input block
    mask: np.ndarray  # (K, E) per-tick lane activity
    any_readout: bool
    states_block: Optional[jnp.ndarray] = None  # (K, N, E) device
    outs_block: Optional[jnp.ndarray] = None  # (K, E, n_out) device
    # learning engines only
    targets: Optional[np.ndarray] = None  # (K, E, n_out) target rows
    lmask: Optional[np.ndarray] = None  # (K, E) who LEARNS which tick
    any_learn: bool = False
    preds_block: Optional[jnp.ndarray] = None  # (K, E, n_out) device


# ---------------------------------------------------------------------------
# jit'd readout application (the integrate tick itself lives in repro/api)
# ---------------------------------------------------------------------------


@jax.jit
def _apply_readouts(states_plane, w_out):
    """Slot-batched readout: (N, E) states x (E, N+1, n_out) -> (E, n_out)."""
    e = states_plane.shape[1]
    xb = jnp.concatenate(
        [states_plane, jnp.ones((1, e), states_plane.dtype)], axis=0
    )
    return jnp.einsum("ne,eno->eo", xb, w_out, precision=EXACT_MATMUL)


@jax.jit
def _apply_readouts_chunk(states_block, w_out):
    """Chunked readout: (K, N, E) x (E, N+1, n_out) -> (K, E, n_out).

    ONE device program that maps the per-tick `_apply_readouts` over the K
    planes — a single batched einsum ("kne,eno->keo") contracts in a
    different order and drifts from the per-tick outputs by a ULP, and
    chunked serving pins bit-equality with per-tick serving. It must stay
    one dispatch at any K: the launch queues it behind the running kernel,
    and the runtime holds the host once about 32 programs are in flight
    on a device, so K eager slices and readouts would make the launch wait
    out the kernel. The result stays device-side until the harvest."""
    return jax.lax.map(lambda plane: _apply_readouts(plane, w_out), states_block)


def _spec_host(spec: Optional[SimSpec]) -> Optional[SimSpec]:
    """A SimSpec with every array leaf pulled to host numpy, so it rides a
    SessionCheckpoint across the pickling replica transport unchanged.
    Structural hashes are byte-identical (the hash canonicalizes through
    numpy), so routing on restore lands on the same sub-engine key."""
    if spec is None:
        return None
    params = type(spec.params)(*[np.asarray(leaf) for leaf in spec.params])
    return spec._replace(
        params=params,
        w_cp=np.asarray(spec.w_cp),
        w_in=np.asarray(spec.w_in),
        m0=np.asarray(spec.m0),
    )


def _bucket_slots(demand: int, min_slots: int, max_slots: int) -> int:
    """Smallest cached bucket covering demand: min_slots * 2^k, clamped.

    Power-of-two widths keep the plan cache tiny (log2 of the range) and —
    for buckets >= the kernels' LANE — MXU-aligned, so every bucket's padded
    shapes are ones the dispatch table already knows."""
    b = min_slots
    while b < demand and b < max_slots:
        b *= 2
    return min(b, max_slots)


def _bucket_ladder(min_slots: int, max_slots: int) -> List[int]:
    """Every width `_bucket_slots` can return: min_slots * 2^k while below
    max_slots, plus the clamp bucket max_slots itself (which need not be a
    power-of-two multiple)."""
    ladder = []
    b = min_slots
    while b < max_slots:
        ladder.append(b)
        b *= 2
    ladder.append(max_slots)
    return ladder


def _ensemble_axis_size(plan: ExecPlan) -> int:
    """Devices the ensemble axis spans on a sharded plan (1 if unsharded)."""
    if plan.mesh is None:
        return 1
    size = 1
    for a in plan.ensemble_axes:
        size *= int(plan.mesh.shape[a])
    return size


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


class ReservoirEngine:
    """Serve many concurrent reservoir streams from one batched simulator.

    Construct either from a reservoir template (Reservoir or SimSpec —
    topology W^cp/W^in, dt, hold_steps, default params) plus num_slots (the
    ensemble capacity E), in which case the engine compiles an ExecPlan
    itself; or from an already-compiled `repro.api.CompiledSim` (num_slots
    defaults to the plan's ensemble width) — the route to sharded serving:

        sim = compile_plan(spec, ExecPlan(ensemble=64, mesh=mesh, chunk_ticks=16))
        eng = ReservoirEngine(sim)

    Serving knobs:
      chunk_ticks   (template route; CompiledSim route: set on the ExecPlan)
                    K ticks per dispatch — `run()` pipelines K-tick chunks.
      max_retained  cap on finished SessionResults kept in `results`; oldest
                    are evicted. Pair with `pop_results()` for long-running
                    serving so retired-session state can't accumulate.
      autoscale     an AutoscalePolicy (or True for QueueDepthPolicy()):
                    grow/shrink the slot count between min_slots and
                    max_slots at chunk boundaries via the bucketed plan
                    cache (powers of two from min_slots).
      learn         "rls" or "lms" (template route; CompiledSim route: set
                    on the ExecPlan) enables online readout learning for
                    sessions that submit targets; learn_lam / learn_reg are
                    the RLS forgetting factor and regularization, learn_mu
                    the NLMS step size (see repro.api.plan.ExecPlan).
                    Learning engines serve through the chunked path
                    (run()/step_chunk()) only.
      precision     numerical policy for the compute-bound GEMMs (template
                    route; CompiledSim route: set on the ExecPlan):
                    None/"highest" bit-exact, "bf16_coupling"/"mixed"
                    reduced — see repro.api.plan.ExecPlan.precision.
      compilation_cache_dir  (template route) opt into JAX's persistent
                    compilation cache so cold-start survives restarts —
                    see repro.api.plan.ExecPlan.compilation_cache_dir.
      prewarm       autoscale engines pre-compile + warm the adjacent
                    buckets in a background daemon thread (at construction
                    and after every rescale), so `_rescale` at a chunk
                    boundary finds its bucket ready in the process-wide
                    PlanCache — zero XLA stall. prewarm=False disables the
                    thread (deterministic compile counting in tests);
                    `prewarm_buckets(block=True)` warms explicitly.

    Compilation is shared: the template route and every rescale draw from
    `repro.api.PLAN_CACHE`, so repeated engines over the same topology and
    plan (fleet replicas, tune combos) compile once per process.
    """

    def __init__(
        self,
        res: Union[Reservoir, SimSpec, CompiledSim],
        num_slots: Optional[int] = None,
        backend: str = "auto",
        n_out: int = 1,
        measure: bool = False,
        interpret: bool = False,
        chunk_ticks: Optional[int] = None,
        max_retained: Optional[int] = None,
        autoscale: Union[AutoscalePolicy, bool, None] = None,
        min_slots: Optional[int] = None,
        max_slots: Optional[int] = None,
        learn: Optional[str] = None,
        learn_lam: Optional[float] = None,
        learn_reg: Optional[float] = None,
        learn_mu: Optional[float] = None,
        precision: Optional[str] = None,
        compilation_cache_dir: Optional[str] = None,
        prewarm: bool = True,
        nan_guard: bool = True,
    ):
        if isinstance(res, CompiledSim):
            sim = res
            if num_slots is not None and num_slots != sim.plan.ensemble:
                raise ValueError(
                    f"num_slots ({num_slots}) must match the compiled plan's "
                    f"ensemble width ({sim.plan.ensemble}); omit num_slots to "
                    f"use the plan's"
                )
            if (
                backend != "auto"
                or measure
                or interpret
                or chunk_ticks is not None
                or learn is not None
                or learn_lam is not None
                or learn_reg is not None
                or learn_mu is not None
                or precision is not None
                or compilation_cache_dir is not None
            ):
                raise ValueError(
                    "backend/measure/interpret/chunk_ticks/learn*/precision/"
                    "compilation_cache_dir are ExecPlan decisions; when "
                    "constructing from a CompiledSim, set them on the plan "
                    "passed to compile_plan instead"
                )
            num_slots = sim.plan.ensemble
        else:
            if num_slots is None:
                raise TypeError("num_slots is required when constructing from a reservoir template")
            if backend not in BACKENDS:
                raise ValueError(f"backend must be one of {BACKENDS}; got {backend!r}")
            spec = res if isinstance(res, SimSpec) else SimSpec.from_reservoir(res)
            # backend="auto" resolves inside compile_plan: measured-latency
            # dispatch table (in-process + persisted JSON) > platform gate >
            # VMEM heuristic. On CPU that lands on "ref" — the plain-lax.scan
            # XLA path over the planes layout (unpadded, measured faster than
            # the core-layout scan at every (N, E)); "scan" remains available
            # as the core-layout mode that reproduces solo drive() bit-for-bit.
            # Drawn through the process-wide PlanCache: engines built from
            # the same topology + plan (fleet replicas, repeated spin-ups)
            # share one CompiledSim instead of re-tracing it.
            sim = PLAN_CACHE.get_or_compile(
                spec,
                ExecPlan(
                    impl=backend,
                    ensemble=num_slots,
                    interpret=interpret,
                    measure=measure,
                    chunk_ticks=1 if chunk_ticks is None else chunk_ticks,
                    learn=learn,
                    learn_lam=1.0 if learn_lam is None else learn_lam,
                    learn_reg=1e-6 if learn_reg is None else learn_reg,
                    learn_mu=0.5 if learn_mu is None else learn_mu,
                    precision=precision,
                    compilation_cache_dir=compilation_cache_dir,
                ),
            )
        self.sim = sim
        self.res = sim.spec
        self._spec_hash = spec_structural_hash(sim.spec)
        self.chunk_ticks = sim.plan.chunk_ticks
        self.learn = sim.plan.learn
        self.store = SlotStore(
            sim.spec,
            num_slots,
            n_out=n_out,
            learn=self.learn,
            learn_reg=sim.plan.learn_reg,
        )
        self.scheduler = SlotScheduler(num_slots)
        self.tick_count = 0
        self.results: Dict[int, SessionResult] = {}
        self.max_retained = max_retained
        self.backend = sim.impl
        # the plan's numerical policy ("highest" = bit-exact default) — the
        # serve bench reports it per cell alongside the backend
        self.precision = sim.precision

        # -- autoscaling: bucketed plan cache over ensemble widths ---------
        if autoscale is True:
            autoscale = QueueDepthPolicy()
        self.autoscale: Optional[AutoscalePolicy] = autoscale or None
        self.min_slots = num_slots if min_slots is None else min_slots
        self.max_slots = num_slots if max_slots is None else max_slots
        if self.autoscale is not None:
            if not (1 <= self.min_slots <= num_slots <= self.max_slots):
                raise ValueError(
                    f"autoscale bounds must satisfy 1 <= min_slots <= "
                    f"num_slots <= max_slots; got min={self.min_slots} "
                    f"num={num_slots} max={self.max_slots}"
                )
            if sim.plan.sharded:
                # every reachable bucket width must divide evenly across
                # the mesh's ensemble axis, or a rescale would strand lanes
                # on a decomposition the shard_map body can't express
                axis = _ensemble_axis_size(sim.plan)
                widths = [num_slots] + _bucket_ladder(self.min_slots, self.max_slots)
                bad = sorted({w for w in widths if w % axis})
                if bad:
                    raise ValueError(
                        "autoscale on a sharded plan requires every bucket "
                        "width to be divisible by the ensemble-axis size "
                        f"{axis} (mesh axes {tuple(sim.plan.ensemble_axes)}); "
                        f"min_slots={self.min_slots} / max_slots="
                        f"{self.max_slots} reach incompatible widths {bad}"
                    )
            leaf = jnp.asarray(sim.spec.params.gamma)
            if leaf.ndim != 0:
                raise ValueError(
                    "autoscale requires scalar-leaved spec params (per-tenant "
                    "params ride in session lanes, not the spec)"
                )
        self._sims: Dict[int, CompiledSim] = {num_slots: sim}
        # background pre-warm of adjacent autoscale buckets (daemon thread;
        # advisory — _rescale compiles on demand if the thread hasn't won)
        self._prewarm_enabled = bool(prewarm)
        self._prewarm_thread: Optional[threading.Thread] = None
        if self._prewarm_enabled and self.autoscale is not None:
            self.prewarm_buckets()

        # -- pipelined-chunk bookkeeping ------------------------------------
        # sessions whose final tick was served by the most recently LAUNCHED
        # chunk (slot still holds their state until the next boundary)
        self._finishing: List[Tuple[int, StreamSession]] = []
        # one boundary's retired sessions awaiting their last chunk's
        # harvest: ([(slot, session), ...], (k, N, 3) final-m device block,
        # (k, S, n_out) learned-W device block or None)
        self._awaiting: Optional[
            Tuple[
                List[Tuple[int, StreamSession]],
                jnp.ndarray,
                Optional[jnp.ndarray],
            ]
        ] = None
        # device copy of the last chunk's lane-mask block; steady-state
        # chunks repeat the same mask, so skip the re-upload (same for the
        # learn mask — constant once every learner is past washout)
        self._mask_np: Optional[np.ndarray] = None
        self._mask_dev: Optional[jnp.ndarray] = None
        self._lmask_np: Optional[np.ndarray] = None
        self._lmask_dev: Optional[jnp.ndarray] = None
        # -- tenant lane quarantine -----------------------------------------
        # nan_guard=True: every harvested chunk's state/output/prediction
        # blocks are scanned for non-finite values (one aggregate isfinite
        # per block on the cheap path); an offending tenant's lane is
        # QUARANTINED — force-retired at the next boundary with a
        # structured SessionResult.error — while co-tenant lanes stream on
        # bit-identically (lanes are independent columns of the E axis).
        self.nan_guard = bool(nan_guard)
        self._quarantine: List[Tuple[int, StreamSession]] = []
        # the launched-but-unharvested chunk (the pipeline's second buffer)
        self._pending: Optional[_ChunkPlan] = None
        # wall time of recent step_chunk calls that launched work — the
        # stats() latency signal the fleet planner checks itself against
        self._chunk_times: deque = deque(maxlen=128)
        self._launches_overlapped = 0
        # -- spec-level multi-tenancy ---------------------------------------
        # sessions whose SimSpec structural hash differs from the template's
        # serve on an internal sub-engine compiled for THEIR spec (one per
        # distinct hash, drawn through the shared PLAN_CACHE); step_chunk
        # advances them in lockstep and drains their results into ours
        self._subengines: Dict[str, "ReservoirEngine"] = {}

    @property
    def num_slots(self) -> int:
        return self.store.num_slots

    # -- session lifecycle -------------------------------------------------

    def _pad_cols(self, a: np.ndarray, what: str, sid: int) -> np.ndarray:
        """Zero-pad the trailing (column) axis to the store's n_out width.

        Per-session n_out: a session whose readout/targets carry q < n_out
        columns rides the store-width lanes with zero columns appended.
        RLS weight columns update independently given the shared gain
        (W' = W + k e^T is column-wise), so the padding columns never
        perturb the real ones and results slice back exactly."""
        q = a.shape[-1]
        if q == self.store.n_out:
            return a
        if q > self.store.n_out:
            raise ValueError(
                f"session {sid}: {what} has {q} output columns but the "
                f"engine was built with n_out={self.store.n_out}; construct "
                f"ReservoirEngine(..., n_out={q}) (or wider) to serve it"
            )
        pad = np.zeros(a.shape[:-1] + (self.store.n_out - q,), a.dtype)
        return np.concatenate([a, pad], axis=-1)

    # -- spec-level multi-tenancy -------------------------------------------

    def _route_spec(self, session: StreamSession) -> Optional["ReservoirEngine"]:
        """Resolve a spec-carrying session to the engine that serves it.

        Returns None when the session belongs on THIS engine (its spec
        structurally matches the template: same shapes/dtype/topology
        contents/family — the hash ignores scalar param values, which ride
        the session's lane instead), or the per-hash sub-engine otherwise.
        """
        spec = session.spec
        leaf = jnp.asarray(spec.params.gamma)
        if leaf.ndim != 0:
            raise ValueError(
                f"session {session.sid}: a session spec must carry "
                f"scalar-leaved params (per-lane values are the lane's job; "
                f"ensemble-leaved sweeps belong on the engine template)"
            )
        h = spec_structural_hash(spec)
        if h == self._spec_hash:
            # structurally the template's physics: serve in a primary lane.
            # The spec's scalar params become the lane values unless the
            # session pinned its own params explicitly (explicit wins).
            if session.params is None and not _params_equal(
                spec.params, self.res.params
            ):
                session.params = spec.params
            return None
        sub = self._subengines.get(h)
        if sub is None:
            sub = self._make_subengine(spec)
            self._subengines[h] = sub
        return sub

    def _make_subengine(self, spec: SimSpec) -> "ReservoirEngine":
        """Compile + wrap a sub-engine for a structurally different spec.

        The sub-plan is the template plan at the engine's min_slots width —
        drawn through the process-wide PLAN_CACHE, so two engines (or two
        lifetimes of one engine) serving the same foreign spec compile it
        once. An impl the spec's physics family cannot execute (e.g. a
        fused Pallas template serving a time_multiplexed tenant) falls back
        to impl="auto", which resolves to a family-capable backend inside
        compile_plan. Sharded templates refuse: families do not shard, and
        silently serving a tenant unsharded on a mesh engine would lie
        about its placement.
        """
        plan = self.sim.plan
        if plan.mesh is not None:
            raise ValueError(
                "mixed-spec tenancy is not supported on sharded engines — "
                "a sub-engine cannot inherit the mesh decomposition; serve "
                f"the {spec.topology!r} spec from an unsharded engine"
            )
        impl = plan.impl
        if impl not in FAMILY_IMPLS.get(spec.topology, ()):
            impl = "auto"
        sub_plan = dataclasses.replace(
            plan, ensemble=self.min_slots, impl=impl
        )
        sim = PLAN_CACHE.get_or_compile(spec, sub_plan)
        return ReservoirEngine(
            sim,
            n_out=self.store.n_out,
            max_retained=self.max_retained,
            prewarm=False,
            nan_guard=self.nan_guard,
        )

    def submit(self, session: StreamSession) -> None:
        if session.spec is not None:
            sub = self._route_spec(session)
            if sub is not None:
                sub.submit(session)
                return
        # xp=np: the engine assembles u blocks host-side, so the series must
        # stay a numpy array — coercing through the device would round-trip
        # every stream through HBM for nothing
        u = coerce_input_series(
            session.u_seq, self.store.n_in, self.store.dtype, xp=np
        )
        if u.shape[0] == 0 and not session.open:
            raise ValueError(f"session {session.sid}: empty input stream")
        session.u_seq = u
        n_out = None  # the session's own width, inferred below
        if session.readout is not None:
            w = np.asarray(session.readout.w_out)
            if w.ndim != 2 or w.shape[0] != self.store.n + 1 or not (
                1 <= w.shape[1] <= self.store.n_out
            ):
                raise ValueError(
                    f"session {session.sid}: readout w_out shape "
                    f"{tuple(w.shape)} must be ({self.store.n + 1}, q) with "
                    f"1 <= q <= {self.store.n_out} (the engine's n_out)"
                )
            n_out = w.shape[1]
        if session.targets is not None:
            if self.learn is None:
                raise ValueError(
                    f"session {session.sid}: targets require a learning "
                    f"engine — compile the plan with ExecPlan(learn='rls') "
                    f"or learn='lms' (or pass learn=... to ReservoirEngine)"
                )
            t = np.asarray(session.targets, dtype=self.store.dtype)
            if t.ndim == 1:
                t = t[:, None]
            if (
                t.ndim != 2
                or t.shape[0] != u.shape[0]
                or not (1 <= t.shape[1] <= self.store.n_out)
            ):
                raise ValueError(
                    f"session {session.sid}: targets must have shape "
                    f"({u.shape[0]}, q) — one row per input row, "
                    f"1 <= q <= {self.store.n_out} — or ({u.shape[0]},) for "
                    f"q == 1; got {tuple(np.shape(session.targets))}"
                )
            if n_out is not None and t.shape[1] != n_out:
                raise ValueError(
                    f"session {session.sid}: targets carry {t.shape[1]} "
                    f"output columns but the readout carries {n_out}; a "
                    f"session has ONE output width"
                )
            n_out = t.shape[1]
            # store-width padded targets: chunk assembly copies rows straight
            # into the (K, E, n_out) block; results slice back to q columns
            session.targets = self._pad_cols(t, "targets", session.sid)
            if (
                isinstance(session.learn_washout, bool)
                or not isinstance(session.learn_washout, int)
                or session.learn_washout < 0
            ):
                raise ValueError(
                    f"session {session.sid}: learn_washout must be an int "
                    f">= 0; got {session.learn_washout!r}"
                )
        session._n_out = self.store.n_out if n_out is None else n_out
        if session.learn_w0 is not None or session.learn_P0 is not None:
            if self.learn is None or session.targets is None:
                raise ValueError(
                    f"session {session.sid}: learn_w0/learn_P0 resume a "
                    f"learn recursion — they require a learning engine and "
                    f"targets"
                )
            if session.learn_P0 is not None and self.learn == "lms":
                raise ValueError(
                    f"session {session.sid}: learn_P0 resumes an RLS "
                    f"inverse-Gram — learn='lms' carries no P; resume LMS "
                    f"sessions with learn_w0 alone"
                )
            if session.learn_w0 is not None:
                w0 = np.asarray(session.learn_w0, self.store.dtype)
                if w0.shape != (self.store.n + 1, session._n_out):
                    raise ValueError(
                        f"session {session.sid}: learn_w0 shape "
                        f"{tuple(w0.shape)} != ({self.store.n + 1}, "
                        f"{session._n_out})"
                    )
                session.learn_w0 = w0
            if session.learn_P0 is not None:
                p0 = np.asarray(session.learn_P0, self.store.dtype)
                s = self.store.n + 1
                if p0.shape != (s, s):
                    raise ValueError(
                        f"session {session.sid}: learn_P0 shape "
                        f"{tuple(p0.shape)} != ({s}, {s})"
                    )
                session.learn_P0 = p0
        self.scheduler.submit(session)

    def _admit_pending(self) -> None:
        placed = self.scheduler.admissions(self.store.free_slots())
        if not placed:
            return
        items = []
        for slot, sess in placed:
            w_out = None
            if sess.readout is not None:
                w_out = self._pad_cols(
                    np.asarray(sess.readout.w_out, self.store.dtype),
                    "readout",
                    sess.sid,
                )
            # a learning session's lane warm-starts from (priority order)
            # a migration checkpoint's in-flight weights, else its provided
            # readout, else zeros; learn_P0 resumes the inverse-Gram
            w_learn = None
            p_learn = None
            if sess.targets is not None:
                if sess.learn_w0 is not None:
                    w_learn = self._pad_cols(
                        sess.learn_w0, "learn_w0", sess.sid
                    )
                else:
                    w_learn = w_out
                if sess.learn_P0 is not None:
                    p_learn = sess.learn_P0
            items.append(
                (slot, sess.m0, sess.params, w_out, w_learn, p_learn)
            )
            sess._slot = slot
            if sess._restored:
                # a migrated session resumes mid-stream: _t and the
                # harvested prefix were seeded by restore_session()
                sess._restored = False
            else:
                sess._t = 0
                sess._states = []
                sess._outs = []
                sess._preds = []
            sess._admitted_tick = self.tick_count
        self.store.admit_many(items)  # one scatter per array, not per session

    def _record_result(
        self,
        sess: StreamSession,
        slot: int,
        final_m: jnp.ndarray,
        learned_w: Optional[np.ndarray] = None,
    ) -> None:
        """Assemble a SessionResult from the session's harvested pieces.

        The per-tick path accumulates (N,) device state rows / (n_out,)
        output rows; the chunked path accumulates host (n, N) / (n, n_out)
        blocks — both concatenate to the same (T, N) / (T, n_out).
        Assembly is numpy: the chunked path's blocks were already bulk
        device->host transfers, and re-uploading the history just so the
        caller can pull it back down would round-trip every finished
        session's full state through the device."""
        # empty accumulators (a lane quarantined before its first harvest)
        # yield (0, width) arrays so error results keep uniform shapes
        states = None
        if sess.collect_states:
            states = (
                np.concatenate([np.atleast_2d(np.asarray(s)) for s in sess._states])
                if sess._states
                else np.zeros((0, self.store.n), self.store.dtype)
            )
        outputs = None
        if sess.readout is not None:
            outs = (
                np.concatenate([np.atleast_2d(np.asarray(o)) for o in sess._outs])
                if sess._outs
                else np.zeros((0, sess._n_out), self.store.dtype)
            )
            outputs = outs[sess.readout.washout :]
        predictions = None
        learned_readout = None
        learn_nmse = None
        if sess.targets is not None:
            q = sess._n_out
            predictions = (
                np.concatenate([np.atleast_2d(np.asarray(p)) for p in sess._preds])
                if sess._preds
                else np.zeros((0, q), self.store.dtype)
            )
            if learned_w is not None:
                # washout=0: the trained readout applies to arbitrary
                # states; padding columns (store width > session width)
                # slice off so the tenant gets back exactly its shape
                learned_readout = Readout(
                    w_out=jnp.asarray(learned_w[:, :q]), washout=0
                )
            wo = sess.learn_washout
            if predictions.shape[0] > wo:
                p, y = predictions[wo:], sess.targets[wo:, :q]
                learn_nmse = float(
                    np.mean((p - y) ** 2) / (np.var(y) + 1e-30)
                )
        self.results[sess.sid] = SessionResult(
            sid=sess.sid,
            states=states,
            outputs=outputs,
            final_m=final_m,
            admitted_tick=sess._admitted_tick,
            finished_tick=sess._finished_tick,
            slot=slot,
            predictions=predictions,
            learned_readout=learned_readout,
            learn_nmse=learn_nmse,
            error=sess._error,
        )
        sess._states = []
        sess._outs = []
        sess._preds = []
        if self.max_retained is not None:
            while len(self.results) > self.max_retained:
                self.results.pop(next(iter(self.results)))

    def submit_autotuned(
        self,
        session: StreamSession,
        space,
        budget: int = 8,
        strategy="random",
        seed: int = 0,
        **kwargs,
    ):
        """Auto-tune this session's lane knobs during its washout window,
        then submit it with the winning parameters.

        `space` is a `repro.tune.SearchSpace` over LANE knobs (STOParams
        fields — structural knobs would need a recompile, which a live
        engine cannot do). Probe sessions stream the tenant's washout
        prefix on spare lanes with negative sids, scored by the fused
        online learner; the best assignment is frozen into
        `session.params` and the session submits normally. Returns the
        probe `TuneResult` (trial history + winner). Requires a learning
        engine and a learning session with learn_washout >= 2.

        Thin delegate to `repro.tune.washout_autotune` (imported lazily:
        serve must not depend on tune at import time — tune drives serve).
        """
        from repro.tune.driver import washout_autotune

        return washout_autotune(
            self, session, space,
            budget=budget, strategy=strategy, seed=seed, **kwargs,
        )

    def pop_results(self) -> Dict[int, SessionResult]:
        """Drain finished-session results: returns sid -> SessionResult and
        clears the retained map. Long-running serving loops should call this
        (or set max_retained) so retired-session state cannot accumulate."""
        out = self.results
        self.results = {}
        return out

    def _retire(self, slot: int) -> None:
        """Per-tick path: retire immediately (state column is current)."""
        sess = self.scheduler.retire(slot)
        sess._finished_tick = self.tick_count
        final_m = self.store.state_column(slot)
        self._record_result(sess, slot, final_m)
        self.store.retire(slot)

    # -- autoscaling --------------------------------------------------------

    def _maybe_autoscale(self) -> None:
        sched = self.scheduler
        active = len(sched.running)
        target = self.autoscale.target_slots(
            active=active,
            queued=len(sched.queue),
            num_slots=self.num_slots,
            min_slots=self.min_slots,
            max_slots=self.max_slots,
        )
        target = max(target, active, 1)
        bucket = _bucket_slots(target, self.min_slots, self.max_slots)
        if bucket != self.num_slots:
            self._rescale(bucket)

    def _rescale(self, new_e: int) -> None:
        """Migrate serving onto the cached CompiledSim of width new_e.

        Occupied slots compact into the low lanes of the new store (one
        gather-scatter of the (3, N, E) planes + readout lanes); running
        sessions keep streaming across the boundary bit-identically.

        The bucket is drawn from the process-wide PlanCache. A bucket the
        background pre-warm thread (prewarm_buckets) already compiled AND
        executed costs zero XLA work here (warm_rescales); otherwise the
        boundary pays the compile NOW — warmed synchronously so the stall
        is measured here (cold_rescales / rescale_stall_s) instead of
        surfacing as one mysteriously slow chunk."""
        stats = self.scheduler.stats
        sim = self._sims.get(new_e)
        if sim is not None:
            stats.warm_rescales += 1
        else:
            spec = self.sim.spec
            plan_b = dataclasses.replace(self.sim.plan, ensemble=new_e)
            n_out = self.store.n_out
            warm = PLAN_CACHE.contains(spec, plan_b) and PLAN_CACHE.is_warm(
                spec, plan_b, n_out=n_out
            )
            t0 = time.perf_counter()
            sim = PLAN_CACHE.get_or_compile(spec, plan_b)
            PLAN_CACHE.warm(sim, n_out=n_out)
            if warm:
                stats.warm_rescales += 1
            else:
                stats.cold_rescales += 1
                stats.rescale_stall_s += time.perf_counter() - t0
            self._sims[new_e] = sim
        slot_map = {old: new for new, old in enumerate(sorted(self.scheduler.running))}
        self.store = self.store.resized(new_e, slot_map)
        self.scheduler.remap(slot_map, new_e)
        for slot, sess in self.scheduler.running.items():
            sess._slot = slot
        self.sim = sim
        self.backend = sim.impl
        self.precision = sim.precision
        if self._prewarm_enabled:
            self.prewarm_buckets()

    def prewarm(self, block: bool = True) -> None:
        """Warm-start the engine: force XLA compilation of the current
        width's serving hot path (one masked zero chunk through the shared
        PlanCache) plus the adjacent autoscale buckets. The fleet spin-up /
        migration warm-start entry point — after this, the first real
        chunk and the next rescale both dispatch pre-compiled executables."""
        PLAN_CACHE.warm(self.sim, n_out=self.store.n_out)
        self.prewarm_buckets(block=block)

    def prewarm_buckets(self, block: bool = False) -> Tuple[int, ...]:
        """Pre-compile the autoscale buckets adjacent to the current width.

        Runs in a daemon thread so a later `_rescale` at a chunk boundary
        finds its bucket already compiled AND warmed in the shared
        PlanCache — the serving loop never stalls on XLA. The compile runs
        outside the cache lock with per-key in-flight events, so a
        concurrent `_rescale` racing the pre-warm waits for that one
        compile rather than duplicating it. Advisory: failures are
        swallowed (the rescale path compiles on demand), and a still-busy
        previous pre-warm skips this round. Returns the widths scheduled;
        block=True waits for completion (tests, explicit warm spin-up)."""
        if self.autoscale is None:
            return ()
        if self._prewarm_thread is not None and self._prewarm_thread.is_alive():
            if not block:
                return ()
            self._prewarm_thread.join()
        ladder = _bucket_ladder(self.min_slots, self.max_slots)
        below = [b for b in ladder if b < self.num_slots]
        above = [b for b in ladder if b > self.num_slots]
        spec, plan = self.sim.spec, self.sim.plan
        n_out = self.store.n_out
        targets = tuple(
            b
            for b in ([below[-1]] if below else []) + ([above[0]] if above else [])
            if not PLAN_CACHE.is_warm(
                spec, dataclasses.replace(plan, ensemble=b), n_out=n_out
            )
        )
        if not targets:
            return ()

        def work():
            for b in targets:
                try:
                    sim = PLAN_CACHE.ensure_warm(
                        spec, dataclasses.replace(plan, ensemble=b), n_out=n_out
                    )
                    self._sims.setdefault(b, sim)
                except Exception:  # advisory: the serving loop compiles on demand
                    pass

        t = threading.Thread(target=work, daemon=True, name="plan-prewarm")
        self._prewarm_thread = t
        t.start()
        if block:
            t.join()
        return targets

    # -- the synchronous per-tick path --------------------------------------

    def _advance(self, u: jnp.ndarray) -> jnp.ndarray:
        """One input tick for every slot; returns the (N, E) states plane."""
        store = self.store
        store.m, states_plane = self.sim.tick(
            store.m,
            u,
            lane_mask=store.active_mask,
            params=store.params_ensemble,
        )
        return states_plane

    def step(self) -> bool:
        """Admit, advance one tick, harvest. Returns False when drained.

        The synchronous baseline: one `CompiledSim.tick` dispatch and one
        per-slot harvest per input tick. `run()` is the pipelined chunked
        path; both produce identical per-session results on the scan
        backend (bit-exact) and tolerance-equal elsewhere."""
        if self.learn is not None:
            raise RuntimeError(
                "online learning (ExecPlan.learn) runs on the chunked "
                "serving path only — drive the engine with run() or "
                "step_chunk() (chunk_ticks=1 preserves per-tick semantics)"
            )
        if self._subengines:
            raise RuntimeError(
                "mixed-spec tenants are served on the chunked path only — "
                "drive the engine with run() or step_chunk()"
            )
        self._admit_pending()
        running = self.scheduler.running
        if not running:
            return self.scheduler.has_work()

        u = np.zeros((self.store.num_slots, self.store.n_in), self.store.dtype)
        any_readout = False
        for slot, sess in running.items():
            if sess.open:
                raise RuntimeError(
                    "open (push) streams are served on the chunked path "
                    "only — drive the engine with run() or step_chunk()"
                )
            u[slot] = sess.u_seq[sess._t]
            any_readout = any_readout or sess.readout is not None
        states_plane = self._advance(jnp.asarray(u))
        outs = (
            _apply_readouts(states_plane, self.store.w_out)  # (E, n_out)
            if any_readout
            else None
        )
        self.scheduler.on_tick()
        self.tick_count += 1

        for slot, sess in list(running.items()):
            if sess.collect_states:
                sess._states.append(states_plane[:, slot])
            if sess.readout is not None:
                sess._outs.append(outs[slot, : sess._n_out])
            sess._t += 1
            if sess._t >= sess.u_seq.shape[0]:
                self._retire(slot)
        return True

    # -- the pipelined chunked path -----------------------------------------

    def _retire_finishers(self) -> None:
        """Snapshot + free the slots of sessions that finished inside the
        launched chunk. store.m already points at that chunk's (possibly
        still in-flight) result; jnp arrays are immutable, so slicing now
        snapshots it lazily. One gather snapshots every finisher's final
        state (and trained Wl column on learning engines); one scatter
        frees the slots. Results materialize at `_finalize_awaiting`."""
        if not self._finishing:
            return
        slots = [slot for slot, _ in self._finishing]
        finals = self.store.state_columns(slots)  # (k, N, 3) device, lazy
        w_finals = (
            self.store.learn_w_columns(slots)
            if self.learn is not None
            else None
        )
        for slot, sess in self._finishing:
            self.scheduler.retire(slot)
        self._awaiting = (self._finishing, finals, w_finals)
        self.store.retire_many(slots)
        self._finishing = []

    def _scan_for_nonfinite(
        self,
        plan: _ChunkPlan,
        states_np: Optional[np.ndarray],
        outs_np: Optional[np.ndarray],
        preds_np: Optional[np.ndarray],
    ) -> None:
        """Per-chunk nan guard over the harvested blocks. The cheap path is
        one aggregate isfinite per block; only when that trips does the
        per-lane isolation run. An offending tenant is marked for
        quarantine — its lane retires at the next boundary with a
        structured error, its already-harvested prefix intact. Co-tenant
        lanes are untouched by construction: every lane is an independent
        column of the ensemble axis (the batched GEMMs never mix columns),
        so a NaN cannot cross lanes and the guard itself performs no
        device work. A session with no harvested block at all (no states
        collected, no readout, no targets) has no surface to scan — its
        divergence shows up in final_m instead."""
        blocks = [b for b in (states_np, outs_np, preds_np) if b is not None]
        if not blocks or all(np.isfinite(b).all() for b in blocks):
            return
        for sess, slot, n in plan.entries:
            if n == 0 or sess._error is not None:
                continue
            bad = []
            if (
                states_np is not None
                and sess.collect_states
                and not np.isfinite(states_np[:n, :, slot]).all()
            ):
                bad.append("states")
            if (
                outs_np is not None
                and sess.readout is not None
                and not np.isfinite(outs_np[:n, slot, : sess._n_out]).all()
            ):
                bad.append("outputs")
            if (
                preds_np is not None
                and sess.targets is not None
                and not np.isfinite(preds_np[:n, slot, : sess._n_out]).all()
            ):
                bad.append("predictions")
            if bad:
                sess._error = (
                    f"non_finite_state: session {sess.sid} (lane {slot}) "
                    f"produced non-finite {'/'.join(bad)} in the chunk "
                    f"ending at tick {sess._t}; tenant quarantined "
                    f"(co-tenant lanes unaffected)"
                )
                self.scheduler.stats.quarantined_lanes += 1
                self._quarantine.append((slot, sess))

    def _retire_quarantined(self) -> None:
        """Force-retire lanes the nan guard flagged: record an error-bearing
        SessionResult (clean harvested prefix + structured error) and free
        the slot. A flagged session that also finished naturally was
        already retired by the finisher path — its result still carries
        the error via `_record_result`."""
        if not self._quarantine:
            return
        for slot, sess in self._quarantine:
            if self.scheduler.running.get(slot) is not sess:
                continue  # finished (or detached) since it was flagged
            self.scheduler.retire(slot)
            sess._finished_tick = self.tick_count
            final_m = np.asarray(self.store.state_column(slot)).copy()
            learned_w = None
            if self.learn is not None and sess.targets is not None:
                learned_w = np.asarray(
                    self.store.learn_w_columns([slot])[0]
                ).copy()
            self._record_result(sess, slot, final_m, learned_w=learned_w)
            self.store.retire(slot)
        self._quarantine = []

    def _assemble_chunk(self) -> Optional[_ChunkPlan]:
        """Host-side boundary work: finalize the previous chunk's finishers,
        autoscale, admit, and build the next K-tick u/mask block.

        Returns None when nothing is left to serve. Runs while the device
        executes the previously launched chunk — this is the overlap the
        pipeline exists for."""
        with _span("engine.retire"):
            # 1) sessions that finished inside the launched chunk: their lanes
            # were masked off after their last tick, so the chunk-output column
            # IS their final state — snapshot + free in one gather/scatter pair.
            self._retire_finishers()

            # 1b) lanes the nan guard flagged at the last harvest: force-retire
            # them (error result) before admissions so their slots refill
            self._retire_quarantined()

        # 2) resize at the boundary (slots now reflect retirements)
        if self.autoscale is not None:
            self._maybe_autoscale()

        # 3) refill freed slots
        with _span("engine.admit"):
            self._admit_pending()
        running = self.scheduler.running
        if not running:
            return None
        with _span("engine.assemble"):
            return self._assemble_block(running)

    def _assemble_block(self, running) -> Optional[_ChunkPlan]:
        """Step 4 of `_assemble_chunk`: the chunk's blocks for the running
        lanes, or None when every resident is an idle open stream."""
        # 4) K-tick input block + per-tick lane masks (mid-chunk retires
        # mask a lane's trailing rows off; the slot refills next boundary),
        # plus — on learning engines — the target block and learn mask
        # (False rows: washout ticks, inference-only tenants, idle lanes)
        k = self.chunk_ticks
        e, n_in = self.store.num_slots, self.store.n_in
        u = np.zeros((k, e, n_in), self.store.dtype)
        mask = np.zeros((k, e), dtype=bool)
        learning = self.learn is not None
        y = np.zeros((k, e, self.store.n_out), self.store.dtype) if learning else None
        lmask = np.zeros((k, e), dtype=bool) if learning else None
        entries = []
        any_readout = False
        any_learn = False
        session_ticks = 0
        for slot, sess in running.items():
            t0 = sess._t
            # an idle OPEN session (input exhausted, not closed) serves
            # n == 0 ticks: its lane mask stays False for the whole chunk,
            # so tick_chunk freezes the state until append_ticks refills it
            n = min(k, sess.u_seq.shape[0] - t0)
            u[:n, slot] = sess.u_seq[t0 : t0 + n]
            mask[:n, slot] = True
            if learning and sess.targets is not None:
                y[:n, slot] = sess.targets[t0 : t0 + n]
                # update only from the session's learn_washout tick onward
                start = max(0, sess.learn_washout - t0)
                lmask[start:n, slot] = True
                # a-priori predictions are recorded even during washout, so
                # any served tick of a learning session needs the preds block
                any_learn = any_learn or n > 0
            sess._t = t0 + n
            entries.append((sess, slot, n))
            session_ticks += n
            any_readout = any_readout or (sess.readout is not None and n > 0)
            if sess._t >= sess.u_seq.shape[0] and not sess.open:
                sess._finished_tick = self.tick_count + n
                self._finishing.append((slot, sess))
        if session_ticks == 0:
            # every resident is an idle open stream: nothing to launch, and
            # the clock must NOT advance (a push stream parked for a million
            # boundaries would otherwise distort occupancy/throughput
            # stats). quiesce() drains the in-flight chunk first, so a
            # just-closed exhausted stream retires with every harvested row.
            self.quiesce()
            return None
        self.scheduler.on_ticks(k, session_ticks)
        self.tick_count += k

        return _ChunkPlan(
            entries=entries, u=u, mask=mask, any_readout=any_readout,
            targets=y, lmask=lmask, any_learn=any_learn,
        )

    def _launch_chunk(self, plan: _ChunkPlan) -> None:
        """Dispatch the chunk; returns immediately (JAX async dispatch)."""
        with _span("engine.launch"):
            store = self.store
            if self._mask_np is None or not (
                self._mask_np.shape == plan.mask.shape
                and np.array_equal(self._mask_np, plan.mask)
            ):
                self._mask_np = plan.mask
                self._mask_dev = jnp.asarray(plan.mask)
            if self.learn is not None:
                if self._lmask_np is None or not (
                    self._lmask_np.shape == plan.lmask.shape
                    and np.array_equal(self._lmask_np, plan.lmask)
                ):
                    self._lmask_np = plan.lmask
                    self._lmask_dev = jnp.asarray(plan.lmask)
                # one dispatch advances physics AND learning: P/Wl lanes ride
                # the chunk, a-priori predictions come back in the same result
                store.m, states_block, (store.P, store.Wl), preds = (
                    self.sim.tick_chunk(
                        store.m,
                        jnp.asarray(plan.u),
                        lane_mask=self._mask_dev,
                        params=store.params_ensemble,
                        targets=jnp.asarray(plan.targets),
                        learn_state=(store.P, store.Wl),
                        learn_mask=self._lmask_dev,
                    )
                )
                plan.preds_block = preds
            else:
                store.m, states_block = self.sim.tick_chunk(
                    store.m,
                    jnp.asarray(plan.u),
                    lane_mask=self._mask_dev,
                    params=store.params_ensemble,
                )
            plan.states_block = states_block
            if plan.any_readout:
                plan.outs_block = _apply_readouts_chunk(states_block, store.w_out)
            if not states_block.is_ready():  # non-blocking
                self._launches_overlapped += 1

    def _harvest_chunk(self, plan: _ChunkPlan) -> None:
        """ONE bulk device->host transfer for the chunk, then host-side
        per-session masking/slicing — replaces per-tick per-slot slicing.

        When nobody in the chunk collects states, the (K, N, E) block never
        leaves the device (at N=1024, E=256, K=8 that is an 8 MB transfer
        per chunk saved)."""
        with _span("engine.harvest"):
            with _span("engine.fetch"):
                # the host waits here for the chunk; the finals of the
                # sessions retired at the last boundary were gathered from
                # the same chunk, so they come down in the same wait
                states_np = (
                    np.asarray(plan.states_block)  # (K, N, E)
                    if any(sess.collect_states for sess, _, _ in plan.entries)
                    else None
                )
                outs_np = (
                    np.asarray(plan.outs_block) if plan.outs_block is not None else None
                )
                preds_np = (
                    np.asarray(plan.preds_block)  # (K, E, n_out)
                    if plan.any_learn and plan.preds_block is not None
                    else None
                )
                finals = self._fetch_awaiting()
            if self.nan_guard:
                with _span("engine.nan_guard"):
                    self._scan_for_nonfinite(plan, states_np, outs_np, preds_np)
            # .copy(): a bare slice is a VIEW pinning the whole (K, N, E) block
            # for the session's lifetime — a long-running collector would retain
            # every chunk block it ever touched instead of its own lane.
            # Columns beyond the session's own n_out are padding lanes — sliced
            # off here so accumulators stay at session width.
            for sess, slot, n in plan.entries:
                if n == 0:  # idle open stream — nothing served this chunk
                    continue
                if sess._error is not None:
                    # quarantined: keep the clean prefix, drop the poisoned rows
                    continue
                if sess.collect_states:
                    sess._states.append(states_np[:n, :, slot].copy())  # (n, N)
                if sess.readout is not None:
                    sess._outs.append(outs_np[:n, slot, : sess._n_out].copy())
                if preds_np is not None and sess.targets is not None:
                    sess._preds.append(preds_np[:n, slot, : sess._n_out].copy())
        # sessions retired at the last boundary: their final chunk is now
        # harvested, so their results are complete
        self._finalize_awaiting(finals)

    def _fetch_awaiting(self) -> Optional[Tuple[np.ndarray, Optional[np.ndarray]]]:
        """Host copies of the final states (and trained weights) of the
        sessions retired at the previous boundary; None with none."""
        if self._awaiting is None:
            return None
        _, finals, w_finals = self._awaiting
        return (
            np.asarray(finals),  # (k, N, 3)
            np.asarray(w_finals) if w_finals is not None else None,
        )

    def _finalize_awaiting(
        self, fetched: Optional[Tuple[np.ndarray, Optional[np.ndarray]]] = None
    ) -> None:
        """Record results for sessions retired at the previous boundary
        (their final states/weights arrive as one bulk transfer, handed out
        as copied rows). `fetched` is `_fetch_awaiting()`'s result where the
        caller already holds it. Safe to call with nothing awaiting."""
        with _span("engine.finalize"):
            if self._awaiting is None:
                return
            if fetched is None:
                with _span("engine.fetch"):
                    fetched = self._fetch_awaiting()
            finals_np, w_np = fetched
            for i, (slot, sess) in enumerate(self._awaiting[0]):
                # .copy(): a row view would pin the whole boundary's finals
                # block per retained result
                self._record_result(
                    sess,
                    slot,
                    finals_np[i].copy(),
                    learned_w=(
                        w_np[i].copy()
                        if w_np is not None and sess.targets is not None
                        else None
                    ),
                )
            self._awaiting = None

    def step_chunk(self) -> bool:
        """Advance the pipeline by one chunk. Returns False when drained.

        One call = assemble + launch the next K-tick chunk, then harvest
        the PREVIOUSLY launched one (which the device finished while the
        host assembled). The final call launches nothing and harvests the
        trailing chunk. Callers driving this directly (benchmarks, external
        event loops) must keep calling until it returns False — or hand
        control back to `run()` — so no launched chunk is left unharvested;
        don't interleave with per-tick `step()` while a chunk is in flight.
        """
        with _span("engine.step_chunk"):
            t0 = time.perf_counter()
            plan = self._assemble_chunk()
            if plan is not None:
                self._launch_chunk(plan)
            if self._pending is not None:
                self._harvest_chunk(self._pending)
            else:
                # nothing in flight, but the boundary may still have snapshot
                # finals to hand out (all-idle open streams after a finisher)
                self._finalize_awaiting()
            self._pending = plan
            if plan is not None:
                self._chunk_times.append(time.perf_counter() - t0)
            progress = plan is not None
            # advance mixed-spec tenants in lockstep; their finished sessions
            # surface through OUR results map so callers have one drain point
            for sub in self._subengines.values():
                if sub.step_chunk():
                    progress = True
                if sub.results:
                    self.results.update(sub.pop_results())
            if self._subengines and self.max_retained is not None:
                while len(self.results) > self.max_retained:
                    self.results.pop(next(iter(self.results)))
            return progress

    def run(
        self, sessions: Optional[List[StreamSession]] = None
    ) -> Dict[int, SessionResult]:
        """Serve sessions to completion; returns sid -> SessionResult.

        Double-buffered chunk pipeline: assemble chunk C+1 and harvest
        chunk C on the host while the device executes chunk C+1's
        predecessor — admissions, retirements, and autoscaling all happen
        at chunk boundaries. With chunk_ticks == 1 this degenerates to
        per-tick serving with bulk harvest (still one transfer per tick,
        never per slot)."""
        for s in sessions or []:
            self.submit(s)
        while self.step_chunk():
            pass
        return self.results

    # -- fleet lifecycle: push streams, checkpoint/migration, stats --------

    def _find_session(self, sid: int) -> Tuple[Optional[int], StreamSession]:
        """Locate a live session by sid: (slot, session) if resident,
        (None, session) if still queued. Raises KeyError when unknown
        (finished sessions live in `results`, not here)."""
        for slot, sess in self.scheduler.running.items():
            if sess.sid == sid:
                return slot, sess
        for sess in self.scheduler.queue:
            if sess.sid == sid:
                return None, sess
        raise KeyError(f"no live session with sid {sid}")

    def _owner(self, sid: int) -> "ReservoirEngine":
        """The engine actually holding sid: self, or the sub-engine its
        spec routed it to. Raises KeyError when no engine knows it."""
        try:
            self._find_session(sid)
            return self
        except KeyError:
            pass
        for sub in self._subengines.values():
            try:
                sub._find_session(sid)
                return sub
            except KeyError:
                continue
        raise KeyError(f"no live session with sid {sid}")

    def append_ticks(
        self,
        sid: int,
        u: np.ndarray,
        targets: Optional[np.ndarray] = None,
    ) -> None:
        """Feed more input rows to an OPEN (push) stream.

        The rows join the session's stream at its tail; an idle lane picks
        them up at the next chunk boundary. Learning sessions must push
        matching target rows (and inference sessions must not)."""
        eng = self._owner(sid)
        if eng is not self:
            return eng.append_ticks(sid, u, targets)
        _, sess = self._find_session(sid)
        if not sess.open:
            raise ValueError(
                f"session {sid} is not an open stream — submit it with "
                f"open=True to push ticks"
            )
        u = coerce_input_series(u, self.store.n_in, self.store.dtype, xp=np)
        if sess.targets is not None:
            if targets is None:
                raise ValueError(
                    f"session {sid} is a learning stream — push target rows "
                    f"alongside the inputs"
                )
            t = np.asarray(targets, dtype=self.store.dtype)
            if t.ndim == 1:
                t = t[:, None]
            if t.shape != (u.shape[0], sess._n_out):
                raise ValueError(
                    f"session {sid}: pushed targets shape "
                    f"{tuple(np.shape(targets))} != ({u.shape[0]}, "
                    f"{sess._n_out})"
                )
            sess.targets = np.concatenate(
                [sess.targets, self._pad_cols(t, "targets", sid)]
            )
        elif targets is not None:
            raise ValueError(
                f"session {sid} is inference-only; it cannot take targets"
            )
        sess.u_seq = np.concatenate([sess.u_seq, u])

    def close_session(self, sid: int) -> None:
        """End an open stream: once its pushed input is exhausted the
        session finishes like any closed-stream session (result in
        `results`/`pop_results`)."""
        _, sess = self._owner(sid)._find_session(sid)
        sess.open = False

    def quiesce(self) -> None:
        """Drain the pipeline without launching new work: harvest the
        in-flight chunk, retire + record any finishers. Afterwards the
        SlotStore columns are current for every resident session — the
        precondition for `checkpoint_session`. Serving resumes with the
        next `step_chunk()`/`run()`."""
        if self._pending is not None:
            self._harvest_chunk(self._pending)
            self._pending = None
        self._retire_finishers()
        self._finalize_awaiting()
        for sub in self._subengines.values():
            sub.quiesce()
            if sub.results:
                self.results.update(sub.pop_results())

    def _freeze_session(
        self, slot: Optional[int], sess: StreamSession, detach: bool
    ) -> SessionCheckpoint:
        """Build a host-side SessionCheckpoint of one live session (the
        pipeline must be quiesced: slot columns current, nothing in
        flight). detach=True removes the session from this engine (the
        migration path); detach=False leaves it serving untouched — every
        array that could later mutate is copied or replaced-on-write
        (u_seq/targets only ever grow by reassignment in append_ticks;
        prefix blocks concatenate into fresh arrays), so a non-destructive
        snapshot never aliases live engine state."""
        q = sess._n_out
        learning = self.learn is not None and sess.targets is not None
        if slot is None:
            # still queued: nothing on device yet
            if detach:
                self.scheduler.remove_queued(sess)
            m = None if sess.m0 is None else np.asarray(sess.m0)
            P = Wl = None
        else:
            m = np.asarray(self.store.state_column(slot))
            if learning:
                # LMS learners have no inverse-Gram: Wl IS the whole
                # resumable learn state (SessionCheckpoint.P stays None)
                P = (
                    np.asarray(self.store.learn_P_columns([slot])[0])
                    if self.learn == "rls"
                    else None
                )
                # padding columns stay zero for the session's whole life
                # (zero targets + zero init), so slicing to q is exact
                Wl = np.asarray(self.store.learn_w_columns([slot])[0])[:, :q]
            else:
                P = Wl = None
            if detach:
                self.scheduler.detach(slot)
                self.store.retire(slot)

        def cat(blocks):
            if not blocks:
                return None
            return np.concatenate([np.atleast_2d(np.asarray(b)) for b in blocks])

        ckpt = SessionCheckpoint(
            sid=sess.sid,
            u_seq=np.asarray(sess.u_seq),
            t=sess._t,
            m=m,
            params=sess.params,
            readout_w=(
                None
                if sess.readout is None
                else np.asarray(sess.readout.w_out)
            ),
            readout_washout=(
                0 if sess.readout is None else sess.readout.washout
            ),
            collect_states=sess.collect_states,
            targets=(
                None if sess.targets is None else sess.targets[:, :q].copy()
            ),
            learn_washout=sess.learn_washout,
            open=sess.open,
            n_out=q,
            states=cat(sess._states) if sess.collect_states else None,
            outs=cat(sess._outs) if sess.readout is not None else None,
            preds=cat(sess._preds) if learning else None,
            P=P,
            Wl=Wl,
            spec=_spec_host(sess.spec),
        )
        if detach:
            sess._states = []
            sess._outs = []
            sess._preds = []
        return ckpt

    def checkpoint_session(self, sid: int) -> SessionCheckpoint:
        """Freeze a live session into a host-side SessionCheckpoint and
        remove it from this engine (detach — not a retirement; no
        SessionResult is recorded here). The checkpoint restores into any
        engine compiled for the same reservoir spec via
        `restore_session`, resuming bit-identically on the scan backend.
        Quiesces the pipeline first."""
        self.quiesce()
        eng = self._owner(sid)
        if eng is not self:
            return eng.checkpoint_session(sid)
        slot, sess = self._find_session(sid)
        return self._freeze_session(slot, sess, detach=True)

    def snapshot_sessions(self) -> List[SessionCheckpoint]:
        """Non-destructive checkpoints of EVERY live session (running and
        queued, sub-engines included) — the periodic auto-checkpoint the
        fleet failover layer rides: the router calls this every
        `checkpoint_every` pump rounds and keeps the checkpoints PARENT
        side, so they survive the replica process dying. Quiesces the
        pipeline first; every session keeps serving afterwards, and its
        stream is bit-identical to one that was never snapshotted
        (tests/test_fleet_faults.py pins this). Sessions already flagged
        by the nan guard are excluded — failover must not resurrect a
        poisoned stream."""
        self.quiesce()
        out: List[SessionCheckpoint] = []
        for slot, sess in list(self.scheduler.running.items()):
            if sess._error is None:
                out.append(self._freeze_session(slot, sess, detach=False))
        for sess in list(self.scheduler.queue):
            if sess._error is None:
                out.append(self._freeze_session(None, sess, detach=False))
        for sub in self._subengines.values():
            out.extend(sub.snapshot_sessions())
        return out

    def restore_session(self, ckpt: SessionCheckpoint) -> StreamSession:
        """Resume a checkpointed session on THIS engine: re-submit it with
        the frozen magnetization as m0 and the in-flight RLS learner
        injected into the destination slot's P/Wl columns, then seed the
        already-served prefix so the final SessionResult covers the whole
        stream. The resumed stream is bit-identical to one that never
        migrated (scan backend; tests/test_fleet.py)."""
        readout = None
        if ckpt.readout_w is not None:
            readout = Readout(
                w_out=jnp.asarray(ckpt.readout_w),
                washout=ckpt.readout_washout,
            )
        sess = StreamSession(
            sid=ckpt.sid,
            u_seq=ckpt.u_seq,
            params=ckpt.params,
            readout=readout,
            m0=None if ckpt.m is None else jnp.asarray(ckpt.m),
            collect_states=ckpt.collect_states,
            targets=ckpt.targets,
            learn_washout=ckpt.learn_washout,
            open=ckpt.open,
            learn_w0=ckpt.Wl,
            learn_P0=ckpt.P,
            spec=ckpt.spec,
        )
        # submit() re-routes a spec-carrying session (possibly onto a
        # sub-engine of THIS engine) and validates/pads against whichever
        # store it lands in
        self.submit(sess)
        if ckpt.t:
            sess._t = ckpt.t
            sess._states = [] if ckpt.states is None else [ckpt.states]
            sess._outs = [] if ckpt.outs is None else [ckpt.outs]
            sess._preds = [] if ckpt.preds is None else [ckpt.preds]
            sess._restored = True  # _admit_pending keeps the seeded prefix
        return sess

    def stats(self) -> EngineStats:
        """Load/latency snapshot for the fleet planner and router — plain
        scalars only (pickles across the replica transport)."""
        sched = self.scheduler
        timed = sorted(self._chunk_times)
        median = timed[len(timed) // 2] if timed else None
        return EngineStats(
            n=self.res.n,
            num_slots=self.num_slots,
            active=len(sched.running),
            queued=len(sched.queue),
            backend=self.backend,
            precision=self.precision,
            learn=self.learn,
            chunk_ticks=self.chunk_ticks,
            ticks=sched.stats.ticks,
            session_ticks=sched.stats.session_ticks,
            occupancy=sched.occupancy(),
            queue_depth=sched.queue_depth(),
            mean_queue_wait=sched.mean_queue_wait(),
            grows=sched.stats.grows,
            shrinks=sched.stats.shrinks,
            detached=sched.stats.detached,
            cold_rescales=sched.stats.cold_rescales,
            warm_rescales=sched.stats.warm_rescales,
            rescale_stall_s=sched.stats.rescale_stall_s,
            chunk_median_s=median,
            chunks_timed=len(timed),
            launches_overlapped=self._launches_overlapped,
            ticks_per_sec=(
                None
                if not median
                else self.num_slots * self.chunk_ticks / median
            ),
            sub_engines=len(self._subengines),
            quarantined_lanes=(
                sched.stats.quarantined_lanes
                + sum(
                    s.scheduler.stats.quarantined_lanes
                    for s in self._subengines.values()
                )
            ),
        )
