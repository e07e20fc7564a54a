"""ExecPlan: HOW to execute a SimSpec — backend, padding, batching, sharding.

Every execution decision that used to be scattered across
`core/reservoir.py`, `core/ensemble.py`, `kernels/ops.py`, and
`serve/reservoir.py` is declared here and resolved exactly once, in
`repro.api.compile_plan`:

  impl       "auto" consults the measured-latency dispatch table
             (in-process + the persisted per-platform JSON from
             kernels/dispatch_table.py), then the platform gate / VMEM
             heuristic — `kernels.ops.choose_impl`. Explicit values:
             "scan" (core (E, N, 3) layout, bit-identical to the legacy
             `drive` math), "ref" (planes-layout jnp oracle), "fused" /
             "tiled" (Pallas TPU kernels), "chunk" (chunk-resident fused
             RK4: the K-tick x hold_steps x 4-stage loop runs as one
             device-side region — a Pallas kernel on TPU that keeps the
             state planes VMEM-resident and streams W once per chunk, a
             single fused XLA region elsewhere).
  ensemble   E: how many reservoir lanes run per dispatch (1 = solo).
  block_n/e  MXU padding granules for the Pallas kernels.
  n_inner    fused-kernel inner steps (None = one hold window per launch).
  mesh       a jax Mesh makes the plan SHARDED: E spans `ensemble_axes`,
             N spans `model_axis`, with PartitionSpecs from
             `distributed.sharding.reservoir_specs`. The sharded bodies
             run on its devices and axis names with Auto axis types
             (`distributed.sharding.auto_axes`), whatever types it has.
  precision  numerical policy for the compute-bound GEMMs (the paper's
             large-N regime is dominated by the dense N x N coupling GEMM
             re-evaluated 4 x hold_steps times per tick):
               None / "highest"  bit-exact default: every op runs in the
                     spec dtype, results identical to plans that predate
                     the field.
               "bf16_coupling"   the coupling GEMM (W^cp @ m^x) consumes
                     bf16 operands and accumulates in f32 (MXU-native on
                     TPU; on sharded plans this also halves the all-gather
                     wire bytes, subsuming gather_dtype=bf16).
               "mixed"           "bf16_coupling" plus the input-field GEMM
                     (W^in u) in bf16. State carry, all elementwise LLG
                     math, and the RK4 stage accumulation stay f32 — only
                     the GEMMs are reduced, so the NARMA-10 NMSE guardrail
                     (within 10% of f32, pinned by tests) holds.
             Reduced precision applies to the planes impls
             (ref/fused/tiled/chunk) and sharded plans; impl="scan" is the
             repo's bit-exact oracle and refuses it. The readout-learning
             recursion (kernels/rls.py) deliberately stays f32 — P's
             conditioning is the one place bf16 noise compounds.
  gather_dtype  reduced-precision coupling path for sharded plans (bf16
             wire + matmul; see core/ensemble.py §Perf C notes). Subsumed
             by `precision` — an explicit gather_dtype still wins, but new
             code should say precision="bf16_coupling" instead.
  chunk_ticks  K: how many input ticks one serving dispatch covers.
             K > 1 turns `CompiledSim.tick_chunk` into a lax.scan over K
             ticks whose per-tick states stay in a device-side buffer and
             reach the host as ONE transfer per chunk — the pipelined
             serving path (`serve.reservoir.ReservoirEngine.run`) overlaps
             host u-block assembly with device execution of the previous
             chunk. K = 1 keeps per-tick serving semantics.
  learn      online readout learning fused into `tick_chunk`'s per-tick
             scan body: "rls" runs one masked batched recursive-least-
             squares update (kernels/rls.py) per tick — per-lane
             (S, S) = (N+1, N+1) inverse-Gram P and (S, n_out) weight
             lanes ride the dispatch alongside the magnetization, zero
             extra host round-trips. "lms" runs normalized least mean
             squares instead: no P block at all, O(S) state and work per
             tick — approximate where RLS is exact, but the per-candidate
             cost the `repro.tune` search lanes want at large S. None
             (default) keeps tick_chunk inference-only (signature and
             results unchanged).
  aot        ahead-of-time compile: `compile_plan` immediately lowers and
             compiles the chunked serving hot path (`lower().compile()`,
             falling back to executing one masked zero chunk where AOT is
             not wired, e.g. sharded plans) instead of deferring XLA work
             to the first dispatch. Pair with `compilation_cache_dir` to
             populate the on-disk cache at spin-up.
  compilation_cache_dir  turn on JAX's persistent compilation cache for
             the process: the XLA executables this plan compiles are
             spilled to (and read back from) disk, so cold-start survives
             process restarts. The directory is resolved by
             api/cache.resolve_cache_dir: JAX_COMPILATION_CACHE_DIR when
             set (this field is then ignored, with a warning if it
             differs), else this field. First resolved directory wins for
             the process; launcher flag `--compilation-cache-dir` threads
             it through serve + fleet.
             Neither field changes numerics or the compiled executable —
             both are excluded from the PlanCache key.
  learn_lam  RLS forgetting factor in (0, 1]. 1.0 (default) weights all
             history equally and converges to batch ridge regression;
             < 1 exponentially forgets, tracking non-stationary targets.
             RLS-only (LMS has no history weighting to forget).
  learn_reg  RLS regularization: P initializes to I / learn_reg, the
             exact analogue of `fit_ridge`'s `reg`. RLS-only.
  learn_mu   LMS step size in (0, 2) — the normalized-LMS stability
             range, input-scale-free because the update divides by
             ||x||^2. LMS-only.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import numpy as np

from jax.sharding import Mesh

PLAN_IMPLS = ("auto", "scan", "ref", "fused", "tiled", "chunk")
PLAN_LEARN = (None, "rls", "lms")
PLAN_PRECISIONS = (None, "highest", "bf16_coupling", "mixed")

# Which impls can execute which physics family (SimSpec.topology). The
# coupled-array Pallas kernels (fused/tiled) bake the N x N coupling GEMM
# into every RK stage; the time-multiplexed delay line has no such stage
# GEMM (feedback is once per tick), so those impls cannot express it and
# compile_plan refuses the pairing up front ("auto" resolves around it).
# Mesh plans shard the coupled array's N axis; neither family decomposes
# that way (the delay line is sequential in N, the transient window is a
# readout detail), so families are unsharded — scale them across ensemble
# lanes / engine replicas instead.
FAMILY_IMPLS = {
    "coupled_array": PLAN_IMPLS,
    "time_multiplexed": ("auto", "scan", "ref", "chunk"),
    "array_transient": ("auto", "scan", "ref", "fused", "tiled", "chunk"),
}


def check_plan_supports_topology(plan: "ExecPlan", topology: str) -> None:
    """Refuse plan/physics-family pairings that have no executable mapping.

    Called by compile_plan after spec validation; kept here so the support
    table lives next to PLAN_IMPLS and stays in sync with new impls.
    """
    allowed = FAMILY_IMPLS.get(topology)
    if allowed is None:
        raise ValueError(
            f"unknown topology {topology!r}; expected one of "
            f"{tuple(FAMILY_IMPLS)}"
        )
    if topology == "coupled_array":
        return
    if plan.mesh is not None:
        raise ValueError(
            f"mesh plans shard the coupled array; topology {topology!r} is "
            "unsharded — scale it across ensemble lanes or engine replicas"
        )
    if plan.impl not in allowed:
        raise ValueError(
            f"impl {plan.impl!r} cannot execute topology {topology!r}; "
            f"supported impls: {allowed}"
        )


# ExecPlan knobs `repro.tune` may search over. All are STRUCTURAL: each is
# either a static argument of the jit'd learn workers (learn_lam / learn_mu
# specialize the compiled update) or folded into per-lane init state once at
# admit (learn_reg -> P0) — so candidates with different values group into
# separate compiled engines, like SimSpec.STRUCT_TUNABLE.
PLAN_TUNABLE = ("learn_lam", "learn_reg", "learn_mu")


@dataclasses.dataclass(frozen=True)
class ExecPlan:
    impl: str = "auto"
    ensemble: int = 1
    block_n: Optional[int] = None  # None = kernels' LANE default
    block_e: Optional[int] = None
    n_inner: Optional[int] = None  # None = full hold window per kernel launch
    mesh: Optional[Mesh] = None
    ensemble_axes: Sequence[str] = ("data",)
    model_axis: Optional[str] = "model"
    gather_dtype: Optional[object] = None
    precision: Optional[str] = None  # None/"highest" = bit-exact
    chunk_ticks: int = 1
    learn: Optional[str] = None  # None = inference-only; "rls"/"lms" = online
    learn_lam: float = 1.0  # RLS forgetting factor, (0, 1]
    learn_reg: float = 1e-6  # RLS regularization: P0 = I / learn_reg
    learn_mu: float = 0.5  # NLMS step size, (0, 2)
    interpret: bool = False
    measure: bool = False  # time impl candidates at compile, pin the winner
    aot: bool = False  # lower().compile() the hot path at compile_plan time
    compilation_cache_dir: Optional[str] = None  # JAX persistent cache dir

    def __post_init__(self):
        if self.impl not in PLAN_IMPLS:
            raise ValueError(f"impl must be one of {PLAN_IMPLS}; got {self.impl!r}")
        if self.ensemble < 1:
            raise ValueError(f"ensemble must be >= 1; got {self.ensemble}")
        if self.mesh is not None and self.impl not in ("auto", "scan"):
            raise ValueError(
                "sharded plans integrate in the core layout via shard_map; "
                f"impl must be 'auto' or 'scan' when mesh is set, got {self.impl!r}"
            )
        if isinstance(self.chunk_ticks, bool) or not isinstance(self.chunk_ticks, int):
            raise ValueError(
                f"chunk_ticks must be an int >= 1; got {self.chunk_ticks!r}"
            )
        if self.chunk_ticks < 1:
            raise ValueError(
                f"chunk_ticks must be >= 1; got {self.chunk_ticks}"
            )
        if self.gather_dtype is not None:
            try:
                np.dtype(self.gather_dtype)
            except TypeError:
                raise ValueError(
                    f"gather_dtype must be a dtype (e.g. jnp.bfloat16) or None; "
                    f"got {self.gather_dtype!r}"
                ) from None
        if self.precision not in PLAN_PRECISIONS:
            raise ValueError(
                f"precision must be one of {PLAN_PRECISIONS}; got "
                f"{self.precision!r}"
            )
        if self.reduced_precision and self.impl == "scan" and self.mesh is None:
            raise ValueError(
                "impl='scan' is the bit-exact oracle; reduced precision "
                f"({self.precision!r}) applies to the planes impls "
                "(ref/fused/tiled/chunk) and sharded plans — use "
                "impl='auto' or an explicit planes impl"
            )
        if self.learn not in PLAN_LEARN:
            raise ValueError(
                f"learn must be one of {PLAN_LEARN}; got {self.learn!r}"
            )
        if self.learn == "lms" and self.mesh is not None:
            raise ValueError(
                "learn='lms' is not wired through the sharded (mesh) serving "
                "path yet — its per-lane weight columns would need the "
                "lane-sharded P-free variant of api/sharded's learn plumbing; "
                "use learn='rls' on sharded plans"
            )
        if not isinstance(self.learn_lam, (int, float)) or isinstance(
            self.learn_lam, bool
        ) or not (0.0 < float(self.learn_lam) <= 1.0):
            raise ValueError(
                f"learn_lam (RLS forgetting factor) must be a float in "
                f"(0, 1]; got {self.learn_lam!r}"
            )
        if not isinstance(self.learn_reg, (int, float)) or isinstance(
            self.learn_reg, bool
        ) or not float(self.learn_reg) > 0.0:
            raise ValueError(
                f"learn_reg (RLS regularization; P0 = I / learn_reg) must be "
                f"> 0; got {self.learn_reg!r}"
            )
        if not isinstance(self.learn_mu, (int, float)) or isinstance(
            self.learn_mu, bool
        ) or not (0.0 < float(self.learn_mu) < 2.0):
            raise ValueError(
                f"learn_mu (NLMS step size) must be a float in (0, 2); got "
                f"{self.learn_mu!r}"
            )
        if self.compilation_cache_dir is not None and not isinstance(
            self.compilation_cache_dir, str
        ):
            raise ValueError(
                "compilation_cache_dir must be a directory path string or "
                f"None; got {self.compilation_cache_dir!r}"
            )

    def with_knobs(self, **knobs) -> "ExecPlan":
        """A new plan with named PLAN_TUNABLE knobs applied — the validated
        write path for parameter search (`repro.tune`). Unknown names raise
        with the valid list; values re-run the full __post_init__
        validation (dataclasses.replace)."""
        for name in knobs:
            if name not in PLAN_TUNABLE:
                raise ValueError(
                    f"unknown plan knob {name!r}; tunable plan knobs: "
                    f"{PLAN_TUNABLE}"
                )
        return dataclasses.replace(self, **knobs)

    @property
    def sharded(self) -> bool:
        return self.mesh is not None

    @property
    def effective_precision(self) -> Optional[str]:
        """The precision policy with the bit-exact aliases collapsed:
        returns None for both None and "highest"."""
        return None if self.precision == "highest" else self.precision

    @property
    def reduced_precision(self) -> bool:
        return self.effective_precision is not None

    @property
    def effective_gather_dtype(self):
        """The sharded coupling-path wire/matmul dtype after precision
        resolution: an explicit gather_dtype wins (backward compat);
        otherwise reduced-precision plans gather in bf16."""
        if self.gather_dtype is not None:
            return self.gather_dtype
        if self.reduced_precision:
            import jax.numpy as jnp

            return jnp.bfloat16
        return None
