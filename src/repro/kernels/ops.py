"""Public jit'd wrappers around the Pallas kernels.

Handles layout conversion ((E, N, 3) user layout <-> (3, N, E) kernel
layout), MXU-alignment padding, and implementation dispatch:

    impl="fused"  VMEM-resident whole-RK4(-multi-step) kernel (small/med N)
    impl="tiled"  per-stage row-tiled kernel (large N)
    impl="ref"    pure-jnp oracle (also the non-TPU production path)
    impl="chunk"  chunk-resident serving kernel: the K-tick x hold_steps x
                  4-stage loop as ONE device-side region (Pallas rk4_chunk
                  on TPU — W and state planes VMEM-resident per chunk; the
                  jnp chunk oracle elsewhere). Per-hold-window entry points
                  fall back to the ref math (a chunk of one window).
    impl="auto"   measured-latency table if populated; else fused while
                  W + state + stages fit the VMEM budget, else tiled
                  (on non-TPU backends: always ref — Pallas is unavailable)

Precision policies (ExecPlan.precision) resolve HERE into a single W-cast
hoisted outside the integration loops: "bf16_coupling"/"mixed" pass a bf16
W into the kernels/oracle, whose coupling dots consume the reduced
operands and accumulate in the state dtype. The dispatch table is keyed by
precision as well as shape — a winner measured at f32 says nothing about
the bf16-coupling ranking.

Serving extensions (repro/serve/reservoir.py rides on these):
  - `h_in`: an (N, E) input-drive x-field added to the coupling field inside
    the kernels, held constant over the integration window — one kernel
    invocation advances a whole hold window of a *driven* reservoir.
  - `lane_mask`: partial-batch masking over the ensemble axis. Lanes where
    the mask is False come back bit-identical to their input state, so idle
    serving slots stay frozen while active slots advance in the same batch.

Zero-padding correctness: padded W rows/cols are zero so padded oscillators
receive/contribute no coupling; padded h_in rows/lanes are zero; padded
ensemble lanes evolve garbage that is sliced away on exit; params rows are
broadcast into padded lanes so no division hits uninitialized memory
(denominators are 1 + lam*m.p >= 1-lam).
"""

from __future__ import annotations

import functools
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.constants import EXACT_MATMUL, STOParams
from repro.kernels import ref as kref
from repro.kernels import sto_step

LANE = sto_step.LANE


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _vmem_refusal_line(exc: Exception) -> Optional[str]:
    """The compiler's VMEM out-of-memory line in `exc`, if it is one."""
    msg = str(exc)
    if "RESOURCE_EXHAUSTED" not in msg:
        return None
    lines = [ln.strip() for ln in msg.splitlines() if "vmem" in ln.lower()]
    return lines[0][:400] if lines else None


@functools.lru_cache(maxsize=None)
def _kernel_refusal(impl, n, e, w_dtype, dtype, k_ticks, n_inner,
                    block_n, block_e, device) -> Optional[str]:
    """Compile the pallas_call `impl` runs at padded (n, e) for `device`."""
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(device)

    def s(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    dt = 1.0e-11  # a compile-time constant of the kernel; any value sizes it
    planes, w, pv, h = s((3, n, e)), s((n, n), w_dtype), s((kref.NP, e)), s((n, e))
    if impl == "fused":
        fn = lambda m, w, p, h: sto_step.rk4_fused(
            m, w, p, dt, n_inner=n_inner, block_e=block_e, h_in=h)
        args = (planes, w, pv, h)
    elif impl == "tiled":
        fn = lambda m, y, k, w, p, h: sto_step.field_tiled(
            m, y, k, w, p, 0.5 * dt, block_n=block_n, block_e=block_e, h_in=h)
        args = (planes, h, planes, w, pv, h)
    elif impl == "chunk":
        fn = lambda m, w, p, hb, mb: sto_step.rk4_chunk(
            m, w, p, dt, n_inner, hb, mb, block_e=block_e)
        args = (planes, w, pv, s((k_ticks, n, e)), s((k_ticks, e)))
    else:
        raise ValueError(f"{impl!r} is not a Pallas impl")
    try:
        jax.jit(fn).lower(*args).compile()
    except Exception as exc:  # only a VMEM refusal means "does not fit"
        refusal = _vmem_refusal_line(exc)
        if refusal is None:
            raise
        return refusal
    return None


def kernel_vmem_refusal(
    impl: str,
    n: int,
    e: int,
    *,
    itemsize: int = 4,
    precision: Optional[str] = None,
    k_ticks: int = 1,
    n_inner: int = 1,
    block_n: int = LANE,
    block_e: int = LANE,
) -> Optional[str]:
    """Why the TPU compiler refuses the `impl` kernel at (N, E), or None.

    The fit check behind impl="auto" and compile_plan. The kernels' scoped
    VMEM use depends on W, every double-buffered block, the stage
    temporaries and on how Mosaic schedules the RK4 loop (straight-line
    code for one inner step needs about twice what a loop of several
    does), so no closed form tracks it: the check compiles the exact
    pallas_call at the padded shape under sto_step.VMEM_LIMIT_BYTES and
    reports the compiler's own refusal, for the first JAX device. Memoized
    per shape and device. Off a TPU no Mosaic kernel is compiled and
    nothing is refused. Compile errors other than a VMEM refusal propagate.
    """
    device = jax.devices()[0]
    if device.platform != "tpu":
        return None
    dtype = jnp.dtype(jnp.float32 if itemsize == 4 else jnp.float64)
    w_dtype = _coupling_operand(
        jnp.zeros((), dtype), normalize_precision(precision)
    ).dtype
    return _kernel_refusal(
        impl, _round_up(n, block_n), _round_up(e, block_e), w_dtype, dtype,
        int(k_ticks), int(n_inner), int(block_n), int(block_e), device,
    )


def effective_n_inner(n_steps: int, n_inner: int) -> int:
    """The fused kernel's inner-step count: the largest divisor of n_steps
    not above n_inner."""
    n_inner = max(1, min(int(n_inner), int(n_steps)))
    while n_steps % n_inner != 0:
        n_inner -= 1
    return n_inner


# ---------------------------------------------------------------------------
# Measured-latency dispatch table
# ---------------------------------------------------------------------------

# (platform, N_padded, E_padded, itemsize, precision) -> impl name.
# Populated by measure_impl_latency(), register_impl_choice(), or the
# persisted per-platform JSON tables (kernels/dispatch_table.py, loaded
# lazily by choose_impl); consulted before falling back to the VMEM
# heuristic. itemsize is part of the key because a choice measured at f32
# says nothing about the f64 VMEM footprint / bandwidth at the same padded
# shape; precision is part of the key because the impl ranking shifts when
# the coupling GEMM goes bf16 (e.g. MXU-native on TPU, software-emulated
# on most CPUs).
_LATENCY_TABLE: Dict[Tuple[str, int, int, int, str], str] = {}

# Bumped on every register_impl_choice(): the api-layer PlanCache keys
# impl="auto" resolutions on this, so a cached auto plan is invalidated
# (and re-resolves) the moment a new measurement pins a different winner.
_DISPATCH_GEN = 0


def dispatch_generation() -> int:
    """Monotonic version of the in-process dispatch table state."""
    return _DISPATCH_GEN

# The bit-exact default's tag in dispatch keys (ExecPlan.precision None
# and "highest" collapse to this).
PRECISION_DEFAULT = "highest"


def normalize_precision(precision: Optional[str]) -> str:
    """Collapse the ExecPlan.precision aliases to a dispatch-key tag."""
    return PRECISION_DEFAULT if precision in (None, PRECISION_DEFAULT) else precision


def register_impl_choice(
    n: int,
    e: int,
    impl: str,
    platform: Optional[str] = None,
    itemsize: int = 4,
    precision: Optional[str] = None,
):
    """Pin the dispatch choice for a padded (N, E, itemsize, precision)
    shape on a platform."""
    global _DISPATCH_GEN
    platform = platform or jax.default_backend()
    _DISPATCH_GEN += 1
    _LATENCY_TABLE[
        (
            platform,
            _round_up(n, LANE),
            _round_up(e, LANE),
            itemsize,
            normalize_precision(precision),
        )
    ] = impl


def latency_table() -> Dict[Tuple[str, int, int, int, str], str]:
    return dict(_LATENCY_TABLE)


def choose_impl(
    n: int,
    e: int,
    itemsize: int = 4,
    platform: Optional[str] = None,
    precision: Optional[str] = None,
    *,
    k_ticks: int = 1,
    hold_steps: int = 8,
    n_inner: int = 8,
    block_n: int = LANE,
    block_e: int = LANE,
) -> str:
    """Resolve impl="auto" for a given (N, E, precision) problem shape.

    Priority: measured-latency table (in-process measurements, then the
    committed per-platform JSON from kernels/dispatch_table.py) — first at
    the exact precision key, then at the bit-exact default key for the
    same shape (the best f32 impl is the best prior for a reduced-precision
    run that was never measured) > platform gate (Pallas kernels only
    compile on TPU; everything else integrates through the jnp oracle,
    which XLA fuses well on CPU/GPU) > the VMEM fit check: fused, else
    tiled, else the XLA oracle. On a TPU every Pallas choice, a table's
    included, must pass `kernel_vmem_refusal` at the caller's chunk length
    (k_ticks), hold window, fused inner-step count and blocks, so "auto"
    never names a kernel the compiler refuses.
    """
    from repro.kernels import dispatch_table

    platform = platform or jax.default_backend()
    dispatch_table.ensure_loaded(platform)
    prec = normalize_precision(precision)

    def fits(impl):
        if platform != "tpu" or impl not in ("fused", "tiled", "chunk"):
            return True
        return kernel_vmem_refusal(
            impl, n, e, itemsize=itemsize, precision=precision,
            k_ticks=k_ticks,
            n_inner=(hold_steps if impl == "chunk"
                     else effective_n_inner(hold_steps, n_inner)),
            block_n=block_n, block_e=block_e,
        ) is None

    shape_key = (platform, _round_up(n, LANE), _round_up(e, LANE), itemsize)
    for key in (shape_key + (prec,), shape_key + (PRECISION_DEFAULT,)):
        if key in _LATENCY_TABLE and fits(_LATENCY_TABLE[key]):
            return _LATENCY_TABLE[key]
    if platform != "tpu":
        return "ref"
    for impl in ("fused", "tiled"):
        if fits(impl):
            return impl
    return "ref"


def measure_impl_latency(
    n: int,
    e: int,
    dt: float = 1.0e-11,
    n_steps: int = 8,
    candidates: Optional[Tuple[str, ...]] = None,
    dtype=jnp.float32,
    reps: int = 3,
    register: bool = True,
    precision: Optional[str] = None,
    chunk_ticks: int = 4,
) -> Dict[str, object]:
    """Time each candidate impl at (N, E, precision) and record the winner.

    Each candidate runs the CHUNKED serving shape of the problem —
    chunk_ticks hold windows of n_steps each (the serving hot path the
    dispatch table mostly arbitrates) — so the measurement captures what
    chunk residency is worth on TPU, where impl="chunk" is the Pallas
    rk4_chunk kernel (W read once per chunk) while fused/tiled re-enter
    per tick. Off-TPU, "chunk" lowers to the SAME fused XLA region as
    "ref" (see _tick_chunk_planes_jit), so it is excluded from the default
    candidates there — timing two names for one computation would register
    a coin-flip winner; pass it via `candidates` explicitly if you must.

    Returns {impl: seconds per chunk} for the candidates that ran. On a
    TPU, a Pallas candidate the VMEM fit check refuses at this shape is
    left out up front and listed under "excluded" (impl -> the compiler's
    refusal); an admitted candidate that then fails raises, because a
    kernel the chip should run and does not is a fault, not a slower
    choice. Off a TPU a failing candidate is listed under "failed" and
    raised as a RuntimeWarning instead (fused/tiled need the TPU compiler).
    With register=True the fastest candidate that ran is written into the
    dispatch table so subsequent impl="auto" calls at this padded (shape,
    precision) use the measured choice.
    """
    on_tpu = jax.default_backend() == "tpu"
    if candidates is None:
        candidates = ("fused", "tiled", "chunk", "ref") if on_tpu else ("ref",)
    from repro.core import constants, coupling

    w = jnp.asarray(coupling.make_coupling_matrix(n, seed=0), dtype)
    m0 = to_planes(
        jnp.broadcast_to(constants.initial_magnetization(n, dtype), (e, n, 3))
    )
    pv = kref.pack_params(constants.default_params(dtype), e, dtype)
    h_block = jnp.zeros((chunk_ticks, n, e), dtype)
    mask_block = jnp.ones((chunk_ticks, e), dtype=bool)
    timings: Dict[str, object] = {}
    failed: Dict[str, str] = {}
    excluded: Dict[str, str] = {}
    itemsize = jnp.dtype(dtype).itemsize
    for impl in candidates:
        if on_tpu and impl in ("fused", "tiled", "chunk"):
            refusal = kernel_vmem_refusal(
                impl, n, e, itemsize=itemsize, precision=precision,
                k_ticks=chunk_ticks if impl == "chunk" else 1,
                n_inner=n_steps if impl == "chunk" else effective_n_inner(n_steps, 8),
            )
            if refusal is not None:
                excluded[impl] = refusal
                continue
        fn = lambda: sto_rk4_tick_chunk_planes(
            m0, w, pv, float(dt), n_steps, h_block, mask_block,
            impl=impl, precision=precision,
        )[0]
        try:
            jax.block_until_ready(fn())  # compile + warm
        except Exception as exc:
            if on_tpu:
                raise RuntimeError(
                    f"measure_impl_latency({n}, {e}): impl {impl!r} passed "
                    "the VMEM fit check but failed to run"
                ) from exc
            failed[impl] = f"{type(exc).__name__}: {exc}"
            continue
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            times.append(time.perf_counter() - t0)
        timings[impl] = sorted(times)[len(times) // 2]
    if excluded:
        timings["excluded"] = excluded
    if failed:
        import warnings

        timings["failed"] = failed
        warnings.warn(
            f"measure_impl_latency({n}, {e}): candidate impl(s) failed and "
            f"were excluded from dispatch: "
            + ", ".join(f"{k} ({v})" for k, v in failed.items()),
            RuntimeWarning,
            stacklevel=2,
        )
    successes = {k: v for k, v in timings.items() if isinstance(v, float)}
    if register and successes:
        register_impl_choice(
            n, e, min(successes, key=successes.get),
            itemsize=jnp.dtype(dtype).itemsize,
            precision=precision,
        )
    return timings


# ---------------------------------------------------------------------------
# Layout conversion + padding
# ---------------------------------------------------------------------------


def to_planes(m_user: jnp.ndarray) -> jnp.ndarray:
    """(..., N, 3) -> (3, N, E) kernel layout (E = flattened batch, >=1)."""
    if m_user.ndim == 2:
        m_user = m_user[None]
    e = 1
    for s in m_user.shape[:-2]:
        e *= int(s)
    n = m_user.shape[-2]
    flat = m_user.reshape(e, n, 3)
    return jnp.transpose(flat, (2, 1, 0))


def from_planes(m_planes: jnp.ndarray, batch_shape) -> jnp.ndarray:
    """(3, N, E) -> (*batch_shape, N, 3)."""
    e = m_planes.shape[-1]
    out = jnp.transpose(m_planes, (2, 1, 0))  # (E, N, 3)
    return out.reshape(*batch_shape, m_planes.shape[1], 3)


def _pad_planes(m, w, params, h_in, block_n, block_e):
    _, n, e = m.shape
    n_p = _round_up(max(n, 1), block_n)
    e_p = _round_up(max(e, 1), block_e)
    if n_p != n or e_p != e:
        m = jnp.pad(m, ((0, 0), (0, n_p - n), (0, e_p - e)))
        w = jnp.pad(w, ((0, n_p - n), (0, n_p - n)))
        if h_in is not None:
            h_in = jnp.pad(h_in, ((0, n_p - n), (0, e_p - e)))
        # broadcast params into padded lanes (edge mode keeps denominators sane)
        params = jnp.pad(params, ((0, 0), (0, e_p - e)), mode="edge")
    return m, w, params, h_in, n, e


# ---------------------------------------------------------------------------
# Integration entry points
# ---------------------------------------------------------------------------


def sto_rk4_integrate_planes(
    m0: jnp.ndarray,  # (3, N, E) kernel layout
    w_cp: jnp.ndarray,  # (N, N)
    params_vec: jnp.ndarray,  # (NP, E) packed (kernels/ref.pack_params)
    dt: float,
    n_steps: int,
    h_in: Optional[jnp.ndarray] = None,  # (N, E) input-drive x-field
    lane_mask: Optional[jnp.ndarray] = None,  # (E,) bool; False lanes frozen
    impl: str = "auto",
    n_inner: int = 8,
    block_n: int = LANE,
    block_e: int = LANE,
    interpret: bool = False,
    precision: Optional[str] = None,
) -> jnp.ndarray:
    """Integrate n_steps of (optionally driven) coupled-STO RK4 in kernel
    layout. Returns the final (3, N, E) state.

    This is the serving engine's hot path: one call advances every ensemble
    lane (= serving slot) by a full hold window. n_steps must be divisible by
    n_inner for the fused path (auto-adjusted otherwise).

    impl="auto" is resolved HERE, outside the jit, so dispatch-table updates
    (measure_impl_latency / register_impl_choice) take effect on the next
    call — the resolved impl is the jit cache key, never the string "auto".
    """
    _, n, e = m0.shape
    if impl == "auto":
        impl = choose_impl(
            n, e, m0.dtype.itemsize, precision=precision, hold_steps=n_steps,
            n_inner=n_inner, block_n=block_n, block_e=block_e,
        )
    return _integrate_planes_jit(
        m0, w_cp, params_vec, h_in, lane_mask,
        dt=dt, n_steps=n_steps, impl=impl, n_inner=n_inner,
        block_n=block_n, block_e=block_e, interpret=interpret,
        precision=normalize_precision(precision),
    )


def input_field_einsum(eq: str, w_in, u, precision) -> jnp.ndarray:
    """The input-field GEMM under the precision policy — ONE home for it.

    "mixed" (ExecPlan.precision) runs W^in u on bf16 operands accumulating
    in the input dtype; every other policy traces the exact einsum the
    callers have always used. Callers (api/compiled._input_field,
    api/sharded._input_field_local) own their layout/equation strings and
    their a_in scaling op order — only the reduction policy lives here, so
    a future policy (e.g. fp8) lands in one place for planes AND sharded
    plans.
    """
    if precision == "mixed":
        return jnp.einsum(
            eq, w_in.astype(jnp.bfloat16), u.astype(jnp.bfloat16),
            preferred_element_type=u.dtype,
        )
    return jnp.einsum(eq, w_in, u, precision=EXACT_MATMUL)


def _coupling_operand(w: jnp.ndarray, precision: str) -> jnp.ndarray:
    """Resolve the precision policy into the W operand the kernels consume.

    The cast happens ONCE, outside the integration loops; the kernels and
    the jnp oracle detect the reduced dtype and accumulate the coupling dot
    in the state dtype. "mixed" adds the input-field GEMM on top of
    "bf16_coupling" — that GEMM lives at the API layer (repro/api), so here
    both map to a bf16 W.
    """
    if precision in ("bf16_coupling", "mixed"):
        return w.astype(jnp.bfloat16)
    return w


@functools.partial(
    jax.jit,
    static_argnames=("dt", "n_steps", "impl", "n_inner", "block_n", "block_e", "interpret", "precision"),
)
def _integrate_planes_jit(
    m0, w_cp, params_vec, h_in, lane_mask,
    *, dt, n_steps, impl, n_inner, block_n, block_e, interpret,
    precision=PRECISION_DEFAULT,
):
    # the oracle is pure XLA — no MXU tile constraint, so padding would only
    # burn FLOPs on dead lanes; the Pallas kernels need lane alignment.
    # "chunk" at the per-hold-window level is a one-tick chunk: the Pallas
    # rk4_chunk kernel on TPU (W VMEM-resident for the whole window — so a
    # dispatch winner measured on the chunked shape stays a sane choice for
    # tick()/drive()/integrate() too), the same math as the jnp oracle
    # elsewhere.
    use_pallas_chunk = impl == "chunk" and (
        jax.default_backend() == "tpu" or interpret
    )
    pb_n, pb_e = (
        (1, 1)
        if impl in ("ref", "chunk") and not use_pallas_chunk
        else (block_n, block_e)
    )
    m, w, pv, h, n_orig, e_orig = _pad_planes(
        m0, w_cp, params_vec, h_in, pb_n, pb_e
    )
    w = _coupling_operand(w, precision)

    if use_pallas_chunk:
        _, n_p, e_p = m.shape
        h_block = (
            jnp.zeros((1, n_p, e_p), m.dtype) if h is None else h[None]
        )
        m, _ = sto_step.rk4_chunk(
            m, w, pv, dt, n_steps, h_block,
            jnp.ones((1, e_p), m.dtype), block_e=block_e, interpret=interpret,
        )
    elif impl in ("ref", "chunk"):
        dt_c = jnp.asarray(dt, m.dtype)

        def body(mm, _):
            return kref.rk4_step_planes(mm, w, pv, dt_c, h), None

        m, _ = jax.lax.scan(body, m, None, length=n_steps)
    elif impl == "fused":
        n_inner = effective_n_inner(n_steps, n_inner)

        def body(mm, _):
            return (
                sto_step.rk4_fused(
                    mm, w, pv, dt, n_inner=n_inner, block_e=block_e,
                    h_in=h, interpret=interpret,
                ),
                None,
            )

        m, _ = jax.lax.scan(body, m, None, length=n_steps // n_inner)
    elif impl == "tiled":
        def body(mm, _):
            return (
                sto_step.rk4_tiled_step(
                    mm, w, pv, dt, block_n=block_n, block_e=block_e,
                    h_in=h, interpret=interpret,
                ),
                None,
            )

        m, _ = jax.lax.scan(body, m, None, length=n_steps)
    else:
        raise ValueError(f"unknown impl: {impl}")

    m = m[:, :n_orig, :e_orig]
    if lane_mask is not None:
        # Partial-batch masking: frozen lanes return their input state
        # bit-identically (idle serving slots don't drift).
        m = jnp.where(lane_mask[None, None, :], m, m0)
    return m


def sto_rk4_tick_chunk_planes(
    m0: jnp.ndarray,  # (3, N, E) kernel layout
    w_cp: jnp.ndarray,  # (N, N)
    params_vec: jnp.ndarray,  # (NP, E) packed (kernels/ref.pack_params)
    dt: float,
    hold_steps: int,
    h_block: jnp.ndarray,  # (K, N, E) per-tick input-drive x-fields
    mask_block: jnp.ndarray,  # (K, E) bool; False = lane frozen that tick
    impl: str = "auto",
    precision: Optional[str] = None,
    n_inner: int = 8,
    block_n: int = LANE,
    block_e: int = LANE,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """K serving ticks (K hold windows) in kernel layout, one dispatch.

    The chunk-level integration entry: per-tick input fields arrive as a
    precomputed (K, N, E) block and the per-tick states block stays device-
    side. impl="chunk" runs the whole K x hold_steps x 4-stage loop as one
    chunk-resident region (Pallas rk4_chunk on TPU — W read from HBM once
    per chunk; the jnp chunk oracle elsewhere); the per-window impls
    (ref/fused/tiled) scan over ticks re-entering their kernels. Returns
    (m' (3, N, E), states (K, N, E) per-tick x-planes). Frozen (masked
    False) lanes come back bit-identical for every impl.
    """
    _, n, e = m0.shape
    if impl == "auto":
        impl = choose_impl(
            n, e, m0.dtype.itemsize, precision=precision,
            k_ticks=h_block.shape[0], hold_steps=hold_steps, n_inner=n_inner,
            block_n=block_n, block_e=block_e,
        )
    return _tick_chunk_planes_jit(
        m0, w_cp, params_vec, h_block, mask_block,
        dt=dt, hold_steps=hold_steps, impl=impl, n_inner=n_inner,
        block_n=block_n, block_e=block_e, interpret=interpret,
        precision=normalize_precision(precision),
    )


@functools.partial(
    jax.jit,
    static_argnames=("dt", "hold_steps", "impl", "n_inner", "block_n", "block_e", "interpret", "precision"),
)
def _tick_chunk_planes_jit(
    m0, w_cp, params_vec, h_block, mask_block,
    *, dt, hold_steps, impl, n_inner, block_n, block_e, interpret,
    precision=PRECISION_DEFAULT,
):
    k_ticks = h_block.shape[0]
    pb_n, pb_e = (1, 1) if impl in ("ref", "chunk") else (block_n, block_e)
    use_pallas_chunk = impl == "chunk" and (
        jax.default_backend() == "tpu" or interpret
    )
    if use_pallas_chunk:
        pb_n, pb_e = block_n, block_e
    m, w, pv, _, n_orig, e_orig = _pad_planes(
        m0, w_cp, params_vec, None, pb_n, pb_e
    )
    _, n_p, e_p = m.shape
    if (n_p, e_p) != h_block.shape[1:]:
        h_block = jnp.pad(
            h_block,
            ((0, 0), (0, n_p - h_block.shape[1]), (0, e_p - h_block.shape[2])),
        )
        # padded lanes stay frozen: their params are edge-broadcast so the
        # math is safe either way, but frozen is cheaper to reason about
        mask_block = jnp.pad(mask_block, ((0, 0), (0, e_p - mask_block.shape[1])))
    w = _coupling_operand(w, precision)

    if use_pallas_chunk:
        mT, states = sto_step.rk4_chunk(
            m, w, pv, dt, hold_steps, h_block,
            mask_block.astype(m.dtype), block_e=block_e, interpret=interpret,
        )
    elif impl in ("ref", "chunk"):
        # one fused region either way off-TPU; "chunk" additionally means
        # the caller precomputed h_block with ONE input GEMM per chunk
        mT, states = kref.rk4_chunk_planes(
            m, w, pv, dt, hold_steps, h_block, mask_block
        )
    elif impl in ("fused", "tiled"):
        if impl == "fused":
            n_inner = effective_n_inner(hold_steps, n_inner)

        def per_tick(mm, tick_in):
            h_t, mask_t = tick_in
            if impl == "fused":
                def win(mw, _):
                    return (
                        sto_step.rk4_fused(
                            mw, w, pv, dt, n_inner=n_inner, block_e=block_e,
                            h_in=h_t, interpret=interpret,
                        ),
                        None,
                    )

                m_new, _ = jax.lax.scan(win, mm, None, length=hold_steps // n_inner)
            else:
                def win(mw, _):
                    return (
                        sto_step.rk4_tiled_step(
                            mw, w, pv, dt, block_n=block_n, block_e=block_e,
                            h_in=h_t, interpret=interpret,
                        ),
                        None,
                    )

                m_new, _ = jax.lax.scan(win, mm, None, length=hold_steps)
            m_new = jnp.where(mask_t[None, None, :], m_new, mm)
            return m_new, m_new[0]

        mT, states = jax.lax.scan(per_tick, m, (h_block, mask_block))
    else:
        raise ValueError(f"unknown impl: {impl}")

    return mT[:, :n_orig, :e_orig], states[:, :n_orig, :e_orig]


def sto_rk4_integrate(
    m0: jnp.ndarray,  # (..., N, 3) user layout
    w_cp: jnp.ndarray,  # (N, N)
    params_vec: jnp.ndarray,  # (NP, E) packed (kernels/ref.pack_params)
    dt: float,
    n_steps: int,
    impl: str = "auto",
    n_inner: int = 8,
    block_n: int = LANE,
    block_e: int = LANE,
    interpret: bool = False,
    precision: Optional[str] = None,
) -> jnp.ndarray:
    """Integrate n_steps of coupled-STO RK4 with the chosen implementation.

    Returns the final state in user layout. n_steps must be divisible by
    n_inner for the fused path (auto-adjusted otherwise). Like the planes
    entry point, impl="auto" is resolved eagerly against the dispatch table.
    """
    batch_shape = m0.shape[:-2]
    e = 1
    for s in batch_shape:
        e *= int(s)
    if impl == "auto":
        impl = choose_impl(
            m0.shape[-2], e, m0.dtype.itemsize, precision=precision,
            hold_steps=n_steps, n_inner=n_inner, block_n=block_n,
            block_e=block_e,
        )
    m = _integrate_planes_jit(
        to_planes(m0), w_cp, params_vec, None, None,
        dt=dt, n_steps=n_steps, impl=impl, n_inner=n_inner,
        block_n=block_n, block_e=block_e, interpret=interpret,
        precision=normalize_precision(precision),
    )
    return from_planes(m, batch_shape)


def sto_rk4_step(
    m0: jnp.ndarray,
    w_cp: jnp.ndarray,
    params: STOParams,
    dt: float,
    impl: str = "auto",
    interpret: bool = False,
    block_n: int = LANE,
    block_e: int = LANE,
) -> jnp.ndarray:
    """Single RK4 step convenience wrapper taking STOParams directly."""
    e = 1
    for s in m0.shape[:-2]:
        e *= s
    pv = kref.pack_params(params, e, dtype=m0.dtype)
    return sto_rk4_integrate(
        m0, w_cp, pv, dt, 1,
        impl=impl, n_inner=1, block_n=block_n, block_e=block_e, interpret=interpret,
    )
