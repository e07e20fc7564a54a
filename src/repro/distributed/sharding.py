"""Sharding rules: param/cache/batch pytrees -> NamedShardings.

Megatron-style tensor parallelism with divisibility-aware fallbacks:

  embed / lm_head           vocab dim         -> model
  attention wq/wk/wv        out (heads*hd)    -> model  (column parallel)
  attention wo              in  (heads*hd)    -> model  (row parallel)
  mlp w_in/w_gate           out (d_ff)        -> model
  mlp w_out                 in  (d_ff)        -> model
  MoE experts (E, d, f)     E -> model if E % |model| == 0 else f -> model
  mamba in/out_proj         d_inner           -> model
  xlstm projections         d_inner           -> model
  biases / norms / small    replicated

Batch dims shard over ("pod", "data") for training and ("data",) or
configured axes for serving. Any dim not divisible by its axis size falls
back to replication (never fails to produce a valid sharding) — dry-run
coherence across all 10 archs relies on this.
"""

from __future__ import annotations

import re
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def auto_axes(mesh: Mesh) -> Mesh:
    """The same devices and axis names with every axis typed Auto.

    `jax.make_mesh` types its axes Explicit, under which an array's sharding
    is part of its type: indexing a sharded array then needs an out_sharding
    and with_sharding_constraint refuses the spec. The rules in this module
    and the reservoir's shard_map bodies place data through NamedShardings
    and leave propagation to the compiler, which is what Auto axes mean.
    """
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


# ---------------------------------------------------------------------------
# Activation sharding constraints (enabled by the launcher/dry-run; model
# code calls constrain(x, BATCH, None, MODEL) unconditionally and it is a
# no-op unless a mesh was registered).
# ---------------------------------------------------------------------------

BATCH = "__batch__"  # placeholder resolved to ("pod","data") / ("data",)
MODEL = "__model__"

_ACTIVE_MESH: Optional[Mesh] = None


def kv_seq_mode() -> str:
    """KV-cache layout policy (§Perf B):
      "0"    heads/head_dim sharding (the naive baseline in §Roofline)
      "1"    force sequence sharding (flash-decode layout)
      "auto" (default) sequence sharding ONLY when kv_heads doesn't divide
             the model axis — measured per-cell in EXPERIMENTS.md §Perf:
             10-17.6x where heads don't divide, ~0.9x where they do."""
    import os

    return os.environ.get("REPRO_KV_SEQ_SHARD", "auto")


def want_kv_seq_shard(kv_heads: int, mesh: Optional[Mesh] = None) -> bool:
    mode = kv_seq_mode()
    if mode == "1":
        return True
    if mode == "0":
        return False
    mesh = mesh or _ACTIVE_MESH
    if mesh is None or "model" not in mesh.shape:
        return False
    # MLA latent caches pass kv_heads=0: always prefer seq sharding there
    return kv_heads == 0 or kv_heads % mesh.shape["model"] != 0


def enable_constraints(mesh: Optional[Mesh]):
    """Register the mesh used to resolve activation sharding constraints.
    Pass None to disable (single-device tests)."""
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh


def constrain(x, *spec):
    """with_sharding_constraint that (a) is inert without a registered mesh,
    (b) resolves BATCH/MODEL placeholders, (c) drops axes that don't divide."""
    mesh = _ACTIVE_MESH
    if mesh is None:
        return x
    ba = _batch_axes(mesh)
    resolved = []
    for dim, s in zip(x.shape, spec):
        if s == BATCH:
            s = ba if len(ba) > 1 else (ba[0] if ba else None)
        elif s == MODEL:
            s = "model" if "model" in mesh.shape else None
        if s is not None and dim % _axis_size(mesh, s if isinstance(s, tuple) else (s,)) != 0:
            s = None
        resolved.append(s)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*resolved))
    )


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _ok(dim: int, mesh: Mesh, axes) -> bool:
    return dim % _axis_size(mesh, axes) == 0


# (path regex, candidate specs tried in order; first divisible wins)
# spec entries: tuple of per-dim axis assignments
_PARAM_RULES: Tuple[Tuple[str, Tuple[Tuple, ...]], ...] = (
    # embeddings: shard vocab; fall back to d_model
    (r"embed/embed$", ((("model",), None), (None, ("model",)))),
    (r"lm_head/kernel$", ((None, ("model",)),)),  # (d, vocab)
    (r"dec_pos$", ((None, None),)),
    # attention projections
    (r"(mixer|cross)/wq/kernel$", ((None, ("model",)),)),
    (r"(mixer|cross)/wk/kernel$", ((None, ("model",)),)),
    (r"(mixer|cross)/wv/kernel$", ((None, ("model",)),)),
    (r"(mixer|cross)/wo/kernel$", ((("model",), None),)),
    # MLA
    (r"mixer/wkv_a/kernel$", ((None, None),)),  # tiny latent proj: replicate
    (r"mixer/w_uk$", ((None, ("model",), None),)),  # (r, H, dn): shard heads
    (r"mixer/w_uv$", ((None, ("model",), None),)),
    # MoE: experts first, then expert-ff fallback
    (r"mlp/(w_gate|w_in)$", ((("model",), None, None), (None, None, ("model",)))),
    (r"mlp/w_out$", ((("model",), None, None), (None, ("model",), None))),
    (r"mlp/router/kernel$", ((None, None),)),
    (r"mlp/shared/(w_gate|w_in)/kernel$", ((None, ("model",)),)),
    (r"mlp/shared/w_out/kernel$", ((("model",), None),)),
    # dense MLP
    (r"mlp/(w_gate|w_in)/kernel$", ((None, ("model",)),)),
    (r"mlp/w_out/kernel$", ((("model",), None),)),
    # mamba
    (r"mixer/in_proj/kernel$", ((None, ("model",)),)),
    (r"mixer/out_proj/kernel$", ((("model",), None),)),
    (r"mixer/(conv_w|conv_b)$", ((None, ("model",)), (("model",),))),
    (r"mixer/x_proj/kernel$", ((("model",), None),)),
    (r"mixer/dt_proj/kernel$", ((None, ("model",)),)),
    (r"mixer/dt_proj/bias$", ((("model",),),)),
    (r"mixer/a_log$", ((("model",), None),)),
    (r"mixer/d_skip$", ((("model",),),)),
    # xlstm
    (r"mixer/up_proj/kernel$", ((None, ("model",)),)),
    (r"mixer/down_proj/kernel$", ((("model",), None),)),
    (r"mixer/(wq|wk|wv)/kernel$", ((None, ("model",)),)),
    (r"mixer/w_if/kernel$", ((None, None),)),
    (r"mixer/w_gates/kernel$", ((None, ("model",)),)),
    (r"mixer/r_gates$", ((None, ("model",), None, None),)),
    (r"mixer/b_gates$", ((None, None),)),
)


def _path_str(path) -> str:
    parts = []
    for k in path:
        if isinstance(k, jax.tree_util.DictKey):
            parts.append(str(k.key))
        elif isinstance(k, jax.tree_util.SequenceKey):
            parts.append(str(k.idx))
        elif isinstance(k, jax.tree_util.GetAttrKey):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


def _spec_for_param(path: str, shape, mesh: Mesh, stacked: bool) -> P:
    """First matching rule whose axis sizes divide the dims; else replicate.

    stacked: leaf carries a leading num_periods axis (from scan stacking)."""
    ndims = len(shape)
    offset = 1 if stacked else 0
    for pat, candidates in _PARAM_RULES:
        if re.search(pat, path):
            for cand in candidates:
                if len(cand) != ndims - offset:
                    continue
                good = True
                for dim, axes in zip(shape[offset:], cand):
                    if axes is not None and not _ok(dim, mesh, axes):
                        good = False
                        break
                if good:
                    spec = (None,) * offset + tuple(
                        axes if axes is None else (axes[0] if len(axes) == 1 else axes)
                        for axes in cand
                    )
                    return P(*spec)
            break
    return P()  # replicate


def param_shardings(mesh: Mesh, params_or_specs, cfg=None):
    """NamedSharding pytree for a param tree (arrays or ShapeDtypeStructs)."""

    def assign(path, leaf):
        ps = _path_str(path)
        stacked = ps.startswith("stack/") or "/stack/" in ps
        spec = _spec_for_param(ps, leaf.shape, mesh, stacked)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(assign, params_or_specs)


# ---------------------------------------------------------------------------
# batch / cache shardings
# ---------------------------------------------------------------------------


def _batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def batch_shardings(mesh: Mesh, batch_specs, seq_axis: Optional[str] = None):
    """Shard the leading batch dim over (pod, data); optionally the sequence
    dim over `seq_axis` (sequence parallelism for B=1 long-context)."""
    ba = _batch_axes(mesh)

    def assign(path, leaf):
        ps = _path_str(path)
        if "caches" in ps:
            return NamedSharding(mesh, cache_spec_for(ps, leaf, mesh))
        shape = leaf.shape
        if len(shape) == 0:
            return NamedSharding(mesh, P())
        bspec = ba if shape[0] % _axis_size(mesh, ba) == 0 else (
            ("data",) if shape[0] % _axis_size(mesh, ("data",)) == 0 else None
        )
        spec = [bspec] + [None] * (len(shape) - 1)
        if seq_axis and len(shape) >= 2 and shape[1] % _axis_size(mesh, (seq_axis,)) == 0:
            # only shard seq when batch is NOT absorbing that axis
            if bspec is None or seq_axis not in (bspec if isinstance(bspec, tuple) else (bspec,)):
                spec[1] = seq_axis
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(assign, batch_specs)


def cache_spec_for(path: str, leaf, mesh: Mesh) -> P:
    """KV-cache sharding: batch -> data(+pod), heads/head_dim -> model.

    Layouts: attention k/v (B,S,KVH,HD) [stacked: +lead]; MLA c_kv (B,S,r);
    mamba h (B,di,ds); conv_tail (B,K-1,di); xlstm c (B,H,dh,dh).
    """
    ba = _batch_axes(mesh)
    shape = leaf.shape
    stacked = "/stack/" in path or path.startswith("stack/")
    off = 1 if stacked else 0
    dims = shape[off:]
    spec = [None] * off + [None] * len(dims)

    # batch dim
    if dims and dims[0] % _axis_size(mesh, ba) == 0:
        spec[off] = ba if len(ba) > 1 else ba[0]
    elif dims and dims[0] % _axis_size(mesh, ("data",)) == 0:
        spec[off] = "data"

    def try_model(i):
        if dims[i] % _axis_size(mesh, ("model",)) == 0:
            spec[off + i] = "model"
            return True
        return False

    if re.search(r"/(k|v)$", path) and len(dims) == 4:
        # (B,S,KVH,HD). Layouts (§Perf B):
        #   heads -> model (fall back to head_dim), or
        #   sequence -> model (flash-decode style; decode attention reduces
        #   partial softmax stats instead of all-gathering the cache).
        # "auto" picks seq exactly when kv_heads doesn't divide the axis.
        if want_kv_seq_shard(dims[2], mesh):
            if try_model(1):
                return P(*spec)
        if not try_model(2):
            try_model(3)
    elif re.search(r"/(c_kv|k_rope)$", path) and len(dims) == 3:
        # MLA latent cache (B, S, r): seq-sharded layout (auto: always — the
        # latent has no head structure to shard cleanly; 7.8x in §Perf B)
        if want_kv_seq_shard(0, mesh):
            if try_model(1):
                return P(*spec)
        try_model(2)
    elif re.search(r"/(h|conv_tail)$", path) and len(dims) == 3:
        try_model(1) if re.search(r"/h$", path) else try_model(2)
    elif re.search(r"/c$", path) and len(dims) == 4:
        try_model(1)
    elif re.search(r"/(n|m)$", path) and len(dims) >= 2:
        try_model(1)
    return P(*spec)


# ---------------------------------------------------------------------------
# Reservoir ensemble shardings (consumed by repro.api's sharded plans)
# ---------------------------------------------------------------------------


def reservoir_specs(
    ensemble_axes: Sequence[str] = ("data",),
    model_axis: Optional[str] = "model",
):
    """PartitionSpecs for the coupled-STO ensemble state.

    The layout every sharded reservoir path in this repo uses: the ensemble
    axis E spans `ensemble_axes` (data/pod parallelism — independent
    reservoirs), the oscillator axis N spans `model_axis` (W^cp row-sharded;
    each RK stage all-gathers the m^x slice). Keys:

      params  STOParams leaves (E, 1)
      w       coupling matrix (N, N), row-sharded
      w_in    input matrix (N, N_in), row-sharded like w
      m       magnetization (E, N, 3)
      u       shared input series (T, N_in), replicated
      u_e     per-lane input (T, E, N_in)
      u_tick  one tick's per-lane input rows (E, N_in)
      lane    per-lane vectors (E,) — masks, gains
      lane_block  per-tick per-lane mask block (K, E) — chunked serving
      states  collected node states (T, E, N)
      states_tick  one tick's states plane (E, N)
      learn_p  per-lane RLS inverse-Gram (E, S, S) — lane-sharded, the
               (S, S) = (N+1, N+1) feature block replicated (the update
               consumes the all-gathered feature vector)
      learn_w  per-lane readout weights (E, S, n_out), sharded like learn_p
      y_block  per-tick per-lane targets / predictions (K, E, n_out)
    """
    ens = tuple(ensemble_axes)
    return {
        "params": P(ens),
        "w": P(model_axis, None),
        "w_in": P(model_axis, None),
        "m": P(ens, model_axis, None),
        "u": P(None, None),
        "u_e": P(None, ens, None),
        "u_tick": P(ens, None),
        "lane": P(ens),
        "lane_block": P(None, ens),
        "states": P(None, ens, model_axis),
        "states_tick": P(ens, model_axis),
        "learn_p": P(ens, None, None),
        "learn_w": P(ens, None, None),
        "y_block": P(None, ens, None),
    }


def logical_summary(mesh: Mesh, params) -> str:
    """Debug helper: param path -> spec table."""
    rows = []

    def walk(path, leaf):
        ps = _path_str(path)
        stacked = ps.startswith("stack/") or "/stack/" in ps
        spec = _spec_for_param(ps, leaf.shape, mesh, stacked)
        rows.append(f"{ps:60s} {str(leaf.shape):24s} {spec}")
        return leaf

    jax.tree_util.tree_map_with_path(walk, params)
    return "\n".join(rows)
