"""Work of one served chunk (`CompiledSim.tick_chunk` plus the readouts).

Counted from the problem's shape alone, (N, E, K, hold_steps, RK stages,
n_in, n_out), never from the implementation that runs it, so every impl
is measured against the same work. Padding is not work.

FLOPs (a multiply-add is two):
  coupling GEMM   2 N^2 E per RK stage, stages x hold_steps x K times
  input GEMM      2 N n_in E per tick
  LLG field       54 per oscillator per stage: m.p 5, H_s 3, H_z 2,
                  h_x 2, p x m with H_s and b 15, m x b and m x (m x b) 18,
                  the two damping terms 9
  RK4 update      42 per oscillator per step: three stage inputs y + a dt k
                  (6 each) and the weighted sum (24)
  readout         2 (N + 1) n_out E per tick

Bytes, the least a chunk must move: W and W_in once, the (3, N, E) state
planes in and out, the (K, E, n_in) inputs and (K, E) lane mask in, the
readout weights (E, N + 1, n_out) in, and out either the (K, N, E) states
(when a session collects them) or the (K, E, n_out) outputs.
"""

FIELD_FLOPS = 54
RK4_UPDATE_FLOPS = 42


def count(shape: dict) -> dict:
    n, e, k = shape["n"], shape["e"], shape["k"]
    hold, stages = shape["hold_steps"], shape["stages"]
    n_in, n_out, b = shape["n_in"], shape["n_out"], shape["itemsize"]
    steps = k * hold
    flops = (
        2 * n * n * e * stages * steps
        + 2 * n * n_in * e * k
        + FIELD_FLOPS * n * e * stages * steps
        + RK4_UPDATE_FLOPS * n * e * steps
        + 2 * (n + 1) * n_out * e * k
    )
    out = k * n * e if shape["collect_states"] else k * e * n_out
    nbytes = b * (
        n * n + n * n_in
        + 2 * 3 * n * e
        + k * e * n_in
        + e * (n + 1) * n_out
        + out
    ) + k * e
    return {"flops": flops, "bytes": nbytes}


def least_seconds(shape: dict, peak: dict) -> dict:
    """max(FLOPs / peak FLOP/s, bytes / peak bytes/s), and which bounds it."""
    w = count(shape)
    t_flops = w["flops"] / peak["bf16_flops_per_s"]
    t_bytes = w["bytes"] / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "flops" if t_flops >= t_bytes else "bytes", **w}
