"""Share of the roofline for the chunks served in the traced window.

The least time for those chunks, max(FLOPs / peak, bytes / bandwidth) from
bench/work/tick_chunk.py, over ALL device busy time in the window (not a
named kernel's, so renaming a kernel cannot silence it). The peak is the
chip's bf16 rate; the configuration's float32 products at HIGHEST take
several MXU passes, so this share reads low by construction."""

from benchlib import registry


def read(ctx):
    t = ctx.trace
    if t is None or ctx.peak is None or ctx.chunks_in_trace <= 0 or t["busy_s"] <= 0:
        return None
    least = registry.work("tick_chunk").least_seconds(ctx.shape, ctx.peak)
    ctx.notes.append(
        f"tick_chunk_roofline: {ctx.chunks_in_trace} chunks, bound by "
        f"{least['bound']}, least {least['seconds']!r} s per chunk "
        f"({least['flops']} FLOP, {least['bytes']} B)")
    return 100.0 * least["seconds"] * ctx.chunks_in_trace / t["busy_s"]
