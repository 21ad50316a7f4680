"""The whole served step's share of the chip's peak: the algorithm's
least operations for every acquisition served (bench/work.py), over the
window, over the device's published bf16 peak (bench/peaks.json). The
pipeline computes in float32 at the highest matmul precision, so the
share is of a peak it cannot reach; it is comparable across formulations
because the count is."""

from bench import work


def read(ctx):
    peak = work.peaks(ctx.device_kind)["flops_bf16"] * ctx.chips
    return ctx.flops / ctx.window_s / peak * 100.0
