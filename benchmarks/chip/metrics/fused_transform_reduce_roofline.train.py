"""fused_transform_reduce's share of its roofline over the window's training
steps, in %."""
from benchmarks.chip.readers import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "fused_transform_reduce")
