"""gather_segment_reduce's share of its roofline over the window's training
steps, in %."""
from benchmarks.chip.readers import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "gather_segment_reduce")
