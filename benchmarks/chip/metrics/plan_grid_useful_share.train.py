"""Share of the kernels' chunk grid that does work: the sum of the plan's
chunk counts over out_blocks x max_chunks, in %."""
from benchmarks.chip.readers import plan_useful_share


def read(ctx):
    return plan_useful_share(ctx)
