"""Share of the kernels' (output block, chunk) grid steps that had work,
as each executed launch counted them (kernel.grid_steps: owned over
walked), over the run, in %."""
from benchmarks.chip.program_obs import grid_useful_share


def read(ctx):
    return grid_useful_share()
