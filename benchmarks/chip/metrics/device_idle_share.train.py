"""Share of the training window in which no operation ran on the chip, in %."""
from benchmarks.chip.readers import idle_share


def read(ctx):
    return idle_share(ctx)
