"""The training steps' required flops per second over the chip's bf16 peak,
in % (float32 work against the bf16 peak, the only one published)."""
from benchmarks.chip.readers import mfu


def read(ctx):
    return mfu(ctx)
