"""Host time per step in the trainer's front end: self time of the trainer's
train.sample and train.prepare spans, in ms per step."""
from benchmarks.chip.readers import span_self_ms


def read(ctx):
    return span_self_ms(ctx, ("train.sample", "train.prepare"))
