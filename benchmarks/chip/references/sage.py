"""GraphSAGE (mean aggregator, root weight) as OGB's arxiv script trains
it: the shared plain reference (:mod:`benchmarks.chip.reference`) and the
step's flops (:func:`benchmarks.chip.costs.train_step_flops`)."""
from benchmarks.chip.costs import train_step_flops  # noqa: F401
from benchmarks.chip.reference import init_params, train  # noqa: F401
