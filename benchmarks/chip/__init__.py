"""On-chip benchmark of the GNN library: one cell, one run.

``python benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU it is started
on. Everything that measures lives here, apart from the program: graph
generators (:mod:`.graphs`), the plain reference (:mod:`.reference`),
the comparison that decides ``correct`` (:mod:`.compare`), the peaks table
(:mod:`.peaks`), the operations and bytes of each kernel (:mod:`.costs`) and
the reduction of a profiler trace (:mod:`.trace`). Configurations, traffic
mixes, limits and per-layer metrics are files found by name.
"""
