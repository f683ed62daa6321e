"""The benchmark of training on the chip: one command runs one cell once.

``python -m chipbench --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
drives ``matcha_tpu.train.train`` itself through its boundary seam, measures
a window of whole epochs, checks the first epoch and one step against the
plain reference in ``chipbench/reference`` and prints one JSON line.  Cells, configurations
and per-layer metrics are files found by the names in ``BENCHMARK.json``.
"""
