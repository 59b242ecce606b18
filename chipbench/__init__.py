"""Chip benchmark of the function-space LSH server.

``python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the accelerator the
process finds and prints one JSON result line.  Everything that belongs to
one configuration, traffic mix or per-layer metric is a file of its own,
found by the name ``BENCHMARK.json`` gives it (``chipbench.bench``).

Importing this package imports no JAX: the load generator's child process
uses ``chipbench.wire`` and ``chipbench.loadgen`` without touching the chip.
"""
