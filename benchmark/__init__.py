"""The benchmark of alink_tpu: one cell, one run, one process.

``python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the chips the
machine holds and prints the contract's result line. Everything that
decides a number lives here, where a PR that claims a gain cannot touch
it: traffic generation (``generators/`` reading ``traffic/*.json``), the
configurations (``configs/``), the plain references (``reference/``), the
operations-and-bytes functions (``opcount.py``), the table of peaks
(``peaks.json``), the trace reduction (``trace_reduce.py``) and the
per-layer readers (``readers/``). From ``alink_tpu`` it takes the system
under test and its counters, nothing else.
"""
