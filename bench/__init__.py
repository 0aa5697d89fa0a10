"""On-chip benchmark of the DME training step and the FL round loop.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``. Everything that belongs to one
configuration, traffic mix, per-layer metric or cell lives in a file of its
own (``configs/``, ``mixes/``, ``metrics/``, ``limits/``), found by the name
``BENCHMARK.json`` gives it; the code under ``drivers/`` runs the program,
``reference/`` recomputes what the timed path produced, and ``trace.py``
reduces the profiler's trace to numbers.
"""
