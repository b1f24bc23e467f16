"""On-chip serving benchmark: one command runs one cell of ``BENCHMARK.json``.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own and is found by its name:
``configs/<config>.json``, ``traffic/<mix>.json``, ``limits/<cell>.json``
and ``metrics/<metric>.py``.  The yardstick (traffic generation, trace
reduction, peaks, operation and byte counts, the float32 reference and the
comparison that decides ``correct``) lives here; from the program the
benchmark takes only ``Session``/``Engine`` and the names of its jitted
modules.
"""
