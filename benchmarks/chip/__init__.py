"""The on-chip benchmark of DynaBRO (see PERF.md and BENCHMARK.json).

Run one cell from the repository root:

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that measures or judges lives here: traffic and weight
generation (``generator.py``), the arithmetic of required operations and
bytes (``arithmetic.py``), the table of peaks (``peaks.json``), the trace
reduction (``trace.py``), the plain references (``reference/``) and the
limits that decide ``correct`` (``checks/``). From the program the
benchmark takes only the system under test.
"""
