"""The chip benchmark of implicitglobalgrid_tpu: one cell of BENCHMARK.json
per run (`benchmark/run.py`). Everything here is the yardstick; from the
program it takes only the system under test and what the profiler sees."""
