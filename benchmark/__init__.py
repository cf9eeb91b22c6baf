"""The benchmark of racon-tpu: the yardstick later PRs are judged with.

Everything that decides a number lives in this directory: traffic
generation, the reduction from spans, counters and the profiler trace to
metrics, the table of peaks, the closed forms for a kernel's operations
and bytes, and the comparison that decides ``correct``.  From the program
it takes the system under test (``PolishSession.run_job``, the host path
as the plain reference) and its spans, counters and reports.

Driven by data: a configuration, a traffic mix, a cell and a per-layer
metric are each a JSON file found by the name ``BENCHMARK.json`` gives;
a reducer is a function in any module under ``reducers/``.
"""
