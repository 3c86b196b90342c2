"""The chip benchmark of holo_tpu (BENCHMARK.json, PERF.md).

Everything a later PR may not change lives here: traffic generation,
the clocks, the reduction from spans, counters and the profiler's trace
to metrics, the table of peaks and the comparison that decides
``correct``.  From the program it takes only the system under test and
its spans, counters and kernel names.  ``README.md`` has the layout.
"""
