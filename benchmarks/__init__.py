"""The chip benchmark: ``python benchmarks/run.py --workload <cell> ...``.

Everything that decides a number lives under this directory (and its tests
under ``tests/bench_harness``): traffic generation, the reduction from
traces and counters to metrics, the table of peaks, the operation counts,
the plain references and the comparison that decides ``correct``.  From the
program (``hetu_tpu``) it takes only the system under test and its counters.
"""
