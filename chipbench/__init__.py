"""Chip benchmark of the CKKS engine (``python chipbench/run.py --help``).

Everything that decides a number lives here: traffic generation, the
reduction from profiler traces and counters to metrics, the table of
peaks, the bytes each kernel must move, the plain references and the
comparison that decides ``correct``.  The program under test is only
imported from ``src/repro``.
"""
