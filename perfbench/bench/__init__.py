"""The on-chip benchmark's harness: one cell, one process, one run.

Everything that belongs to one configuration, traffic mix, per-layer metric
or layer map lives in a file of its own under ``perfbench/`` and is found by
the name ``BENCHMARK.json`` gives it; this package holds only the general
code that reads them.
"""
