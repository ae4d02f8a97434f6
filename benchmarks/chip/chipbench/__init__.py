"""The chip benchmark's own code: spec lookup, traffic, clocks, trace
reduction, FLOP and byte counts, weights and the correctness comparisons.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own under ``configs/``, ``traffic/`` and
``metrics/`` beside this package, found by the name ``BENCHMARK.json``
gives it.  The program under test is imported from ``src/`` and read only
through its public entry points.
"""
