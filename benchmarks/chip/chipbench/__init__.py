"""The on-chip benchmark's yardstick: traffic generation, the reduction
from spans, counters and device traces to metrics, the table of peaks,
operation and byte counts, and the correctness comparison.

Nothing here is imported by the program under test; the program is
reached only through ``chipbench.program``.
"""
