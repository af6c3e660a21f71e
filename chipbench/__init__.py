"""The on-chip benchmark: the yardstick later PRs are measured with.

Everything here belongs to the benchmark, not to the program: traffic,
weights, the plain reference, FLOP/byte arithmetic, the table of peaks and
the reduction from a profiler trace to metrics. From the program it takes
only the system under test and its counters, spans and kernel names.
"""
