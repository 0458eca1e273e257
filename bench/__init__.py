"""The chip benchmark: ``python bench/run.py --workload <cell> ...``.

Everything here is the yardstick: traffic generation, the operator and
weight makers, the plain references, the cost functions, the table of
peaks and the reduction from traces and spans to metrics. The program
under test is imported from ``src/`` and gives only the system, its spans,
its counters and its kernel names.
"""
