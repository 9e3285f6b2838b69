"""The chip benchmark's harness: resolution of a cell from
``BENCHMARK.json``, data and traffic made from a seed, the open-loop
drive of ``RetrievalServer``, the plain reference and the comparison that
decides ``correct``, the profiler-trace reduction and the peaks table.

Nothing here is imported by the program under test, and nothing here
reads the program's own generators, metric arithmetic or oracle: the
yardstick stays fixed while the program changes.
"""
