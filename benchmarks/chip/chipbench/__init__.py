"""Chip benchmark of the serving path: closed-loop cells driven from data.

Each configuration (`configs/<name>.json`), traffic mix
(`traffic/<name>.json`) and metric (`metrics/<name>.py`) is a file of its
own, found by the name `BENCHMARK.json` gives it.  This package is the
yardstick: traffic generation, operation and byte counts, the table of
peaks, the trace reduction and the float32 reference that decides
`correct`.  It imports the program only in `adapter.py`.
"""
