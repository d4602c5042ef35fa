"""input - eksml_tpu/data/loader.py: how long ``TokenLoader``'s producer
thread worked on one batch (one row of 4,096 packed tokens), mean over
the window (its ``batch_build`` spans), in the hyper-connected task's
cell: ``batch_build_ms``'s reader, for the cell its closed list does
not name."""

from benchmark.metrics.batch_build_ms import read  # noqa: F401
