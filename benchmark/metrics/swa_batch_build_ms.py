"""input - eksml_tpu/data/loader.py: how long ``TokenLoader``'s producer
thread worked on one batch (two rows of 8,192 packed tokens), mean over
the window (its ``batch_build`` spans), in the window-and-experts
task's cell.  ``batch_build_ms``'s reader, for the cell its closed list
does not name (PERF.md section 7, U(a))."""

from benchmark.metrics.batch_build_ms import read  # noqa: F401
