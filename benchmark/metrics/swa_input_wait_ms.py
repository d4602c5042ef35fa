"""input - eksml_tpu/data/loader.py: how long the step loop waited for its
next batch of packed rows, per step (the ``data_wait`` spans), in the
window-and-experts task's cell.  ``input_wait_ms``'s reader, for the
cell its closed list does not name (PERF.md section 7, U(a))."""

from benchmark.metrics.input_wait_ms import read  # noqa: F401
