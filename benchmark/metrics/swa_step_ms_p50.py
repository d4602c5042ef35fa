"""trainer - Trainer.fit, _train_step: the median time between two
steps' completions on the device (the ``device_step`` spans), in the
window-and-experts task's cell.  ``step_ms_p50``'s reader, for the cell
its closed list does not name (PERF.md section 7, U(a))."""

from benchmark.metrics.step_ms_p50 import read  # noqa: F401
