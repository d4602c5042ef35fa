"""trainer - Trainer.fit, _train_step: the 75th percentile of the time
between two steps' completions on the device, in the sequence task's
cell.  ``step_ms_p75``'s reader, for the cell its closed list does not
name (PERF.md section 7, U(a))."""

from benchmark.metrics.step_ms_p75 import read  # noqa: F401
