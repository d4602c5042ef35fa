"""trainer - Trainer.fit, _train_step: the median time between two steps'
completions on the device (the ``device_step`` stamps), in the
looped-stack task's cell.  ``step_ms_p50``'s reader, for the cell its
closed list does not name (PERF.md section 7, U(a)); it wants 12 steps
in the window."""

from benchmark.metrics.step_ms_p50 import read  # noqa: F401
