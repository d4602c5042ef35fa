"""trainer - Trainer.fit, _train_step: how long the device sits drained
at a log step, mean over the window's log steps, in the hyper-connected
task's cell: ``log_drain_ms``'s reader, for the cell its closed list
does not name."""

from benchmark.metrics.log_drain_ms import read  # noqa: F401
