"""trainer - Trainer.fit, _train_step: the log row's host work a log
step (the ``log_row`` spans), in the hyper-connected task's cell:
``log_row_ms``'s reader, for the cell its closed list does not name."""

from benchmark.metrics.log_row_ms import read  # noqa: F401
