"""trainer - Trainer.fit, _train_step: Python's garbage collector, in
milliseconds a step (the ``log_row`` spans' ``gc_ms`` over their
``steps``), in the hyper-connected task's cell: ``gc_pause_ms``'s
reader, for the cell its closed list does not name."""

from benchmark.metrics.gc_pause_ms import read  # noqa: F401
