"""trainer - Trainer.fit, _train_step: the median time between two
steps' completions on the device, in the hyper-connected task's cell:
``step_ms_p50``'s reader, for the cell its closed list does not name."""

from benchmark.metrics.step_ms_p50 import read  # noqa: F401
