"""trainer - Trainer.fit, _train_step: the 75th percentile of the time
between two steps' completions on the device, in the hyper-connected
task's cell: ``loop_step_ms_p75``'s reader (16 differences do), so that
a step twice the predicted one still reads where ``step_ms_p75``'s 40
would not."""

from benchmark.metrics.loop_step_ms_p75 import read  # noqa: F401
