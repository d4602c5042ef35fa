"""trainer - Trainer.fit, _train_step: the 75th percentile of the time
between two steps' completions on the device, in the window-and-experts
task's cell.  ``loop_step_ms_p75``'s reader (16 differences do): at this
cell's step the 20 s window completes some two dozen steps, where
``step_ms_p75`` wants 40 differences."""

from benchmark.metrics.loop_step_ms_p75 import read  # noqa: F401
