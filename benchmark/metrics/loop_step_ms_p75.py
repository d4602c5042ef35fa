"""trainer - Trainer.fit, _train_step: the 75th percentile of the time
between two steps' completions on the device (``step_ms_p50``'s
differences), in the looped-stack task's cell.  Not ``step_ms_p75``'s
reader under another name: that one wants 40 differences, and at this
cell's 0.9 s step the 20 s window completes about 27 steps.  Here 16
do (four samples beyond the percentile); the log step's sync, one step
in five, lies above the 80th and is not read."""

import statistics

from benchmark.metrics.step_ms_p50 import step_intervals_ms

MIN_DIFFERENCES = 16


def read(ctx):
    diffs = step_intervals_ms(ctx)
    if len(diffs) < MIN_DIFFERENCES:
        return None
    return statistics.quantiles(diffs, n=4, method="inclusive")[2]
