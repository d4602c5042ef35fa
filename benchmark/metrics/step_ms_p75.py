"""trainer - Trainer.fit, _train_step: the 75th percentile of the time
between two steps' completions on the device (step_ms_p50's
differences).  The mask cell's window completes about 57 steps, so a
percentile with ten samples beyond it ends at the 80th; the log step's
sync, one step in twenty, lies above that and is not read here."""

import statistics

from benchmark.metrics.step_ms_p50 import step_intervals_ms

MIN_DIFFERENCES = 40


def read(ctx):
    diffs = step_intervals_ms(ctx)
    if len(diffs) < MIN_DIFFERENCES:
        return None
    return statistics.quantiles(diffs, n=4, method="inclusive")[2]
