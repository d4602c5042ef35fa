"""trainer - Trainer.fit, _train_step: the median time between two
steps' completions on the device.  Reads the program's ``device_step``
spans of the window (one per step, ended by a thread that blocks on
the step's loss: eksml_tpu/telemetry/tracing.StepStamper), ordered by
step: the differences of consecutive ends."""

import statistics

MIN_DIFFERENCES = 11


def step_intervals_ms(ctx):
    """Milliseconds from each step's completion to the next one's,
    over the window's consecutive ``device_step`` spans."""
    ends = sorted((ev["args"]["step"], ev["ts"] + ev["dur"])
                  for ev in ctx.spans if ev.get("name") == "device_step"
                  and "step" in ev.get("args", {}))
    return [(e1 - e0) / 1e3 for (s0, e0), (s1, e1) in zip(ends, ends[1:])
            if s1 == s0 + 1]


def read(ctx):
    diffs = step_intervals_ms(ctx)
    if len(diffs) < MIN_DIFFERENCES:
        return None
    return statistics.median(diffs)
