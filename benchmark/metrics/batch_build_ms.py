"""input - eksml_tpu/data/loader.py: how long the loader's producer
thread worked on one batch (records drawn, resized, augmented, masks
rasterised, stacked), mean over the window.  Reads the program's
``batch_build`` spans, which end before the queue put: a producer
waiting on a full queue is not building."""


def mean_span_ms(ctx, name):
    durs = [ev["dur"] for ev in ctx.spans if ev.get("name") == name]
    return sum(durs) / 1e3 / len(durs) if durs else None


def read(ctx):
    return mean_span_ms(ctx, "batch_build")
