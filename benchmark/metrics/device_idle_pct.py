"""device - TPU v5e: 1 - (union of the device-op intervals) / (traced
window), mean over the chips, from the profiler's trace.  Reported in
both detector cells since PR 32: the Faster-RCNN cell was kept out for
one ~3 s gap a traced stretch, which was the host tracer's (XLA's
re-tiling of a batch records an event a block under it) and went with
it (``harness.Capture`` traces the device alone)."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.window_s or not t.busy_s:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
