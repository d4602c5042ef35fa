"""device - TPU v5e: 1 - (union of the device-op intervals) / (traced
window), mean over the chips, from the profiler's trace."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.window_s or not t.busy_s:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
