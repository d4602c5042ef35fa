"""input - eksml_tpu/data/loader.py: how long the prefetcher's thread
took to hand one host batch to the device (globalize + device_put),
mean over the window.  Reads the program's ``h2d_prefetch`` spans."""

from benchmark.metrics.batch_build_ms import mean_span_ms


def read(ctx):
    return mean_span_ms(ctx, "h2d_prefetch")
