"""input - eksml_tpu/data/loader.py: how long the prefetcher's thread took
to hand one token batch to the device (the ``h2d_prefetch`` spans), in
the hyper-connected task's cell: ``h2d_prefetch_ms``'s reader, for the
cell its closed list does not name."""

from benchmark.metrics.h2d_prefetch_ms import read  # noqa: F401
