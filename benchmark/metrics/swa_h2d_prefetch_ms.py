"""input - eksml_tpu/data/loader.py: how long the prefetcher's thread took
to hand one token batch to the device (the ``h2d_prefetch`` spans), in
the window-and-experts task's cell.  ``h2d_prefetch_ms``'s reader, for
the cell its closed list does not name (PERF.md section 7, U(a))."""

from benchmark.metrics.h2d_prefetch_ms import read  # noqa: F401
