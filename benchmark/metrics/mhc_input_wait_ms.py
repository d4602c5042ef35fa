"""input - eksml_tpu/data/loader.py: the step loop's mean wait for the
next batch in the hyper-connected task's cell: ``input_wait_ms``'s
reader, for the cell its closed list does not name."""

from benchmark.metrics.input_wait_ms import read  # noqa: F401
