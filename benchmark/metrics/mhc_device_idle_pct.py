"""device - TPU v5e: the device's idle share of the traced stretch in
the hyper-connected task's cell: ``device_idle_pct``'s reader, for the
cell its closed list does not name."""

from benchmark.metrics.device_idle_pct import read  # noqa: F401
