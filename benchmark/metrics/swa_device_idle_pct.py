"""device - TPU v5e: the share of the traced stretch in which no device op
ran, in the window-and-experts task's cell.  ``device_idle_pct``'s
reader, for the cell its closed list does not name (PERF.md section 7,
U(a))."""

from benchmark.metrics.device_idle_pct import read  # noqa: F401
