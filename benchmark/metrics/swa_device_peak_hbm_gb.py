"""device - TPU v5e: the fullest chip's peak of device memory after the
window, by the larger of the runtime's two counters (what the result
line's ``memory_peak_bytes`` takes), in the window-and-experts task's
cell: ``lm_device_peak_hbm_gb``'s reader (the float32 state is live
buffers, not a reservation), for the cell its list does not name."""

from benchmark.metrics.lm_device_peak_hbm_gb import read  # noqa: F401
