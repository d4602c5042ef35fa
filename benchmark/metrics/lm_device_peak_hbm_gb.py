"""device - TPU v5e: the fullest chip's peak of device memory after the
window, by the larger of the runtime's two counters (what the result
line's ``memory_peak_bytes`` takes), in the sequence task's cell.  Not
``device_peak_hbm_gb``'s reader: that takes ``peak_bytes_reserved``
alone, which in this cell reads 3.22 GB under a ``peak_bytes_in_use`` of
8.47 (PERF.md section 6, PR 28): the float32 state is live buffers, not
a reservation."""


def read(ctx):
    peaks = [max(s.get("peak_bytes_in_use", 0),
                 s.get("peak_bytes_reserved", 0))
             for s in ctx.memory_stats if s]
    return max(peaks) / 1e9 if peaks and max(peaks) else None
