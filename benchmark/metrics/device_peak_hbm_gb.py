"""device - TPU v5e: ``peak_bytes_reserved`` of ``memory_stats()`` on
the fullest chip after the window.  On this runtime ``peak_bytes_in_use``
leaves out the program's temporaries (PERF.md, PR 21), so the reserved
peak is the one that shows the step's footprint."""


def read(ctx):
    peaks = [s.get("peak_bytes_reserved") for s in ctx.memory_stats if s]
    peaks = [p for p in peaks if p]
    return max(peaks) / 1e9 if peaks else None
