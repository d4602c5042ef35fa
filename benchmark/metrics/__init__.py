"""One reader per per-layer metric, found by the metric's name.

``read(ctx)`` returns the value, or None where it finds nothing to
read (the harness then leaves the metric out of the line).  ``ctx`` is
``harness.TraceContext``.  Dots and dashes in a metric's name map to
underscores in the module's name.
"""
