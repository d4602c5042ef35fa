"""One reader per per-layer metric, found by the metric's name.

``read(ctx)`` returns the value, or None where it finds nothing to
read (the harness then leaves the metric out of the line).  ``ctx`` is
``harness.TraceContext``.  Dots and dashes in a metric's name map to
underscores in the module's name.

Beside each reader, ``examples/<metric>.json`` holds what it reads and
the value it must then return: ``reads`` (a line of prose),
``workload`` (a cell that reports the metric: the example's spec, task
and chips), and whichever of ``context`` (fields of the context),
``peak``, ``spans``, ``trace`` (``TraceSummary``'s fields, ``op_seconds``
among them) and ``memory_stats`` the reader needs, then ``value`` and
``rel_tolerance``.  The tests build every context from these
(``tests/benchmark/bench_smoke.example_context``), so a PR adds a
metric with three new things and no edit: the reader, its example, its
entry appended to ``per_layer``.  No other data file lives here: an
entry is registered, or it is not in the tree.
"""
