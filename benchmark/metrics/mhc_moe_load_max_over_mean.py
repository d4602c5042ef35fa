"""model - eksml_tpu/models/lm: the busiest held expert's pairs over
the mean held expert's, worst expert layer of the step, in the
hyper-connected task's cell: mean over the window's ``moe_route``
spans.  ``moe_load_max_over_mean``'s reader, for the cell its list does
not name."""

from benchmark.metrics.moe_load_max_over_mean import read  # noqa: F401
