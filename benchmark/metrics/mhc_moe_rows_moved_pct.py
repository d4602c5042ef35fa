"""model - eksml_tpu/models/lm: rows the expert layers' two permutations
copy, as a share of the T·k rows of their sorted buffers (the step's
``moe_rows_moved_share`` counter), in the hyper-connected task's cell:
``moe_rows_moved_pct``'s reader, for the cell its closed list does not
name."""

from benchmark.metrics.moe_rows_moved_pct import read  # noqa: F401
