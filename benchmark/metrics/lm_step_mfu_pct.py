"""trainer - Trainer.fit, _train_step: the sequence task's whole step
as a share of the chip's peak.  Operations forward and backward REQUIRE
per row (``tasks/lm.py`` ``train_ops_per_row``, from
``benchmark/lm_flops.py``: every matrix product a token meets, the
routed ones by the mean held pairs, causal attention at half the
square, x 3, no recompute) times the window's rows per second, over
peak bf16 FLOP/s: ``step_mfu_pct``'s arithmetic, for the cells its
closed list does not name."""

from benchmark.metrics.step_mfu_pct import read  # noqa: F401
