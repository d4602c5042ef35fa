"""trainer - Trainer.fit, _train_step: the looped-stack task's whole
step as a share of the chip's peak.  Operations forward and backward
REQUIRE per row (``tasks/looplm.py`` ``train_ops_per_row``, from
``benchmark/looplm_flops.py``: every pass pays for every held block, the
head over the whole vocabulary and the gate; causal attention at half
the square; x 3; no recompute) times the window's rows per second, over
peak bf16 FLOP/s: ``step_mfu_pct``'s arithmetic, for the cell its closed
list does not name (PERF.md section 7, U(a))."""

from benchmark.metrics.step_mfu_pct import read  # noqa: F401
