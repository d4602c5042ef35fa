"""trainer - Trainer.fit, _train_step: the hyper-connected task's whole
step as a share of the chip's peak.  Operations forward and backward
REQUIRE per row (``tasks/mhc_lm.py`` ``train_ops_per_row``, from
``benchmark/mhc_flops.py``: MLA's projections, the dense layer, the
router, the shared expert and one held pair in two a token under
uniform routing, the hyper-connections' projections and mixes, one
causal core a layer, the head over the held rows; x 3; no recompute)
times the window's rows per second, over peak bf16 FLOP/s:
``step_mfu_pct``'s arithmetic, for the cell its closed list does not
name (PERF.md section 7, U(a))."""

from benchmark.metrics.step_mfu_pct import read  # noqa: F401
