"""trainer - Trainer.fit, _train_step: the window-and-experts task's
whole step as a share of the chip's peak.  Operations forward and
backward REQUIRE per row (``tasks/swa_moe.py`` ``train_ops_per_row``,
from ``benchmark/swa_moe_flops.py``: every projection, the router, the
shared expert and one held pair a token under uniform routing, each
core under its own mask, the head over the held rows; x 3; no
recompute) times the window's rows per second, over peak bf16 FLOP/s:
``step_mfu_pct``'s arithmetic, for the cell its closed list does not
name (PERF.md section 7, U(a))."""

from benchmark.metrics.step_mfu_pct import read  # noqa: F401
