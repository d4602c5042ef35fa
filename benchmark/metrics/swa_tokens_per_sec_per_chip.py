"""trainer - Trainer.fit, _train_step: tokens trained per second per
chip in the window, in the window-and-experts task's cell: rows per
second (the end-to-end metric) times the row's 8,192 positions.
``lm_tokens_per_sec_per_chip``'s reader, for the cell its list does not
name."""

from benchmark.metrics.lm_tokens_per_sec_per_chip import read  # noqa: F401
