"""trainer - Trainer.fit, _train_step: tokens trained per second per
chip in the hyper-connected task's cell: ``lm_tokens_per_sec_per_chip``'s
reader (rows per second times the row's 4,096 tokens), for the cell its
list does not name."""

from benchmark.metrics.lm_tokens_per_sec_per_chip import read  # noqa: F401
