"""trainer - Trainer.fit, _train_step: tokens trained per second per
chip in the window, in the looped-stack task's cell: rows per second
(the end-to-end metric) times the row's sequence length; a token here
is one position taken through all the passes.
``lm_tokens_per_sec_per_chip``'s reader, for the cell its list does not
name."""

from benchmark.metrics.lm_tokens_per_sec_per_chip import read  # noqa: F401
