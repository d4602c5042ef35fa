"""trainer - Trainer.fit, _train_step: the device's idle share of the
measured window on the host's clock (the fit's edges and each log
step's drained stretch), in the hyper-connected task's cell:
``window_idle_pct``'s reader, for the cell its closed list does not
name."""

from benchmark.metrics.window_idle_pct import read  # noqa: F401
