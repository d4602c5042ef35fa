"""trainer - Trainer.fit, _train_step: the window's fit's head and tail,
in which the device has nothing of this fit to run, in the
hyper-connected task's cell: ``fit_edges_ms``'s reader, for the cell
its closed list does not name."""

from benchmark.metrics.fit_edges_ms import read  # noqa: F401
