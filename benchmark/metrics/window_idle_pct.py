"""trainer - Trainer.fit, _train_step: the device's idle share of the
measured window, on the host's clock that the ``device_step`` stamps
share with every span: the fit's head and tail (``fit_edges_ms``) and
each log step's drained stretch (``log_drain_ms``), over the window's
seconds.  ``device_idle_pct`` reads another stretch (the traced one
after the window) on the device's clock."""

from benchmark.metrics.fit_edges_ms import edges_s
from benchmark.metrics.log_drain_ms import drains_s


def read(ctx):
    edges = edges_s(ctx)
    if edges is None or not ctx.window_s > 0:
        return None
    return 100 * (sum(edges) + sum(drains_s(ctx))) / ctx.window_s
