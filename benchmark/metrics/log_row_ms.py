"""trainer - Trainer.fit, _train_step: the log row's host work a log
step, while the device sits drained: mean duration of the window's
``log_row`` spans (the model's counter spans, the host aggregate,
``goodput_publish``, ``write_scalars``, ``hbm_gauges`` and the log
line; after ``host_metrics``, before the next dispatch)."""


def read(ctx):
    rows = [ev["dur"] for ev in ctx.spans if ev.get("name") == "log_row"]
    return sum(rows) / 1e3 / len(rows) if rows else None
