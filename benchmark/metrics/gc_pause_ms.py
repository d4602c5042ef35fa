"""trainer - Trainer.fit, _train_step: Python's garbage collector, in
milliseconds a step: the ``gc_ms`` the window's ``log_row`` spans carry
(every collection on any thread since the previous row, timed by the
traced fit's ``gc.callbacks`` hook), over the steps they cover (their
``steps``)."""


def read(ctx):
    rows = [ev["args"] for ev in ctx.spans if ev.get("name") == "log_row"
            and {"gc_ms", "steps"} <= set(ev.get("args", {}))]
    steps = sum(r["steps"] for r in rows)
    return sum(r["gc_ms"] for r in rows) / steps if steps > 0 else None
