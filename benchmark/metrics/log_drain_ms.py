"""trainer - Trainer.fit, _train_step: how long the device sits drained
at a log step.  At a log step the step loop waits for the device to
finish the step (its ``loss_sync`` span; ``host_metrics`` where the
sentinel does not observe that step), writes the log row, and only then
dispatches the next step: from the end of that wait to the end of the
next ``train_step`` span (``args.step`` = s + 1) the device has nothing
of this fit queued.  Mean over the window's log steps; a log step with
no following dispatch (the fit ended there) is left out."""


def drains_s(ctx):
    """Seconds of each of the window's log steps' drained stretches."""
    dispatched = {ev["args"]["step"]: ev["ts"] + ev["dur"]
                  for ev in ctx.spans if ev.get("name") == "train_step"
                  and "step" in ev.get("args", {})}
    waits = {}
    for name in ("host_metrics", "loss_sync"):       # the sync wins
        waits.update({ev["args"]["step"]: ev["ts"] + ev["dur"]
                      for ev in ctx.spans if ev.get("name") == name
                      and "step" in ev.get("args", {})})
    logged = {ev["args"]["step"] for ev in ctx.spans
              if ev.get("name") == "host_metrics"
              and "step" in ev.get("args", {})}
    return [(dispatched[s + 1] - waits[s]) / 1e6 for s in sorted(logged)
            if s + 1 in dispatched]


def read(ctx):
    drains = drains_s(ctx)
    return 1e3 * sum(drains) / len(drains) if drains else None
