"""trainer - Trainer.fit, _train_step: the window's fit's two edges, in
which the device has nothing of this fit to run.  Head: from
``fit_setup``'s start (fit's entry) to the end of the first
``train_step`` (the first dispatch's return).  Tail: from the later of
the last ``device_step``'s end (the device done) and ``fit_teardown``'s
start to ``fit_teardown``'s end, less ``ring_flush`` (the span ring's
write, tracing's own cost: an untraced fit does not pay it;
``ckpt_wait`` and ``writer_flush`` follow it and are counted).  While
the teardown waits for the device (``device_drain``) the device is
busy, not idle."""


def _last(ctx, name, key):
    found = [ev for ev in ctx.spans if ev.get("name") == name]
    return max(found, key=key) if found else None


def edges_s(ctx):
    """(head, tail) in seconds, or None without the fit's edges."""
    setup = _last(ctx, "fit_setup", lambda ev: ev["ts"])
    teardown = _last(ctx, "fit_teardown", lambda ev: ev["ts"])
    dispatches = [ev for ev in ctx.spans if ev.get("name") == "train_step"]
    if setup is None or teardown is None or not dispatches:
        return None
    first = min(dispatches, key=lambda ev: ev["ts"])
    head = first["ts"] + first["dur"] - setup["ts"]
    end = teardown["ts"] + teardown["dur"]
    end -= sum(ev["dur"] for ev in ctx.spans
               if ev.get("name") == "ring_flush"
               and teardown["ts"] <= ev["ts"] <= end)
    done = max([ev["ts"] + ev["dur"] for ev in ctx.spans
                if ev.get("name") == "device_step"] + [teardown["ts"]])
    return head / 1e6, max(0.0, end - done) / 1e6


def read(ctx):
    edges = edges_s(ctx)
    return 1e3 * sum(edges) if edges is not None else None
