"""input - eksml_tpu/data/loader.py: how long the step loop waited for
its next batch, per step.  Reads the program's own ``data_wait`` spans
(telemetry span ring) of the window's steps; the last span, which ends
the window's iterator, is left out."""


def read(ctx):
    waits = [ev["dur"] for ev in ctx.spans if ev.get("name") == "data_wait"]
    if len(waits) < 2:
        return None
    waits = waits[:-1]
    return sum(waits) / 1e3 / len(waits)
