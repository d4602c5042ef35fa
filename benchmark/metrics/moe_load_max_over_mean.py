"""model - eksml_tpu/models/lm: the busiest held expert's pairs over
the mean held expert's, worst expert layer of the step (1 = even
load).  Mean over the window's ``moe_route`` spans, which carry the
step's routing counters as ``args`` at log steps."""


def counter_mean(ctx, key):
    """Mean of ``args[key]`` over the window's ``moe_route`` spans, or
    None where the program wrote none."""
    values = [ev["args"][key] for ev in ctx.spans
              if ev.get("name") == "moe_route" and key in ev.get("args", {})]
    return sum(values) / len(values) if values else None


def read(ctx):
    return counter_mean(ctx, "moe_load_max_over_mean")
