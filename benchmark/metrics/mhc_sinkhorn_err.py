"""model - eksml_tpu/models/lm: how far the hyper-connections'
stream-to-stream maps stand from doubly stochastic after the Sinkhorn
iterations, in the hyper-connected task's cell: the largest ``|row or
column sum - 1|`` of any ``H_res`` over the step's sublayers and
positions (the step's ``mhc_sinkhorn_err`` counter), worst over the
window's ``mhc`` spans.  **Informative**, as ``loop_expected_exit_pass``
is: it guards the projection against a change that shortens it or
lowers its precision, and cannot move rows/s; ``moves`` and ``better``
are what the manifest's form asks of every entry.  At the init's
logits (standard deviation one half) 20 iterations leave 1.2e-6 and
under; the window's log steps read 7.6e-4 on a TPU v5e, once the first
updates have spread the logits (PERF.md section 6)."""


def span_values(ctx, key):
    """``args[key]`` of the window's ``mhc`` spans (the step's counters
    at log steps), in order; empty where the program wrote none."""
    return [ev["args"][key] for ev in ctx.spans
            if ev.get("name") == "mhc" and key in ev.get("args", {})]


def read(ctx):
    values = span_values(ctx, "mhc_sinkhorn_err")
    return max(values) if values else None
