"""lm_kernels - models/lm attention.py, moe.py: jax's splash-attention
forward kernel at the looped model's widths (16 heads of 128).  The
required work is counted from the SPEC, not from call sites: the
seconds one causal core needs over one row (the larger of operations
over peak and bytes over HBM bandwidth, ``benchmark/looplm_flops.py``)
x the rows a chip holds x ``total_ut_steps x layers_held`` cores a step
x the traced steps, over the device time of the instructions named
``splash_mha_fwd*`` in those steps.  (The trace's reduction keeps sums
and no counts, and inside a scanned pass one instruction would run
several times.)  A forward recomputed under remat therefore reads as
the time it costs: at most 50%, and no reading can pass 100%.  Nothing
to read where the step has no such instruction."""

from benchmark import looplm_flops

FORWARD = ("splash_mha_fwd",)


def kernel_seconds(ctx, prefixes):
    """Device seconds per chip, over the traced steps, of the
    instructions whose name starts with one of ``prefixes``."""
    if ctx.trace is None:
        return 0.0
    return sum(s for name, s in ctx.trace.op_seconds.items()
               if name.startswith(prefixes))


def required_seconds(ctx, forwards: float):
    """Roofline seconds of ``forwards`` forward-equivalents of every
    core the traced steps require (backward = 2 forwards of operations;
    bytes scale alike)."""
    rows = ctx.images_per_step / ctx.chips
    core = looplm_flops.attention_core_seconds(
        ctx.spec, ctx.spec["seq_len"], ctx.feature_itemsize, ctx.peak)
    return (forwards * core * rows * looplm_flops.attention_cores(ctx.spec)
            * ctx.traced_steps)


def read(ctx):
    spent = kernel_seconds(ctx, FORWARD)
    if not spent or not ctx.traced_steps:
        return None
    return 100.0 * required_seconds(ctx, 1.0) / spent
