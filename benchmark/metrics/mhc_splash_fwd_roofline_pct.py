"""lm_kernels - models/lm attention.py, moe.py: jax's splash-attention
forward kernel over MLA's 32 heads (q and k 192 wide, v 128) in the
hyper-connected task's cell.  The required work is counted from the
SPEC, the ``loop_`` pair's way: the roofline seconds of one causal core
over one row (``benchmark/mhc_flops.py``) x the rows a chip holds x one
core a held layer x the traced steps, over the device time of the
instructions named ``splash_mha_fwd*`` in those steps.  A forward
recomputed under remat therefore reads as the time it costs (at most
50%), and no reading can pass 100%.  Nothing to read where the step has
no such instruction."""

from benchmark import mhc_flops
from benchmark.metrics.loop_splash_mha_fwd_roofline_pct import kernel_seconds

FORWARD = ("splash_mha_fwd",)


def required_seconds(ctx, forwards: float):
    """Roofline seconds of ``forwards`` forward-equivalents of every
    held layer's core over the traced steps (backward = 2 forwards of
    operations; bytes scale alike)."""
    rows = ctx.images_per_step / ctx.chips
    core = mhc_flops.attention_core_seconds(
        ctx.spec, ctx.spec["seq_len"], ctx.feature_itemsize, ctx.peak)
    return (forwards * core * rows * ctx.spec["layers_held"]
            * ctx.traced_steps)


def read(ctx):
    spent = kernel_seconds(ctx, FORWARD)
    if not spent or not ctx.traced_steps:
        return None
    return 100.0 * required_seconds(ctx, 1.0) / spent
