"""lm_kernels - models/lm attention.py, moe.py: jax's splash-attention
forward kernels in the window-and-experts task's cell: full causal and
512-window cores over grouped key-value heads (``splash_mqa_fwd*``; a
configuration with as many key-value heads as query heads would run
``splash_mha_fwd*``).  The required work is counted from the SPEC,
each layer under its own mask (``benchmark/swa_moe_flops.py``: a
window's scores are ``sum_i min(i + 1, 512)``, not ``S x 512``): the
roofline seconds of every held layer's core over one row x the rows a
chip holds x the traced steps, over the device time of those
instructions in the traced steps: the ``loop_`` pair's way, so a
forward recomputed under remat reads as the time it costs (at most
50%) and no reading can pass 100%.  The window's and the full cores'
instructions carry one name, so one number covers both (PERF.md
section 7, T1).  Nothing to read where the step has no such
instruction."""

from benchmark import swa_moe_flops
from benchmark.metrics.loop_splash_mha_fwd_roofline_pct import kernel_seconds

FORWARD = ("splash_mqa_fwd", "splash_mha_fwd")


def required_seconds(ctx, forwards: float):
    """Roofline seconds of ``forwards`` forward-equivalents of every
    held layer's core over the traced steps (backward = 2 forwards of
    operations; bytes scale alike)."""
    rows = ctx.images_per_step / ctx.chips
    seq = ctx.spec["seq_len"]
    cores = sum(swa_moe_flops.attention_core_seconds(
        ctx.spec, layer, seq, ctx.feature_itemsize, ctx.peak)
        for layer in swa_moe_flops.layers(ctx.spec))
    return forwards * cores * rows * ctx.traced_steps


def read(ctx):
    spent = kernel_seconds(ctx, FORWARD)
    if not spent or not ctx.traced_steps:
        return None
    return 100.0 * required_seconds(ctx, 1.0) / spent
