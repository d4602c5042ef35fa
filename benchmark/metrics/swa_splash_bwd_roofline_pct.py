"""lm_kernels - models/lm attention.py, moe.py: jax's splash-attention
backward kernels in the window-and-experts task's cell:
``splash_mqa_dkv*`` (fused, it gives dq too) and ``splash_mqa_dq*``
where the program runs the two apart (``splash_mha_*`` with ungrouped
heads).  Backward REQUIRES twice forward's operations; the kernels' own
recomputation of the scores is not counted.  Required work from the
spec, each layer under its own mask, as
``swa_splash_fwd_roofline_pct`` counts it, over the device time of
those kernels in the traced steps."""

from benchmark.metrics.loop_splash_mha_fwd_roofline_pct import kernel_seconds
from benchmark.metrics.swa_splash_fwd_roofline_pct import required_seconds

BACKWARD = ("splash_mqa_dkv", "splash_mqa_dq", "splash_mha_dkv",
            "splash_mha_dq")


def read(ctx):
    spent = kernel_seconds(ctx, BACKWARD)
    if not spent or not ctx.traced_steps:
        return None
    return 100.0 * required_seconds(ctx, 2.0) / spent
