"""lm_kernels - models/lm attention.py, moe.py: jax's splash-attention
backward kernels: ``splash_mha_dkv*`` (one per attention core; fused,
it gives dq too) and ``splash_mha_dq*`` where the program runs the two
kernels apart.  Backward REQUIRES twice forward's operations (four
products of the forward's two sizes); the kernels' own recomputation of
the scores is not counted.  Over the device time of those kernels in
the traced steps."""

from benchmark.metrics.splash_mha_fwd_roofline_pct import (
    core_need_seconds, kernel_calls)


def read(ctx):
    dkv, cores = kernel_calls(ctx, ("splash_mha_dkv",))
    dq, _ = kernel_calls(ctx, ("splash_mha_dq",))
    if not dkv or not ctx.traced_steps:
        return None
    return 100.0 * core_need_seconds(ctx, 2.0) * cores \
        * ctx.traced_steps / (dkv + dq)
