"""lm_kernels - models/lm attention.py, moe.py: jax's splash-attention
forward kernel.  The least time the chip could take for one causal
attention core over the step's rows (the larger of operations over
peak and bytes over HBM bandwidth, ``benchmark/lm_flops.py``), per
call, over the device time of the instructions named
``splash_mha_fwd*`` in the traced steps.  A call site is one
instruction; under remat each layer's forward runs twice, and each run
is a call.  Nothing to read where the step has no such instruction."""

from benchmark import lm_flops

FORWARD = ("splash_mha_fwd",)


def kernel_calls(ctx, prefixes):
    """(device seconds per chip over the traced steps, call sites) of
    the instructions whose name starts with one of ``prefixes``."""
    if ctx.trace is None:
        return 0.0, 0
    found = [s for name, s in ctx.trace.op_seconds.items()
             if name.startswith(prefixes)]
    return sum(found), len(found)


def core_need_seconds(ctx, passes: float):
    """Roofline seconds of ``passes`` forward-equivalents of one core
    over the rows a chip holds (backward = 2 forwards of operations;
    bytes scale alike: each pass reads and writes the operands once)."""
    rows = ctx.images_per_step / ctx.chips
    seq = ctx.spec["seq_len"]
    ops = lm_flops.attention_core_forward_ops(ctx.spec, seq)
    moved = lm_flops.attention_core_forward_bytes(
        ctx.spec, seq, ctx.feature_itemsize)
    return rows * passes * max(ops / ctx.peak["bf16_flops_per_s"],
                               moved / ctx.peak["hbm_bytes_per_s"])


def read(ctx):
    spent, calls = kernel_calls(ctx, FORWARD)
    if not spent or not ctx.traced_steps:
        return None
    return 100.0 * core_need_seconds(ctx, 1.0) * calls \
        * ctx.traced_steps / spent
