"""kernels - ops/pallas/roi_align_kernel.py: the forward kernel alone.
The least time the chip could take for ROIAlign's forward passes (box
and mask; the larger of bytes over HBM bandwidth and operations over
peak, per call, from benchmark/flops.py) over the device time of the
instructions the program names ``roi_align_fwd`` in the traced steps.
Nothing to read where the program's kernels carry no name."""

from benchmark import flops


def kernel_seconds(ctx, kernels):
    """Device seconds, per chip, of the traced steps' instructions
    named ``<kernel>.<n>`` for a kernel in ``kernels``."""
    if ctx.trace is None:
        return 0.0
    return sum(s for name, s in ctx.trace.op_seconds.items()
               if name.split(".", 1)[0] in kernels)


def pass_roofline_pct(ctx, direction, kernels):
    """Share of the roofline of the ``direction`` ("forward" or
    "backward") passes of ``flops.roi_align_calls``, run by the
    instructions named for ``kernels``; None where there are none."""
    spent = kernel_seconds(ctx, kernels)
    if not spent or not ctx.traced_steps:
        return None
    need = sum(max(call["bytes"] / ctx.peak["hbm_bytes_per_s"],
                   call["ops"] / ctx.peak["bf16_flops_per_s"])
               for call in flops.roi_align_calls(
                   ctx.spec, *ctx.spec["canvas"],
                   itemsize=ctx.feature_itemsize)
               if call["pass"] == direction)
    need *= ctx.images_per_step / ctx.chips
    return 100.0 * need * ctx.traced_steps / spent


def read(ctx):
    return pass_roofline_pct(ctx, "forward", ("roi_align_fwd",))
