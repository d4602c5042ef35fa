"""kernels - ops/pallas/roi_align_kernel.py: the least time the chip
could take for ROIAlign's own work (box and mask, forward and
backward; the larger of bytes over HBM bandwidth and operations over
peak, per call, from benchmark/flops.py) over the device time of every
``tpu_custom_call`` event of the traced steps.  One number for all the
kernels: their call sites carry no name yet."""

from benchmark import flops


def bound_seconds(ctx):
    """(seconds per step per chip, {"bytes" | "ops": seconds})."""
    by = {"bytes": 0.0, "ops": 0.0}
    for call in flops.roi_align_calls(ctx.spec, *ctx.spec["canvas"],
                                      itemsize=ctx.feature_itemsize):
        t_b = call["bytes"] / ctx.peak["hbm_bytes_per_s"]
        t_o = call["ops"] / ctx.peak["bf16_flops_per_s"]
        by["bytes" if t_b >= t_o else "ops"] += max(t_b, t_o)
    scale = ctx.images_per_step / ctx.chips
    return sum(by.values()) * scale, {k: v * scale for k, v in by.items()}


def read(ctx):
    t = ctx.trace
    if t is None or not t.custom_call_events or not ctx.traced_steps:
        return None
    need, _ = bound_seconds(ctx)
    return 100.0 * need * ctx.traced_steps / t.custom_call_s
