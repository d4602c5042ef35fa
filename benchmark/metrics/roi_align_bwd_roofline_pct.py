"""kernels - ops/pallas/roi_align_kernel.py: the backward kernel, with
the HBM seed copy that exists only to hand it its accumulators.  The
least time the chip could take for ROIAlign's backward passes (read the
output gradient, write the dense gradient of the four feature levels;
benchmark/flops.py) over the device time of the instructions the
program names ``roi_align_bwd`` and ``roi_align_seed_copy`` in the
traced steps."""

from benchmark.metrics.roi_align_fwd_roofline_pct import pass_roofline_pct


def read(ctx):
    return pass_roofline_pct(ctx, "backward",
                             ("roi_align_bwd", "roi_align_seed_copy"))
