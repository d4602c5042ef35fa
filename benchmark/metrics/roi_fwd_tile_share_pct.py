"""kernels - ops/pallas/roi_align_kernel.py: the share of a 64 x 64
feature tile the forward kernel reads and multiplies per ROI, as a
percentage (100 = every ROI takes every strip of its tile; the kernel before
PR 31 read the whole tile by construction).  Mean over the window's
``roi_bwd_strips`` spans, which carry the step's
``roi_fwd_tile_share`` (box and mask ROIs, weighted by count) as
``args`` at log steps, beside the backward's ``roi_bwd_tile_share``.  A
program without the counter (PR 30's and older) gives nothing.
Registered by PR 32, in both detector cells (the mask cell's ROIs are
the box head's 7 x 7 and the mask head's 14 x 14 together)."""


def read(ctx):
    values = [ev["args"]["roi_fwd_tile_share"] for ev in ctx.spans
              if ev.get("name") == "roi_bwd_strips"
              and "roi_fwd_tile_share" in ev.get("args", {})]
    return 100.0 * sum(values) / len(values) if values else None
