"""kernels - ops/pallas/roi_align_kernel.py: the share of a 64 x 64
accumulator tile the backward kernel moves and multiplies per ROI, as a
percentage (100 = every ROI takes every strip of its tile; the kernel before
PR 29 moved the whole tile by construction).  Mean over the window's
``roi_bwd_strips`` spans, which carry the step's
``roi_bwd_tile_share`` (box and mask ROIs, weighted by count) as
``args`` at log steps.  Reported in both detector cells since PR 32
(``frcnn-r50-train-1344-b4`` alone before: an accepted test's hand-made
context lacked the span the mask cell's program writes too)."""


def read(ctx):
    values = [ev["args"]["roi_bwd_tile_share"] for ev in ctx.spans
              if ev.get("name") == "roi_bwd_strips"
              and "roi_bwd_tile_share" in ev.get("args", {})]
    return 100.0 * sum(values) / len(values) if values else None
