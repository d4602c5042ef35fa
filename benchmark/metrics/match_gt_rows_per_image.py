"""detector_model - eksml_tpu/models: ground-truth rows the anchor
matching visits an image.  ``rpn.match_anchors`` computes a row's IoU
against the 451,143 anchors only for the image's valid rows, one row at
a time (``rpn.match_reductions``), of the ``DATA.MAX_GT_BOXES`` = 100
the batch pads to; ``match_gt_rows`` is the batch mean of the rows
visited (a program that forms the dense ``[G, A]`` matrix visits all
100).  Informative: the matching's time follows it, the traffic decides
it.  Mean over the window's ``rpn_targets`` spans, which carry
the step's counter as ``args`` at log steps.  A program without the
counter gives nothing."""


def read(ctx):
    values = [ev["args"]["match_gt_rows"] for ev in ctx.spans
              if ev.get("name") == "rpn_targets"
              and "match_gt_rows" in ev.get("args", {})]
    return sum(values) / len(values) if values else None
