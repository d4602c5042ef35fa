"""detector_model - eksml_tpu/models: how many of the RPN box term's slots
an image fills.  Since PR 34 ``rpn.rpn_losses`` forms the box-regression
term on ``sample_anchors``' foreground draw alone,
``int(RPN.BATCH_PER_IM * RPN.FG_RATIO)`` = 128 anchor rows an image
whatever the draw holds; ``rpn_fg_rows`` is the batch mean of the slots
that are real picks (128 = every image had at least 128 foreground
anchors; COCO images mostly fill a fraction).  Informative: the term's
time does not depend on it.  Mean over the window's ``rpn_targets``
spans, which carry the step's counter as ``args`` at log steps.  A
program without the counter (PR 33's and older) gives nothing."""


def read(ctx):
    values = [ev["args"]["rpn_fg_rows"] for ev in ctx.spans
              if ev.get("name") == "rpn_targets"
              and "rpn_fg_rows" in ev.get("args", {})]
    return sum(values) / len(values) if values else None
