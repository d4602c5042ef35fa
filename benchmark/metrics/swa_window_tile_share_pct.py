"""lm_kernels - models/lm attention.py, moe.py: of the score entries
the sliding layers' attention kernels compute, the share their 512
window lets through, as a percentage (100 = no masked score is
computed): ``attention.window_tile_share``, from the kernel's own block
table at its block sizes (a block of 1,024 queries under a 512 window
visits two blocks of 1,024 keys, most of them masked).  Informative, as
``roi_fwd_tile_share_pct``: a change to the kernel's block sizes moves
it, the traffic does not.  Mean over the window's ``attn_window``
spans, which carry the step's ``window_tile_share`` as ``args`` at log
steps; a program without the span gives nothing."""


def read(ctx):
    values = [ev["args"]["window_tile_share"] for ev in ctx.spans
              if ev.get("name") == "attn_window"
              and "window_tile_share" in ev.get("args", {})]
    return 100.0 * sum(values) / len(values) if values else None
