"""trainer - Trainer.fit, _train_step: tokens trained per second per
chip in the window: rows per second (the end-to-end metric, under the
name the manifest gives every training cell) times the row's sequence
length."""


def read(ctx):
    if not ctx.images_per_sec_per_chip or "seq_len" not in ctx.spec:
        return None
    return ctx.images_per_sec_per_chip * ctx.spec["seq_len"]
