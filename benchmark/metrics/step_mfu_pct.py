"""trainer - Trainer.fit, _train_step: the whole step's share of the
chip's peak.  Operations forward and backward REQUIRE per row of the
batch (the cell's task: ``train_ops_per_row``, from shapes; no remat
recompute) times the window's rows per second (an image in the
detection task), over chips x peak bf16 FLOP/s."""


def read(ctx):
    if not ctx.images_per_sec_per_chip:
        return None
    ops = ctx.task.train_ops_per_row(ctx.spec)
    return 100.0 * ops * ctx.images_per_sec_per_chip / ctx.peak[
        "bf16_flops_per_s"]
