"""trainer - Trainer.fit, _train_step: the whole step's share of the
chip's peak.  Operations forward and backward REQUIRE per image
(benchmark/flops.py, from shapes; no remat recompute) times the
window's images per second, over chips x peak bf16 FLOP/s."""

from benchmark import flops


def read(ctx):
    if not ctx.images_per_sec_per_chip:
        return None
    ops = flops.train_ops_per_image(ctx.spec, *ctx.canvas)
    return 100.0 * ops * ctx.images_per_sec_per_chip / ctx.peak[
        "bf16_flops_per_s"]
