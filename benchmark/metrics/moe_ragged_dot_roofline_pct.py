"""lm_kernels - models/lm attention.py, moe.py: XLA's grouped matrix
product (``jax.lax.ragged_dot`` -> the instructions named
``ragged-dot-none*``) over the held experts.  Each call, forward,
input-gradient or weight-gradient, multiplies the pairs held in its
layer (the step's ``moe_pairs_held`` counter over the expert layers,
from the window's ``moe_route`` spans) through one bank of the held
experts' matrices; its roofline is the larger of those operations over
peak and the bank plus the activations over HBM bandwidth
(``benchmark/lm_flops.py``).  Over the device time of those
instructions in the traced steps; nothing to read without the counter
or the instructions."""

from benchmark import lm_flops
from benchmark.metrics.moe_load_max_over_mean import counter_mean
from benchmark.metrics.moe_pairs_per_held_expert import expert_layers
from benchmark.metrics.splash_mha_fwd_roofline_pct import kernel_calls

GROUPED = ("ragged-dot-none",)


def read(ctx):
    spent, calls = kernel_calls(ctx, GROUPED)
    pairs = counter_mean(ctx, "moe_pairs_held")
    if not spent or not ctx.traced_steps or pairs is None:
        return None
    call = lm_flops.grouped_product_call(
        ctx.spec, pairs / expert_layers(ctx.spec), ctx.feature_itemsize)
    need = max(call["ops"] / ctx.peak["bf16_flops_per_s"],
               call["bytes"] / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * need * calls * ctx.traced_steps / spent
