"""lm_kernels - models/lm attention.py, moe.py: XLA's grouped matrix
product (``jax.lax.ragged_dot`` -> the instructions named
``ragged-dot-none*``) over the held experts, in the window-and-experts
task's cell.  ``moe_ragged_dot_roofline_pct``'s arithmetic (each call
multiplies the pairs held in its layer through one bank of the held
experts' matrices; the larger of operations over peak and the bank
plus the activations over HBM bandwidth; per call site, over the device
time of those instructions in the traced steps), with the expert
layers and the bank's shape taken from this configuration's own keys
(``benchmark/swa_moe_flops.py``: ``mlp_layer_types``, ``num_experts``).
Nothing to read without the counter or the instructions."""

from benchmark import swa_moe_flops
from benchmark.metrics.moe_load_max_over_mean import counter_mean
from benchmark.metrics.splash_mha_fwd_roofline_pct import kernel_calls

GROUPED = ("ragged-dot-none",)


def read(ctx):
    spent, calls = kernel_calls(ctx, GROUPED)
    pairs = counter_mean(ctx, "moe_pairs_held")
    if not spent or not ctx.traced_steps or pairs is None:
        return None
    call = swa_moe_flops.grouped_product_call(
        ctx.spec, pairs / swa_moe_flops.expert_layers(ctx.spec),
        ctx.feature_itemsize)
    need = max(call["ops"] / ctx.peak["bf16_flops_per_s"],
               call["bytes"] / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * need * calls * ctx.traced_steps / spent
