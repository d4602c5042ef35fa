"""model - eksml_tpu/models/lm: token-expert pairs a held expert
computes per step (the step's ``moe_pairs_held`` counter over expert
layers x held experts): how near the experts' load is to the
deployment's, where each would see batch x k / routed pairs of a batch
16 times this chip's."""

from benchmark.metrics.moe_load_max_over_mean import counter_mean


def expert_layers(spec):
    return (spec["layers_held"] - spec["first_k_dense_replace"]
            + spec["num_nextn_predict_layers"])


def read(ctx):
    pairs = counter_mean(ctx, "moe_pairs_held")
    if pairs is None:
        return None
    return pairs / (expert_layers(ctx.spec) * ctx.spec["experts_held"][1])
