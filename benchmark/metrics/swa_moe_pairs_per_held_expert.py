"""model - eksml_tpu/models/lm: token-expert pairs a held expert
computes per step in the window-and-experts task's cell (the step's
``moe_pairs_held`` counter over expert layers x held experts): how near
the experts' load is to the deployment's, where each would see the
pairs of a batch 8 times this chip's.  Not
``moe_pairs_per_held_expert``'s reader: that counts the expert layers
from ``first_k_dense_replace`` and ``num_nextn_predict_layers``, keys
this configuration's ``config.json`` does not have; here they are the
``sparse`` entries of ``mlp_layer_types`` among the held layers
(``benchmark/swa_moe_flops.py``)."""

from benchmark import swa_moe_flops
from benchmark.metrics.moe_load_max_over_mean import counter_mean


def read(ctx):
    pairs = counter_mean(ctx, "moe_pairs_held")
    if pairs is None:
        return None
    return pairs / (swa_moe_flops.expert_layers(ctx.spec)
                    * ctx.spec["experts_held"][1])
