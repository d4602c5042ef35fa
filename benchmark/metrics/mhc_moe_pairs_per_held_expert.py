"""model - eksml_tpu/models/lm: token-expert pairs a held expert
computes per step in the hyper-connected task's cell (the step's
``moe_pairs_held`` counter over expert layers x held experts): how near
the experts' load is to the deployment's, where each would see the
pairs of a batch 8 times this chip's.  Not
``moe_pairs_per_held_expert``'s reader: that counts the expert layers
from ``first_k_dense_replace`` and ``num_nextn_predict_layers``, which
this configuration keeps as published (2 and 1); here they are the held
layers less the held dense ones (``benchmark/mhc_flops.py``)."""

from benchmark import mhc_flops
from benchmark.metrics.moe_load_max_over_mean import counter_mean


def read(ctx):
    pairs = counter_mean(ctx, "moe_pairs_held")
    if pairs is None:
        return None
    return pairs / (mhc_flops.expert_layers(ctx.spec)
                    * ctx.spec["experts_held"][1])
