"""A smoke-width cell of the window-and-experts task for the CPU tests:
the real cell's task, metrics and optimizer at
``config.LAGUNA_TINY_OVERRIDES`` widths in float32 (hidden 64, 4 and 6
query heads over 2 key-value heads of 16, a dense layer and two expert
layers of 8 experts with 4 held, a window of 24 over S 64, 96 vocabulary
rows).  Never a device number."""

import bench_smoke

CELL = "laguna-xs2-train-8k-ep8"

TINY_ROPE = {
    "full_attention": {
        "rope_theta": 100, "rope_type": "yarn", "factor": 4,
        "original_max_position_embeddings": 32, "beta_slow": 1,
        "beta_fast": 4, "attention_factor": 1.1386,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1.0},
}
TINY_MODEL = {
    "hidden_size": 64, "head_dim": 16, "num_key_value_heads": 2,
    "num_attention_heads": 4, "num_hidden_layers": 3,
    "num_attention_heads_per_layer": [4, 6, 4],
    "layer_types": ["full_attention", "sliding_attention",
                    "full_attention"],
    "mlp_layer_types": ["dense", "sparse", "sparse"],
    "sliding_window": 24, "rope_parameters": TINY_ROPE,
    "intermediate_size": 160, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 2, "layers_held": 3, "experts_held": [0, 4],
    "vocab_rows": 96, "vocab_size": 96, "seq_len": 64,
}
TINY_MIX = {"name": "tiny-rows", "rows_per_chip": 2, "seq_len": 64,
            "doc_len_median": 24.0, "doc_len_sigma": 1.2,
            "doc_len_clip": [4, 256], "zipf_exponent": 1.0, "eod_id": 1}

# float32 on both sides from bit-equal weights: what is left is the
# order of summation (blockwise against full-score attention, the
# grouped product against one expert after another, chunked against
# row-wise logits); a top-2 that falls differently would show in the
# worst leaf, and none does at these seeds (seen: losses under 3e-7,
# leaf measures under 3e-5, the direction number under 3e-6)
TINY_LIMITS = {"loss_step1": 1e-5, "loss_step2": 1e-5, "loss_step3": 1e-5,
               "first_grad_worst_leaf": 1e-3, "first_grad_median_leaf": 1e-4,
               "first_grad_direction_median_leaf": 1e-4,
               "delta3_worst_leaf": 1e-2, "delta3_median_leaf": 1e-3,
               "frozen_moved": 0.0}


def smoke_cell(limits=None, extra_overrides=()):
    from benchmark import harness
    from eksml_tpu.config import LAGUNA_TINY_OVERRIDES

    real = harness.load_cell(bench_smoke.ROOT, CELL)
    config = dict(
        real.config,
        model=dict(real.config["model"], **TINY_MODEL),
        precision="float32", batch_per_chip=2,
        overrides=[o for o in real.config["overrides"]
                   if not o.startswith(("TRAIN.PRECISION", "LM.",
                                        "TRAIN.LOG_PERIOD"))]
        + list(LAGUNA_TINY_OVERRIDES) + ["TRAIN.LOG_PERIOD=2"]
        + list(extra_overrides))
    workload = {"name": "swa-moe-smoke", "config": real.config["name"],
                "chips": 1, "traffic": dict(TINY_MIX), "warmup_steps": 4,
                "follow_steps": 3, "trace_steps": 3,
                "limits": dict(TINY_LIMITS if limits is None else limits)}
    return harness.Cell(name="swa-moe-smoke", chips=1, config=config,
                        workload=workload, task=real.task,
                        end_to_end=real.end_to_end,
                        per_layer=real.per_layer)
