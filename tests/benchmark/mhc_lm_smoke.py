"""A smoke-width cell of the hyper-connected task for the CPU tests: the
real cell's task, metrics and optimizer at ``config.XING_TINY_OVERRIDES``
widths in float32 (hidden 64 on 4 streams, 4 heads of MLA over latent
widths 48 / 32, YaRN over an original context of 16, a dense layer and
two expert layers of 8 experts with 4 held, S 64, 96 vocabulary rows).
Never a device number."""

import bench_smoke

CELL = "xing4-29b-train-4k-ep8"

TINY_MODEL = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 4,
                     "original_max_position_embeddings": 16,
                     "beta_fast": 4, "beta_slow": 1, "mscale": 1.0,
                     "mscale_all_dim": 1.0},
    "intermediate_size": 160, "moe_intermediate_size": 32,
    "n_routed_experts": 8, "num_experts_per_tok": 2,
    "routed_scaling_factor": 2.0, "num_hidden_layers": 3,
    "layers_held": 3, "dense_layers_held": 1, "experts_held": [0, 4],
    "vocab_rows": 96, "vocab_size": 96, "seq_len": 64,
    "max_position_embeddings": 64, "hc_phi_std": 0.5 * 256 ** -0.5,
}
TINY_MIX = {"name": "tiny-rows", "rows_per_chip": 2, "seq_len": 64,
            "doc_len_median": 24.0, "doc_len_sigma": 1.2,
            "doc_len_clip": [4, 256], "zipf_exponent": 1.0, "eod_id": 1}

# float32 on both sides from bit-equal weights: what is left is the
# order of summation (blockwise against full-score attention, the
# grouped product against one expert after another, chunked against
# whole logits, a fused stream mix against an einsum); seen: losses
# under 2e-7, leaf measures under 2e-5, the direction number under 1e-5
TINY_LIMITS = {"loss_step1": 1e-5, "loss_step2": 1e-5, "loss_step3": 1e-5,
               "first_grad_worst_leaf": 1e-3, "first_grad_median_leaf": 1e-4,
               "first_grad_direction_median_leaf": 1e-4,
               "delta3_worst_leaf": 1e-2, "delta3_median_leaf": 1e-3,
               "delta3_worst_leaf_past_copies": 1e-2,
               "frozen_moved": 0.0}


def smoke_cell(limits=None, extra_overrides=()):
    from benchmark import harness
    from eksml_tpu.config import XING_TINY_OVERRIDES

    real = harness.load_cell(bench_smoke.ROOT, CELL)
    config = dict(
        real.config,
        model=dict(real.config["model"], **TINY_MODEL),
        precision="float32", batch_per_chip=2,
        overrides=[o for o in real.config["overrides"]
                   if not o.startswith(("TRAIN.PRECISION", "LM.",
                                        "TRAIN.BATCH_SIZE_PER_CHIP"))]
        + list(XING_TINY_OVERRIDES)
        + ["TRAIN.BATCH_SIZE_PER_CHIP=2", "TRAIN.LOG_PERIOD=2"]
        + list(extra_overrides))
    workload = {"name": "mhc-lm-smoke", "config": real.config["name"],
                "chips": 1, "traffic": dict(TINY_MIX), "warmup_steps": 4,
                "follow_steps": 3, "trace_steps": 3,
                "limits": dict(TINY_LIMITS if limits is None else limits)}
    return harness.Cell(name="mhc-lm-smoke", chips=1, config=config,
                        workload=workload, task=real.task,
                        end_to_end=real.end_to_end,
                        per_layer=real.per_layer)
