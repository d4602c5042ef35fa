"""A smoke-width cell of the sequence task for the CPU tests: the real
cell's task, metrics and optimizer at ``config.LM_TINY_OVERRIDES``
widths in float32 (hidden 64, S 64, 2 expert layers after the dense
one, 8 experts of which 4 held, 96 vocabulary rows).  Never a device
number."""

import bench_smoke

CELL = "joyai-flash-train-4k-ep16"

TINY_MODEL = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "qk_head_dim": 24, "head_dim": 8,
    "v_head_dim": 16, "intermediate_size": 160,
    "moe_intermediate_size": 32, "n_routed_experts": 8,
    "num_experts_per_tok": 2, "layers_held": 3, "experts_held": [0, 4],
    "vocab_rows": 96, "seq_len": 64,
}
TINY_MIX = {"name": "tiny-rows", "rows_per_chip": 2, "seq_len": 64,
            "doc_len_median": 24.0, "doc_len_sigma": 1.2,
            "doc_len_clip": [4, 256], "zipf_exponent": 1.0, "eod_id": 1}

# float32 on both sides from bit-equal weights: what is left is the
# order of summation (blockwise against full-score attention, grouped
# against dense experts, chunked against whole cross-entropy) and, at
# steps 2 and 3, a top-k that flips on the last bit (seen: losses under
# 1e-6, leaf measures under 1e-4)
TINY_LIMITS = {"loss_step1": 1e-5, "loss_step2": 1e-4, "loss_step3": 1e-4,
               "ce_loss_step1": 1e-5, "mtp_loss_step1": 1e-5,
               "first_grad_worst_leaf": 1e-3, "first_grad_median_leaf": 1e-4,
               "first_grad_direction_median_leaf": 1e-4,
               "delta3_worst_leaf": 1e-2, "delta3_median_leaf": 1e-3,
               "frozen_moved": 0.0}


def smoke_cell(limits=None, extra_overrides=()):
    from benchmark import harness
    from eksml_tpu.config import LM_TINY_OVERRIDES

    real = harness.load_cell(bench_smoke.ROOT, CELL)
    config = dict(
        real.config, model=dict(real.config["model"], **TINY_MODEL),
        precision="float32", batch_per_chip=2,
        overrides=[o for o in real.config["overrides"]
                   if not o.startswith(("TRAIN.PRECISION", "LM."))]
        + list(LM_TINY_OVERRIDES) + ["TRAIN.LOG_PERIOD=2"]
        + list(extra_overrides))
    workload = {"name": "lm-smoke", "config": real.config["name"],
                "chips": 1, "traffic": dict(TINY_MIX), "warmup_steps": 4,
                "follow_steps": 3, "trace_steps": 3,
                "limits": dict(TINY_LIMITS if limits is None else limits)}
    return harness.Cell(name="lm-smoke", chips=1, config=config,
                        workload=workload, task=real.task,
                        end_to_end=real.end_to_end,
                        per_layer=real.per_layer)
