"""A smoke-width cell of the looped-stack task for the CPU tests: the
real cell's task, metrics and optimizer at ``config.OURO_TINY_OVERRIDES``
widths in float32 (hidden 64, 4 heads of 16, 2 blocks applied 3 times,
96 vocabulary rows, S 64).  Never a device number."""

import bench_smoke

CELL = "ouro-2.6b-train-4k-ut4"

TINY_MODEL = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "head_dim": 16, "intermediate_size": 160, "layers_held": 2,
    "vocab_rows": 96, "vocab_size": 96, "seq_len": 64,
}
TINY_MIX = {"name": "tiny-rows", "rows_per_chip": 2, "seq_len": 64,
            "doc_len_median": 24.0, "doc_len_sigma": 1.2,
            "doc_len_clip": [4, 256], "zipf_exponent": 1.0, "eod_id": 1}

# float32 on both sides from bit-equal weights: what is left is the
# order of summation (blockwise against full-score attention, chunked
# against blocked logits, log-sigmoid sums against products); no
# discrete choice anywhere in this model (seen: losses under 5e-7, leaf
# measures under 2e-5, the direction number under 2e-6)
TINY_LIMITS = {"loss_step1": 1e-5, "loss_step2": 1e-5, "loss_step3": 1e-5,
               "ce_pass1_loss_step1": 1e-5, "expected_ce_loss_step1": 1e-5,
               "exit_entropy_loss_step1": 1e-5,
               "first_grad_worst_leaf": 1e-3, "first_grad_median_leaf": 1e-4,
               "first_grad_direction_median_leaf": 1e-4,
               "delta3_worst_leaf": 1e-2, "delta3_median_leaf": 1e-3,
               "frozen_moved": 0.0}


def smoke_cell(passes=3, limits=None, extra_overrides=()):
    from benchmark import harness
    from eksml_tpu.config import OURO_TINY_OVERRIDES

    real = harness.load_cell(bench_smoke.ROOT, CELL)
    config = dict(
        real.config,
        model=dict(real.config["model"], **TINY_MODEL,
                   total_ut_steps=passes),
        precision="float32", batch_per_chip=2,
        overrides=[o for o in real.config["overrides"]
                   if not o.startswith(("TRAIN.PRECISION", "LM.",
                                        "TRAIN.LOG_PERIOD"))]
        + list(OURO_TINY_OVERRIDES)
        + [f"LM.UT_STEPS={passes}", "TRAIN.LOG_PERIOD=2"]
        + list(extra_overrides))
    limits = dict(TINY_LIMITS if limits is None else limits)
    limits.setdefault(f"ce_pass{passes}_loss_step1", 1e-5)
    workload = {"name": "looplm-smoke", "config": real.config["name"],
                "chips": 1, "traffic": dict(TINY_MIX), "warmup_steps": 4,
                "follow_steps": 3, "trace_steps": 3, "limits": limits}
    return harness.Cell(name="looplm-smoke", chips=1, config=config,
                        workload=workload, task=real.task,
                        end_to_end=real.end_to_end,
                        per_layer=real.per_layer)
