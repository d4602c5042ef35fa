"""The hyper-connected task (Xing4.0-29B-A4B on one chip's share of an
expert-parallel deployment): ``tasks/lm.py``'s ``TokenLoader`` wiring
and AdamW's first moment, the reference in
``benchmark/reference/mhc_lm/``, operations per row from
``benchmark/mhc_flops.py``.  A row of the batch is one packed sequence.

Its own compared numbers: ``first_grad_direction_median_leaf`` /
``_worst_leaf`` through ``benchmark/lm_direction.py``, as the sequence
task has them, and ``delta3_worst_leaf_past_copies``.  The
model has one loss term, so ``compare.py``'s ``loss_step1`` already is
its gap from equal weights.

The configuration keeps every published count as published
(``first_k_dense_replace`` 2, ``num_nextn_predict_layers`` 1) and
states what this chip holds in keys of its own: ``layers_held``,
``dense_layers_held``, ``mtp_layers_held``, ``experts_held``,
``vocab_rows``.  ``spec_mismatches`` holds both to the program.
"""

from __future__ import annotations

import json
import math
import numbers
import statistics

from benchmark import compare, mhc_flops
from benchmark.tasks import lm as lm_task
from benchmark.tasks.lm import build_loader, first_moment  # noqa: F401

# what models/lm/xing.py computes and no key of the program's config can
# change
IMPLEMENTED = {
    "hidden_act": "silu", "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "attention_bias": False,
    "tie_word_embeddings": False, "moe_layer_freq": 1,
}
# config.json's model_type of each MODEL.NAME this task runs
MODEL_TYPE = {"xing4": "xing4_0"}


def _same(a, b) -> bool:
    """Equal as data; numbers by value (the file's 2 is the program's
    2.0)."""
    if isinstance(a, dict) and isinstance(b, dict):
        return a.keys() == b.keys() and all(_same(a[k], b[k]) for k in a)
    if isinstance(a, (list, tuple)) and isinstance(b, (list, tuple)):
        return len(a) == len(b) and all(map(_same, a, b))
    number = (lambda x: isinstance(x, numbers.Real)
              and not isinstance(x, bool))
    if number(a) and number(b):
        return float(a) == float(b)
    return json.dumps(a) == json.dumps(b)


def rope_scaling(block):
    """The program's ``LM.ROPE_SCALING`` under ``config.json``'s keys."""
    return {"type": block.TYPE, "factor": block.FACTOR,
            "original_max_position_embeddings": block.ORIGINAL_MAX_POSITION,
            "beta_fast": block.BETA_FAST, "beta_slow": block.BETA_SLOW,
            "mscale": block.MSCALE, "mscale_all_dim": block.MSCALE_ALL_DIM}


def spec_mismatches(cfg, spec: dict, hyper: dict) -> list:
    """Where the configuration file's ``model``/``optimizer`` blocks
    (what the reference computes) and the program's finalized config
    (what the program computes) differ."""
    from eksml_tpu.models.lm import mhc, model as program

    lm = cfg.LM
    lo, hi = lm.HC_RES_CLAMP
    want = dict(
        IMPLEMENTED,
        model_type=MODEL_TYPE.get(cfg.MODEL.NAME, cfg.MODEL.NAME),
        hidden_size=lm.HIDDEN_SIZE,
        num_attention_heads=lm.NUM_HEADS,
        num_key_value_heads=lm.NUM_HEADS,
        q_lora_rank=lm.Q_LORA_RANK, kv_lora_rank=lm.KV_LORA_RANK,
        qk_nope_head_dim=lm.QK_NOPE_HEAD_DIM,
        qk_rope_head_dim=lm.QK_ROPE_HEAD_DIM,
        v_head_dim=lm.V_HEAD_DIM, rope_theta=lm.ROPE_THETA,
        rope_scaling=rope_scaling(lm.ROPE_SCALING),
        rms_norm_eps=lm.RMS_NORM_EPS,
        intermediate_size=lm.INTERMEDIATE_SIZE,
        moe_intermediate_size=lm.MOE_INTERMEDIATE_SIZE,
        n_routed_experts=lm.N_ROUTED_EXPERTS,
        num_experts_per_tok=lm.NUM_EXPERTS_PER_TOK,
        n_shared_experts=lm.N_SHARED_EXPERTS,
        routed_scaling_factor=lm.ROUTED_SCALING_FACTOR,
        hc_mult=lm.HC_MULT, hc_sinkhorn_iters=lm.HC_SINKHORN_ITERS,
        hc_eps=lm.HC_EPS, mhc_h_res_clamp_min=lo, mhc_h_res_clamp_max=hi,
        layers_held=lm.NUM_LAYERS, dense_layers_held=lm.FIRST_K_DENSE,
        mtp_layers_held=lm.NUM_MTP, experts_held=list(lm.EXPERTS_HELD),
        vocab_rows=lm.VOCAB_ROWS, seq_len=lm.SEQ_LEN, init_std=lm.INIT_STD,
        embed_init_std=program.EMBED_INIT_STD,
        router_bias_std=program.ROUTER_BIAS_STD,
        hc_phi_std=mhc.phi_std(lm.HC_MULT, lm.HIDDEN_SIZE),
        hc_alpha_init=mhc.ALPHA_INIT, hc_bias_init=mhc.BIAS_INIT,
        optimizer=cfg.TRAIN.OPTIMIZER, adam_b1=cfg.TRAIN.ADAM_B1,
        adam_b2=cfg.TRAIN.ADAM_B2, adam_eps=cfg.TRAIN.ADAM_EPS,
        base_lr=cfg.TRAIN.BASE_LR, warmup_steps=cfg.TRAIN.WARMUP_STEPS,
        warmup_init_factor=cfg.TRAIN.WARMUP_INIT_FACTOR,
        lr_schedule=list(cfg.TRAIN.LR_SCHEDULE),
        weight_decay=cfg.TRAIN.WEIGHT_DECAY,
        gradient_clip=cfg.TRAIN.GRADIENT_CLIP,
        global_batch=cfg.TRAIN.NUM_CHIPS * cfg.TRAIN.BATCH_SIZE_PER_CHIP,
    )
    have = dict(spec, **hyper)
    wrong = [f"{k}: file {have.get(k)!r}, program {v!r}"
             for k, v in want.items() if not _same(have.get(k), v)]
    # the published counts bound the share; they reach no code
    for key, held in (
            ("num_hidden_layers", lm.NUM_LAYERS),
            ("first_k_dense_replace", lm.FIRST_K_DENSE),
            ("num_nextn_predict_layers", lm.NUM_MTP),
            ("n_routed_experts", sum(lm.EXPERTS_HELD)),
            ("vocab_size", lm.VOCAB_ROWS),
            ("max_position_embeddings", lm.SEQ_LEN)):
        if not isinstance(spec.get(key), int) or spec[key] < held:
            wrong.append(f"{key}: file {spec.get(key)!r} is under the "
                         f"{held} the program runs")
    return wrong


def reference_steps(spec, hyper, seed, batches, **kw):
    from benchmark.reference.mhc_lm import train

    return train.run_steps(spec, hyper, seed, batches, **kw)


# block 0's attention maps read four exact copies of the embedding: every
# row of their H_res acts on equal inputs, so the exact gradient into its
# logits is zero, and what each side computes there is its own rounding
COPIES_MAP = "block0/attn_hc/"


def delta3_past_copies(program, reference) -> float:
    """``delta3_worst_leaf`` (the same live leaves, the same scale: the
    larger of the leaf's reference norm and the median live leaf's) over
    every leaf but those of ``COPIES_MAP``, whose ``H_res`` columns
    Adam's normalised step turns from rounding into full-size changes of
    either sign."""
    g = {k: v for k, v in reference["grad_norm"].items() if v > 0.0}
    floor = compare.TINY_GRAD_SHARE * statistics.median(g.values())
    live = [k for k in g if g[k] >= floor]
    ref, prog = reference["delta_norm"], program["delta_norm"]
    med = statistics.median(ref[k] for k in live)
    gaps = [abs(prog.get(k, math.nan) - ref[k]) / max(ref[k], med, 1e-30)
            for k in live if not k.startswith(COPIES_MAP)]
    return max((x if math.isfinite(x) else math.inf for x in gaps),
               default=math.inf)


def extra_numbers(program, reference) -> dict:
    """The direction numbers, computed in the sequence task (handed no
    terms, it adds no term of its own), and
    ``delta3_worst_leaf_past_copies``."""
    out = lm_task.extra_numbers(dict(program, terms=None),
                                dict(reference, terms=None))
    if reference.get("grad_norm") and "delta_norm" in program:
        out["delta3_worst_leaf_past_copies"] = delta3_past_copies(
            program, reference)
    return out


def train_ops_per_row(spec) -> float:
    return mhc_flops.train_ops_per_row(spec)
