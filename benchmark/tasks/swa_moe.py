"""The window-and-experts task (Laguna-XS.2 on one chip's share of an
expert-parallel deployment): ``tasks/lm.py``'s ``TokenLoader`` wiring
and AdamW's first moment, the reference in
``benchmark/reference/swa_moe/``, operations per row from
``benchmark/swa_moe_flops.py``.  A row of the batch is one packed
sequence.

Its own compared numbers: ``first_grad_direction_median_leaf`` /
``_worst_leaf`` through ``benchmark/lm_direction.py``, as the sequence
task has them (the number that tells a step computed in a lower
precision from a sound one).  The model has one loss term, so
``compare.py``'s ``loss_step1`` already is its gap from equal weights.
"""

from __future__ import annotations

import json

from benchmark import swa_moe_flops
from benchmark.tasks import lm as lm_task
from benchmark.tasks.lm import build_loader, first_moment  # noqa: F401

# what models/lm/laguna.py computes and no key of the program's config
# can change
IMPLEMENTED = {
    "attention_bias": False, "tie_word_embeddings": False, "gating": True,
    "moe_apply_router_weight_on_input": False,
}


def _rope(block) -> dict:
    """A rotary block of the program's config under the file's keys."""
    out = {"rope_type": block.TYPE, "rope_theta": block.THETA,
           "partial_rotary_factor": block.PARTIAL_ROTARY_FACTOR}
    if block.TYPE == "yarn":
        out.update(
            factor=block.FACTOR,
            original_max_position_embeddings=block.ORIGINAL_MAX_POSITION,
            beta_slow=block.BETA_SLOW, beta_fast=block.BETA_FAST,
            attention_factor=block.ATTENTION_FACTOR)
    return out


def spec_mismatches(cfg, spec: dict, hyper: dict) -> list:
    """Where the configuration file's ``model``/``optimizer`` blocks
    (what the reference computes) and the program's finalized config
    (what the program computes) differ."""
    from eksml_tpu.models.lm import model as program

    lm = cfg.LM
    want = dict(
        IMPLEMENTED,
        model_type=cfg.MODEL.NAME,
        hidden_size=lm.HIDDEN_SIZE, head_dim=lm.HEAD_DIM,
        num_key_value_heads=lm.NUM_KV_HEADS,
        sliding_window=lm.SLIDING_WINDOW,
        rms_norm_eps=lm.RMS_NORM_EPS,
        intermediate_size=lm.INTERMEDIATE_SIZE,
        moe_intermediate_size=lm.MOE_INTERMEDIATE_SIZE,
        shared_expert_intermediate_size=(lm.MOE_INTERMEDIATE_SIZE
                                         * lm.N_SHARED_EXPERTS),
        num_experts=lm.N_ROUTED_EXPERTS,
        num_experts_per_tok=lm.NUM_EXPERTS_PER_TOK,
        moe_routed_scaling_factor=lm.ROUTED_SCALING_FACTOR,
        layers_held=lm.NUM_LAYERS, experts_held=list(lm.EXPERTS_HELD),
        vocab_rows=lm.VOCAB_ROWS, seq_len=lm.SEQ_LEN,
        init_std=lm.INIT_STD, embed_init_std=program.EMBED_INIT_STD,
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
             for k, v in want.items()
             if json.dumps(have.get(k)) != json.dumps(v)]
    # the two rotary blocks, by layer type (the group's own
    # original_max_position_embeddings repeats the full block's)
    ropes = spec.get("rope_parameters", {})
    for kind, block in (("full_attention", lm.ROPE_FULL),
                        ("sliding_attention", lm.ROPE_WINDOW)):
        if json.dumps(ropes.get(kind), sort_keys=True) != json.dumps(
                _rope(block), sort_keys=True):
            wrong.append(f"rope_parameters.{kind}: file "
                         f"{ropes.get(kind)!r}, program {_rope(block)!r}")
    # the per-layer lists: the held layers are the published lists'
    # first entries, and the lists cover the published depth
    held = lm.NUM_LAYERS
    dense = ["dense"] * lm.FIRST_K_DENSE + ["sparse"] * (
        held - lm.FIRST_K_DENSE)
    for key, program_side in (
            ("num_attention_heads_per_layer", list(lm.HEADS_PER_LAYER)),
            ("layer_types", list(lm.LAYER_TYPES)),
            ("mlp_layer_types", dense)):
        published = spec.get(key)
        if (not isinstance(published, list)
                or len(published) != spec.get("num_hidden_layers")
                or published[:held] != program_side):
            wrong.append(f"{key}: the file's first {held} entries "
                         f"{(published or [])[:held]!r}, program "
                         f"{program_side!r} (one entry a published layer)")
    # num_attention_heads is the full layers' count; no code reads it
    full = {h for h, kind in zip(lm.HEADS_PER_LAYER, lm.LAYER_TYPES)
            if kind == "full_attention"}
    if full and {spec.get("num_attention_heads")} != full:
        wrong.append(f"num_attention_heads: file "
                     f"{spec.get('num_attention_heads')!r}, the program's "
                     f"full layers run {sorted(full)}")
    if spec.get("partial_rotary_factor") != (
            lm.ROPE_FULL.PARTIAL_ROTARY_FACTOR):
        wrong.append("partial_rotary_factor: the file's top-level value "
                     "is the full layers'")
    # the published counts bound the share; they reach no code
    for key, least in (("num_hidden_layers", held),
                       ("vocab_size", lm.VOCAB_ROWS),
                       ("max_position_embeddings", lm.SEQ_LEN)):
        if not isinstance(spec.get(key), int) or spec[key] < least:
            wrong.append(f"{key}: file {spec.get(key)!r} is under the "
                         f"{least} the program runs")
    return wrong


def reference_steps(spec, hyper, seed, batches, **kw):
    from benchmark.reference.swa_moe import train

    return train.run_steps(spec, hyper, seed, batches, **kw)


def extra_numbers(program, reference) -> dict:
    """The direction numbers, computed in the sequence task (handed no
    terms, it adds no term of its own)."""
    return lm_task.extra_numbers(dict(program, terms=None),
                                 dict(reference, terms=None))


def train_ops_per_row(spec) -> float:
    return swa_moe_flops.train_ops_per_row(spec)
