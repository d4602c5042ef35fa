"""The sequence task (JoyAI-LLM-Flash on one chip's share of an
expert-parallel deployment): ``TokenLoader`` over the mix's document
stream, AdamW, the reference in ``benchmark/reference/lm/``, operations
per row from ``benchmark/lm_flops.py``.  A row of the batch is one
packed sequence.

Its own compared numbers: ``ce_loss_step1`` and ``mtp_loss_step1``, the
relative gaps of the two loss terms at step 1, from equal weights, so a
term left out or mis-weighted shows by name; and
``first_grad_direction_median_leaf`` / ``_worst_leaf``, how far Adam's
``mu`` after step 1 points away from the reference's, leaf by leaf
(``benchmark/lm_direction.py``: projections on fixed sign patterns,
handed through ``first_moment`` beside ``mu`` itself): the number that
tells a step computed in a lower precision from a sound one, which no
gap between norms does.  (``harness.StepTap`` hands a task the step's
``*_loss`` metrics and the norms of what ``first_moment`` returns, and
nothing else; the routing sets themselves are compared in the CPU
tests.)
"""

from __future__ import annotations

import json
import math
import statistics

from benchmark import compare, lm_direction, lm_flops

# what models/lm computes and no key of the program's config can change
IMPLEMENTED = {
    "hidden_act": "silu", "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "rope_interleave": True, "rope_scaling": None,
    "attention_bias": False, "tie_word_embeddings": False,
    "moe_layer_freq": 1,
}


def spec_mismatches(cfg, spec: dict, hyper: dict) -> list:
    """Where the configuration file's ``model``/``optimizer`` blocks
    (what the reference computes) and the program's finalized config
    (what the program computes) differ."""
    from eksml_tpu.models.lm import model as program

    lm = cfg.LM
    want = dict(
        IMPLEMENTED,
        model_type=cfg.MODEL.NAME,
        hidden_size=lm.HIDDEN_SIZE,
        num_attention_heads=lm.NUM_HEADS,
        num_key_value_heads=lm.NUM_HEADS,
        q_lora_rank=lm.Q_LORA_RANK, kv_lora_rank=lm.KV_LORA_RANK,
        qk_nope_head_dim=lm.QK_NOPE_HEAD_DIM,
        qk_rope_head_dim=lm.QK_ROPE_HEAD_DIM,
        qk_head_dim=lm.QK_NOPE_HEAD_DIM + lm.QK_ROPE_HEAD_DIM,
        head_dim=lm.QK_ROPE_HEAD_DIM,
        v_head_dim=lm.V_HEAD_DIM, rope_theta=lm.ROPE_THETA,
        rms_norm_eps=lm.RMS_NORM_EPS,
        intermediate_size=lm.INTERMEDIATE_SIZE,
        moe_intermediate_size=lm.MOE_INTERMEDIATE_SIZE,
        first_k_dense_replace=lm.FIRST_K_DENSE,
        n_routed_experts=lm.N_ROUTED_EXPERTS,
        num_experts_per_tok=lm.NUM_EXPERTS_PER_TOK,
        n_shared_experts=lm.N_SHARED_EXPERTS,
        routed_scaling_factor=lm.ROUTED_SCALING_FACTOR,
        num_nextn_predict_layers=lm.NUM_MTP,
        layers_held=lm.NUM_LAYERS, experts_held=list(lm.EXPERTS_HELD),
        vocab_rows=lm.VOCAB_ROWS, seq_len=lm.SEQ_LEN,
        mtp_loss_weight=lm.MTP_LOSS_WEIGHT, init_std=lm.INIT_STD,
        embed_init_std=program.EMBED_INIT_STD,
        router_bias_std=program.ROUTER_BIAS_STD,
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
    # the published counts bound the share; they reach no code
    for key, held in (("num_hidden_layers", lm.NUM_LAYERS + lm.NUM_MTP),
                      ("vocab_size", lm.VOCAB_ROWS),
                      ("max_position_embeddings", lm.SEQ_LEN)):
        if not isinstance(spec.get(key), int) or spec[key] < held:
            wrong.append(f"{key}: file {spec.get(key)!r} is under the "
                         f"{held} the program runs")
    return wrong


def build_loader(cell, cfg, seed: int, logdir: str):
    """(loader over the mix's seeded document stream, rows per step),
    wired as ``python -m eksml_tpu.train --synthetic`` wires its
    loader; the mix states every parameter of the stream."""
    from eksml_tpu.data.tokens import TokenLoader

    mix = cell.workload["traffic"]
    rows_per_step = cfg.TRAIN.BATCH_SIZE_PER_CHIP * cell.chips
    if (mix["seq_len"] != cfg.LM.SEQ_LEN
            or mix["rows_per_chip"] != cfg.TRAIN.BATCH_SIZE_PER_CHIP):
        raise RuntimeError(
            f"mix {mix.get('name')!r} is {mix['rows_per_chip']} rows of "
            f"{mix['seq_len']} a chip; the configuration runs "
            f"{cfg.TRAIN.BATCH_SIZE_PER_CHIP} of {cfg.LM.SEQ_LEN}")
    loader = TokenLoader(
        rows_per_step, seq_len=mix["seq_len"], vocab=cfg.LM.VOCAB_ROWS,
        seed=cfg.TRAIN.SEED, doc_len_median=mix["doc_len_median"],
        doc_len_sigma=mix["doc_len_sigma"],
        doc_len_clip=mix["doc_len_clip"],
        zipf_exponent=mix["zipf_exponent"], eod_id=mix["eod_id"])
    return loader, rows_per_step


def first_moment(opt_state):
    """Adam's first moment after one step, (1 - b1) x the clipped
    gradient, and beside it each leaf's projections on the fixed sign
    patterns (``lm_direction``): the tap keeps one norm a leaf, and a
    norm says nothing of direction."""
    import jax
    import optax

    def is_adam(x):
        return isinstance(x, optax.ScaleByAdamState)

    found = [x for x in jax.tree_util.tree_leaves(
        opt_state, is_leaf=is_adam) if is_adam(x)]
    if len(found) != 1:
        raise RuntimeError("expected one Adam state in the optimizer "
                           f"state, found {len(found)}")
    mu = found[0].mu
    return dict(mu, **lm_direction.project(mu))


def reference_steps(spec, hyper, seed, batches, **kw):
    from benchmark.reference.lm import train

    return train.run_steps(spec, hyper, seed, batches, **kw)


def extra_numbers(program, reference) -> dict:
    """``ce_loss_step1``, ``mtp_loss_step1`` where both sides report
    their loss terms; ``first_grad_direction_median_leaf`` and
    ``_worst_leaf`` where both report projections, over the leaves the
    comparison's own leaf measures take (reference gradient not under a
    thousandth of the median leaf's)."""
    out = {}
    if program.get("terms") and reference.get("terms"):
        for term in ("ce_loss", "mtp_loss"):
            p = program["terms"][0].get(term, math.nan)
            r = reference["terms"][0].get(term, math.nan)
            gap = abs(p - r) / max(abs(r), 1e-30)
            out[f"{term}_step1"] = gap if math.isfinite(gap) else math.inf
    g = {k: v for k, v in reference.get("grad_norm", {}).items() if v > 0.0}
    if g:
        floor = compare.TINY_GRAD_SHARE * statistics.median(g.values())
        median, worst = lm_direction.median_and_worst(lm_direction.gaps(
            program["first_trace_norm"], reference["first_trace_norm"],
            [k for k in g if g[k] >= floor]))
        if math.isfinite(median):
            out["first_grad_direction_median_leaf"] = median
            out["first_grad_direction_worst_leaf"] = worst
    return out


def train_ops_per_row(spec) -> float:
    return lm_flops.train_ops_per_row(spec)
