"""The looped-stack task (Ouro, LoopLM, on one pipeline stage's layers):
``tasks/lm.py``'s ``TokenLoader`` wiring and AdamW's first moment, the
reference in ``benchmark/reference/looplm/``, operations per row from
``benchmark/looplm_flops.py``.  A row of the batch is one packed
sequence.

Its own compared numbers: the relative gaps at step 1, from equal
weights, of ``ce_pass1_loss`` and of the last pass's (``ce_pass4_loss``
at the published four), of ``expected_ce_loss`` and of
``exit_entropy_loss``, so that a pass left out, a head applied to the
un-normed state or a mis-weighted entropy shows by name; and
``first_grad_direction_median_leaf`` / ``_worst_leaf`` through
``benchmark/lm_direction.py``, as the sequence task has them (the
number that tells a step computed in a lower precision from a sound
one).
"""

from __future__ import annotations

import json
import math

from benchmark import looplm_flops
from benchmark.tasks import lm as lm_task
from benchmark.tasks.lm import build_loader, first_moment  # noqa: F401

# what models/lm/ouro.py computes and no key of the program's config can
# change
IMPLEMENTED = {
    "hidden_act": "silu", "rope_scaling": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "sliding_window": None,
}


def spec_mismatches(cfg, spec: dict, hyper: dict) -> list:
    """Where the configuration file's ``model``/``optimizer`` blocks
    (what the reference computes) and the program's finalized config
    (what the program computes) differ."""
    lm = cfg.LM
    want = dict(
        IMPLEMENTED,
        model_type=cfg.MODEL.NAME,
        hidden_size=lm.HIDDEN_SIZE,
        num_attention_heads=lm.NUM_HEADS,
        num_key_value_heads=lm.NUM_HEADS,
        head_dim=lm.HEAD_DIM, rope_theta=lm.ROPE_THETA,
        rms_norm_eps=lm.RMS_NORM_EPS,
        intermediate_size=lm.INTERMEDIATE_SIZE,
        total_ut_steps=lm.UT_STEPS,
        exit_entropy_weight=lm.EXIT_ENTROPY_WEIGHT,
        layers_held=lm.NUM_LAYERS, vocab_rows=lm.VOCAB_ROWS,
        seq_len=lm.SEQ_LEN, init_std=lm.INIT_STD,
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
    # the published counts bound what is held; they reach no code.  The
    # vocabulary is whole here: the rows held ARE the published size
    for key, held in (("num_hidden_layers", lm.NUM_LAYERS),
                      ("max_window_layers", lm.NUM_LAYERS),
                      ("max_position_embeddings", lm.SEQ_LEN)):
        if not isinstance(spec.get(key), int) or spec[key] < held:
            wrong.append(f"{key}: file {spec.get(key)!r} is under the "
                         f"{held} the program runs")
    if spec.get("vocab_size") != lm.VOCAB_ROWS:
        wrong.append(f"vocab_size: file {spec.get('vocab_size')!r}, the "
                     f"program holds {lm.VOCAB_ROWS} rows (whole)")
    layers = spec.get("layer_types")
    if (not isinstance(layers, list)
            or len(layers) != spec.get("num_hidden_layers")
            or set(layers) != {"full_attention"}):
        wrong.append("layer_types: the program runs full attention in "
                     "every layer, one entry a published layer")
    return wrong


def reference_steps(spec, hyper, seed, batches, **kw):
    from benchmark.reference.looplm import train

    return train.run_steps(spec, hyper, seed, batches, **kw)


def extra_numbers(program, reference) -> dict:
    """The four terms' gaps at step 1 where both sides report their loss
    terms; the direction numbers where both report projections, over the
    leaves the comparison's own leaf measures take (reference gradient
    not under a thousandth of the median leaf's)."""
    out = {}
    if program.get("terms") and reference.get("terms"):
        ref = reference["terms"][0]
        passes = sum(k.startswith("ce_pass") for k in ref)
        for term in dict.fromkeys((
                "ce_pass1_loss", f"ce_pass{passes}_loss",
                "expected_ce_loss", "exit_entropy_loss")):
            p = program["terms"][0].get(term, math.nan)
            r = ref.get(term, math.nan)
            gap = abs(p - r) / max(abs(r), 1e-30)
            out[f"{term}_step1"] = gap if math.isfinite(gap) else math.inf
    # the direction numbers are the sequence task's, computed there
    # (handed no terms, it adds no term of its own)
    out.update(lm_task.extra_numbers(dict(program, terms=None),
                                     dict(reference, terms=None)))
    return out


def train_ops_per_row(spec) -> float:
    return looplm_flops.train_ops_per_row(spec)
