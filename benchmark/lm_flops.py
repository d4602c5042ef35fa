"""Operations and bytes the sequence task (JoyAI-LLM-Flash on one
chip's share) REQUIRES, from shapes alone: what ``lm_step_mfu_pct`` and
the kernels' roofline readers divide by.  An operation is a multiply
or an add (2 per multiply-add); backward costs twice forward, so a
training step is 3 x forward; recomputation (remat, the attention
kernels' own) is never counted.
"""

from __future__ import annotations


def attention_macs_per_token(spec):
    """Multiply-adds of MLA's five projections for one token."""
    d, h = spec["hidden_size"], spec["num_attention_heads"]
    dn, dr, dv = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                  spec["v_head_dim"])
    ql, kl = spec["q_lora_rank"], spec["kv_lora_rank"]
    return (d * ql + ql * h * (dn + dr) + d * (kl + dr)
            + kl * h * (dn + dv) + h * dv * d)


def held_pairs_per_token(spec):
    """Mean routed experts of a token that live here, under uniform
    routing: k x held / routed (0.5 at 8 x 16 / 256)."""
    return (spec["num_experts_per_tok"] * spec["experts_held"][1]
            / spec["n_routed_experts"])


def expert_layer_macs_per_token(spec):
    d, w = spec["hidden_size"], spec["moe_intermediate_size"]
    return (attention_macs_per_token(spec) + d * spec["n_routed_experts"]
            + 3 * d * w * spec["n_shared_experts"]
            + held_pairs_per_token(spec) * 3 * d * w)


def forward_macs_per_token(spec):
    """Every matrix product a token meets on the way to both losses
    (embedding look-ups are no products)."""
    d = spec["hidden_size"]
    dense = spec["first_k_dense_replace"]
    total = dense * (attention_macs_per_token(spec)
                     + 3 * d * spec["intermediate_size"])
    total += (spec["layers_held"] - dense) * expert_layer_macs_per_token(
        spec)
    total += d * spec["vocab_rows"]
    if spec["num_nextn_predict_layers"]:
        total += (2 * d * d + expert_layer_macs_per_token(spec)
                  + d * spec["vocab_rows"])
    return total


def attention_layers(spec):
    return spec["layers_held"] + spec["num_nextn_predict_layers"]


def attention_core_forward_ops(spec, seq: int):
    """One causal attention core over one row of ``seq`` positions,
    forward: q.kT and p.v over the half of the square at or below the
    diagonal, all heads."""
    h = spec["num_attention_heads"]
    dqk = spec["qk_nope_head_dim"] + spec["qk_rope_head_dim"]
    return 2 * (seq * seq / 2) * h * (dqk + spec["v_head_dim"])


def attention_core_forward_bytes(spec, seq: int, itemsize: int):
    """q, k in, v in, o out for one row (the least a core moves)."""
    h = spec["num_attention_heads"]
    dqk = spec["qk_nope_head_dim"] + spec["qk_rope_head_dim"]
    return seq * h * (2 * dqk + 2 * spec["v_head_dim"]) * itemsize


def train_ops_per_row(spec):
    """Forward + backward operations one row (sequence) requires."""
    seq = spec["seq_len"]
    return 3 * (2 * forward_macs_per_token(spec) * seq
                + attention_layers(spec)
                * attention_core_forward_ops(spec, seq))


def grouped_product_call(spec, pairs: float, itemsize: int):
    """One grouped matrix product over the held experts with ``pairs``
    token-expert rows (forward, input-gradient and weight-gradient
    calls alike touch one bank of ``held x hidden x width`` and two
    activations of ``pairs`` rows, ``hidden`` and ``width`` wide)."""
    d, w = spec["hidden_size"], spec["moe_intermediate_size"]
    return {"ops": 2 * pairs * d * w,
            "bytes": (spec["experts_held"][1] * d * w
                      + pairs * (d + w)) * itemsize}
