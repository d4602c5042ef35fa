"""Operations and bytes the window-and-experts task (Laguna on one
chip's share: window and full attention mixed over grouped key-value
heads, 256-way routing over the held experts) REQUIRES, from shapes
alone: what ``swa_step_mfu_pct`` and the kernels' roofline readers
divide by.  An operation is a multiply or an add (2 per multiply-add);
backward costs twice forward, so a training step is 3 x forward;
recomputation (remat, the attention kernels' own) is never counted, and
neither is a score the mask forbids, whatever block the kernel visits
it in.
"""

from __future__ import annotations


def layers(spec):
    return range(spec["layers_held"])


def is_sparse(spec, layer: int) -> bool:
    return spec["mlp_layer_types"][layer] == "sparse"


def expert_layers(spec) -> int:
    """Held layers that carry the routed experts."""
    return sum(is_sparse(spec, i) for i in layers(spec))


def window_of(spec, layer: int):
    """The layer's window, or None where it sees every earlier key."""
    sliding = spec["layer_types"][layer] == "sliding_attention"
    return spec["sliding_window"] if sliding else None


def attention_macs_per_token(spec, layer: int):
    """Multiply-adds of the layer's five projections for one token: q
    and o over its own query heads, k and v over the key-value heads,
    the gate's column a head."""
    d, dh = spec["hidden_size"], spec["head_dim"]
    h = spec["num_attention_heads_per_layer"][layer]
    return d * (2 * h * dh + 2 * spec["num_key_value_heads"] * dh + h)


def held_pairs_per_token(spec):
    """Mean routed experts of a token that live here, under uniform
    routing: k x held / routed (1 at 8 x 32 / 256)."""
    return (spec["num_experts_per_tok"] * spec["experts_held"][1]
            / spec["num_experts"])


def mlp_macs_per_token(spec, layer: int):
    d = spec["hidden_size"]
    if not is_sparse(spec, layer):
        return 3 * d * spec["intermediate_size"]
    return (d * spec["num_experts"]
            + 3 * d * spec["shared_expert_intermediate_size"]
            + held_pairs_per_token(spec) * 3 * d
            * spec["moe_intermediate_size"])


def forward_macs_per_token(spec):
    """Every matrix product a token meets on the way to the loss
    (embedding look-ups are no products)."""
    return (sum(attention_macs_per_token(spec, i)
                + mlp_macs_per_token(spec, i) for i in layers(spec))
            + spec["hidden_size"] * spec["vocab_rows"])


def visible_scores(seq: int, window) -> int:
    """Score entries a head's mask lets through over one row: query i
    sees ``min(i + 1, window)`` keys (so a window's area is not
    ``seq x window``: its first rows see fewer)."""
    w = seq if window is None else min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def attention_core_forward_ops(spec, layer: int, seq: int):
    """The layer's core over one row of ``seq`` positions, forward:
    q.kT and p.v over the visible scores, all query heads."""
    return (2 * visible_scores(seq, window_of(spec, layer))
            * spec["num_attention_heads_per_layer"][layer]
            * 2 * spec["head_dim"])


def attention_core_forward_bytes(spec, layer: int, seq: int, itemsize: int):
    """q in and o out over the query heads, k and v in over the
    key-value heads, read once (the least a grouped core moves)."""
    h = spec["num_attention_heads_per_layer"][layer]
    return (seq * (2 * h + 2 * spec["num_key_value_heads"])
            * spec["head_dim"] * itemsize)


def attention_core_seconds(spec, layer: int, seq: int, itemsize: int,
                           peak: dict):
    """Roofline seconds of the layer's forward core over one row: the
    larger of operations over peak and bytes over bandwidth."""
    return max(
        attention_core_forward_ops(spec, layer, seq)
        / peak["bf16_flops_per_s"],
        attention_core_forward_bytes(spec, layer, seq, itemsize)
        / peak["hbm_bytes_per_s"])


def train_ops_per_row(spec):
    """Forward + backward operations one row (sequence) requires."""
    seq = spec["seq_len"]
    return 3 * (2 * forward_macs_per_token(spec) * seq
                + sum(attention_core_forward_ops(spec, i, seq)
                      for i in layers(spec)))


def grouped_product_call(spec, pairs: float, itemsize: int):
    """One grouped matrix product over the held experts with ``pairs``
    token-expert rows (forward, input-gradient and weight-gradient
    calls alike touch one bank of ``held x hidden x width`` and two
    activations of ``pairs`` rows, ``hidden`` and ``width`` wide)."""
    d, w = spec["hidden_size"], spec["moe_intermediate_size"]
    return {"ops": 2 * pairs * d * w,
            "bytes": (spec["experts_held"][1] * d * w
                      + pairs * (d + w)) * itemsize}
