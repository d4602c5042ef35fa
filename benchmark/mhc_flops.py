"""Operations and bytes the hyper-connected task (Xing4.0 on one chip's
share) REQUIRES, from shapes alone: what ``mhc_step_mfu_pct`` and the
kernels' roofline readers divide by.  An operation is a multiply or an
add (2 per multiply-add); backward costs twice forward, so a training
step is 3 x forward; recomputation (remat, the attention kernels' own)
is never counted.

The block's products are the sequence task's (``benchmark/lm_flops.py``:
MLA's five projections, the router, the shared expert and the held
pairs under uniform routing); this file adds the hyper-connections'
(``maps_macs_per_token``) and counts the dense and MTP layers by the
configuration's HELD keys (``dense_layers_held``, no MTP module), where
``lm_flops`` reads ``first_k_dense_replace`` and
``num_nextn_predict_layers``, which this configuration keeps as
published.
"""

from __future__ import annotations

from benchmark import lm_flops


def expert_layers(spec) -> int:
    """Held layers that carry the routed experts."""
    return spec["layers_held"] - spec["dense_layers_held"]


def maps_macs_per_token(spec):
    """One sublayer's hyper-connection for one token: the projection of
    the normed streams onto the maps' 2n + n^2 logits (``nC`` wide) and
    the three mixes ``H_pre x``, ``H_res x`` and ``H_post^T y`` (n, n^2
    and n multiply-adds a channel)."""
    n, d = spec["hc_mult"], spec["hidden_size"]
    width = 2 * n + n * n
    return n * d * width + width * d


def forward_macs_per_token(spec):
    """Every matrix product a token meets on the way to the loss
    (embedding look-ups are no products): the held dense and expert
    layers, two hyper-connected sublayers each, and the head."""
    d = spec["hidden_size"]
    dense = spec["dense_layers_held"]
    total = dense * (lm_flops.attention_macs_per_token(spec)
                     + 3 * d * spec["intermediate_size"])
    total += expert_layers(spec) * lm_flops.expert_layer_macs_per_token(spec)
    total += 2 * spec["layers_held"] * maps_macs_per_token(spec)
    return total + d * spec["vocab_rows"]


def attention_core_seconds(spec, seq: int, itemsize: int, peak: dict):
    """Roofline seconds of one causal MLA core over one row, forward:
    the larger of operations over peak and bytes over bandwidth."""
    return max(lm_flops.attention_core_forward_ops(spec, seq)
               / peak["bf16_flops_per_s"],
               lm_flops.attention_core_forward_bytes(spec, seq, itemsize)
               / peak["hbm_bytes_per_s"])


def train_ops_per_row(spec):
    """Forward + backward operations one row (sequence) requires: one
    causal core a held layer."""
    seq = spec["seq_len"]
    return 3 * (2 * forward_macs_per_token(spec) * seq
                + spec["layers_held"]
                * lm_flops.attention_core_forward_ops(spec, seq))
