"""Operations and bytes the looped-stack task (Ouro, LoopLM: a stack of
blocks applied several times with tied weights) REQUIRES, from shapes
alone: what ``loop_step_mfu_pct`` and the attention kernels' roofline
readers divide by.  An operation is a multiply or an add (2 per
multiply-add); backward costs twice forward, so a training step is
3 x forward; recomputation (remat, the attention kernels' own) is never
counted.  Tied weights save parameters, not work: every pass pays for
every block, the head and the gate again.
"""

from __future__ import annotations


def passes(spec) -> int:
    return spec["total_ut_steps"]


def block_macs_per_token(spec):
    """Multiply-adds of one block for one token: q, k, v, o and the
    SwiGLU's three matrices."""
    d, w = spec["hidden_size"], spec["intermediate_size"]
    a = spec["num_attention_heads"] * spec["head_dim"]
    return 4 * d * a + 3 * d * w


def forward_macs_per_token(spec):
    """Every matrix product a token meets on the way to the loss: per
    pass the held blocks, the head over the whole vocabulary and the
    gate's column (embedding look-ups are no products)."""
    d = spec["hidden_size"]
    return passes(spec) * (
        spec["layers_held"] * block_macs_per_token(spec)
        + d * spec["vocab_rows"] + d)


def attention_cores(spec) -> int:
    """Causal attention cores a row runs forward: one a block a pass."""
    return passes(spec) * spec["layers_held"]


def attention_core_forward_ops(spec, seq: int):
    """One causal core over one row of ``seq`` positions, forward: q.kT
    and p.v over the half of the square at or below the diagonal, all
    heads."""
    return (2 * (seq * seq / 2) * spec["num_attention_heads"]
            * 2 * spec["head_dim"])


def attention_core_forward_bytes(spec, seq: int, itemsize: int):
    """q, k and v in, o out for one row (the least a core moves)."""
    return (seq * spec["num_attention_heads"] * 4 * spec["head_dim"]
            * itemsize)


def attention_core_seconds(spec, seq: int, itemsize: int, peak: dict):
    """Roofline seconds of one forward core over one row: the larger of
    operations over peak and bytes over bandwidth."""
    return max(
        attention_core_forward_ops(spec, seq) / peak["bf16_flops_per_s"],
        attention_core_forward_bytes(spec, seq, itemsize)
        / peak["hbm_bytes_per_s"])


def train_ops_per_row(spec):
    """Forward + backward operations one row (sequence) requires."""
    seq = spec["seq_len"]
    return 3 * (2 * forward_macs_per_token(spec) * seq
                + attention_cores(spec)
                * attention_core_forward_ops(spec, seq))
