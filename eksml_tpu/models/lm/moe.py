"""The expert layer's routed part, for the chip's share of the experts.

The router scores ALL ``n_routed`` experts (sigmoid, float32), selects
``top_k`` a token by score + selection-only bias (JoyAI's; Laguna's
router has none and selects by score) and gates by the scores alone
(normalised over the selected, times the scaling factor).
This chip holds the contiguous experts ``[first, first + count)``: the
token-expert pairs that name a held expert are sorted by expert and
multiplied group by group (``jax.lax.ragged_dot``: XLA's grouped
matrix product, whose work follows the group sizes); what the absent
experts would have added is left out.  No pair is dropped whatever the
routing: the sorted buffer has room for every pair a batch can hold.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def route(h, router_kernel, router_bias, top_k: int, scaling: float):
    """``h`` ``[T, D]`` -> (expert ids ``[T, k]`` int32, gates ``[T, k]``
    float32).  Float32 throughout, the matrix product at full precision:
    the top-k is a discrete choice and should not hang on bf16 rounding
    of the scores.  ``router_bias`` None: selection by the scores."""
    with jax.named_scope("moe_route"):
        logits = jnp.dot(h.astype(jnp.float32),
                         router_kernel.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        chosen_by = scores
        if router_bias is not None:
            chosen_by = scores + jax.lax.stop_gradient(
                router_bias.astype(jnp.float32))
        _, ids = jax.lax.top_k(chosen_by, top_k)
        picked = jnp.take_along_axis(scores, ids, axis=-1)
        gates = scaling * picked / jnp.sum(picked, axis=-1, keepdims=True)
        return ids.astype(jnp.int32), gates


@jax.custom_vjp
def permute_rows(x, order, inverse):
    """``x[order]`` for a permutation ``order`` of the rows whose
    inverse is ``inverse``.  Its transpose is the gather by ``inverse``:
    stated here because autodiff would write it as a scatter-add, which
    the TPU runs row by row (7.3 ms against the gather's 3 for 65,536
    rows of 2,048; PERF.md section 6, PR 28)."""
    return x[order]


def _permute_fwd(x, order, inverse):
    return x[order], (order, inverse)


def _permute_bwd(res, g):
    order, inverse = res
    return g[inverse], None, None


permute_rows.defvjp(_permute_fwd, _permute_bwd)


def held_experts(h, ids, gates, w_gate, w_up, w_down, first: int):
    """Sum over the held, selected experts of gate x SwiGLU expert.
    ``h`` ``[T, D]``; ``ids``/``gates`` ``[T, k]``; ``w_*`` ``[count, ..]``
    in the compute dtype.  Returns (``[T, D]``, counters)."""
    t, d = h.shape
    k = ids.shape[1]
    count = w_gate.shape[0]
    pairs = t * k
    with jax.named_scope("moe_dispatch"):
        local = ids.reshape(pairs) - first
        held = (local >= 0) & (local < count)
        # held pairs first, grouped by expert; the rest behind them
        key = jnp.where(held, local, count)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        inverse = jnp.zeros((pairs,), jnp.int32).at[order].set(
            jnp.arange(pairs, dtype=jnp.int32), unique_indices=True)
        group_sizes = jnp.sum(
            (key[:, None] == jnp.arange(count)[None, :]), axis=0,
            dtype=jnp.int32)
        n_held = jnp.sum(group_sizes)
        # rows past the held pairs belong to no group: masked on the
        # way in and out, never computed
        valid = (jnp.arange(pairs) < n_held)[:, None]
        # every pair's copy of its token, permuted into sorted order
        x = jnp.broadcast_to(h[:, None, :], (t, k, d)).reshape(pairs, d)
        x = jnp.where(valid, permute_rows(x, order, inverse), 0)
    with jax.named_scope("moe_experts"):
        g = jax.lax.ragged_dot(x, w_gate, group_sizes)
        u = jax.lax.ragged_dot(x, w_up, group_sizes)
        a = jnp.where(valid, jax.nn.silu(g) * u, 0).astype(h.dtype)
        y = jax.lax.ragged_dot(a, w_down, group_sizes)
        y = jnp.where(valid, y, 0)
    with jax.named_scope("moe_combine"):
        y = permute_rows(y, inverse, order)
        y = y.reshape(t, k, d) * gates[..., None].astype(y.dtype)
        out = jnp.sum(y, axis=1).astype(h.dtype)
    # rows of the sorted buffer that lie inside their own expert's
    # group: the pairs the grouped products really multiplied
    ends = jnp.cumsum(group_sizes)
    sorted_key = key[order]
    own = jnp.minimum(sorted_key, count - 1)
    row = jnp.arange(pairs)
    multiplied = ((sorted_key < count) & (row < ends[own])
                  & (row >= (ends - group_sizes)[own]))
    counters = {
        "pairs_held": n_held.astype(jnp.float32),
        # pairs the router sent to a held expert, less those multiplied
        "pairs_dropped": (jnp.sum(held, dtype=jnp.int32)
                          - jnp.sum(multiplied, dtype=jnp.int32)
                          ).astype(jnp.float32),
        "load_max_over_mean": (
            jnp.max(group_sizes).astype(jnp.float32) * count
            / jnp.maximum(n_held, 1).astype(jnp.float32)),
    }
    return out, counters
