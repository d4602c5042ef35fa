"""Fixed-size random subsampling under jit.

XLA-friendly replacement for the `np.random.choice` fg/bg subsampling
TensorPack does on the host (external, container/Dockerfile:16-19):
each candidate draws a uniform priority, non-candidates get -inf, and
`top_k` selects — identical in distribution to choice-without-
replacement, with static output shapes.  Shared by RPN anchor sampling
and proposal-target sampling.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def sample_by_priority(candidates: jnp.ndarray, rng: jax.Array, k: int,
                       limit: jnp.ndarray = None
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pick up to ``k`` true entries of bool ``candidates`` uniformly.

    Returns ``(idx [k], take [k])``: selected indices and which slots
    are real picks.  ``limit`` (traced scalar ≤ k) further caps the
    number taken.
    """
    n = candidates.shape[0]
    pri = jnp.where(candidates, jax.random.uniform(rng, (n,)), -jnp.inf)
    top, idx = jax.lax.top_k(pri, k)
    take = jnp.isfinite(top)
    if limit is not None:
        take = take & (jnp.arange(k) < limit)
    return idx, take


def picks_to_mask(idx: jnp.ndarray, take: jnp.ndarray, n: int) -> jnp.ndarray:
    """``sample_by_priority``'s picks as a boolean mask over ``n``."""
    return jnp.zeros(n, bool).at[idx].set(take)


def sample_mask_by_priority(candidates: jnp.ndarray, rng: jax.Array, k: int,
                            limit: jnp.ndarray = None) -> jnp.ndarray:
    """Same, as a boolean mask over the input."""
    idx, take = sample_by_priority(candidates, rng, k, limit)
    return picks_to_mask(idx, take, candidates.shape[0])
