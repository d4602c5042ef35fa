"""Which way a leaf of the first gradient points, in numbers that fit
through ``harness.StepTap`` (it hands on one norm a leaf).

The comparison's leaf measures are gaps between norms.  Rounding that
is random in sign barely moves a norm (the gap is second order in the
noise), so a step computed in int8 reads like one computed in bfloat16
on all of them (PERF.md section 7, 0a).  A projection is first order:
for a fixed vector r of +-1, ``<mu + n, r> - <mu, r> = <n, r>``, of the
size of ``|n|`` whatever its direction.  ``project`` gives ``K`` such
inner products a leaf, against sign patterns hashed from the element's
index alone, so the program's side (Adam's ``mu`` on the device) and the
reference's (its ``mu``) use the same vectors without sharing an array.
``gaps`` turns the two sides' projections into one number a leaf:
the root mean square over the K of the gap, over the reference's norm
of the leaf, about ``|n| / |mu|`` (= sqrt(2 (1 - cosine)) for small n).
"""

from __future__ import annotations

import math
import statistics

K = 16
PREFIX = "_projection"      # top-level keys "<PREFIX><k>" beside mu's own


def _leaf_projections(x):
    """``[K]`` float32: sum of +-x, the sign of element i in projection
    k being bit k of a 32-bit mix (murmur3's finaliser) of i.  One
    fused reduction a projection: no ``[K, n]`` array of signs."""
    import jax.numpy as jnp

    x = x.reshape(-1).astype(jnp.float32)
    h = jnp.arange(x.shape[0], dtype=jnp.uint32) + jnp.uint32(0x9E3779B9)
    h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
    h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return jnp.stack([jnp.sum(jnp.where(((h >> k) & 1) == 1, x, -x))
                      for k in range(K)])


def project(tree):
    """{``PREFIX<k>``: tree of numbers}: each leaf's K projections, with
    their signs, computed where the leaf lives (one small jitted
    program a shape)."""
    import jax
    import numpy as np

    run = jax.jit(_leaf_projections)
    per_leaf = jax.tree.map(lambda x: np.asarray(run(x)), tree)
    return {f"{PREFIX}{k}": jax.tree.map(lambda a, k=k: a[k], per_leaf)
            for k in range(K)}


def magnitudes(tree) -> dict:
    """{"PREFIX<k>/<leaf>": |projection|} of a {leaf: array} dict: what
    the tap makes of ``project``'s trees, for the reference's side."""
    return {f"{prefix}/{name}": abs(float(v))
            for prefix, leaves in project(tree).items()
            for name, v in leaves.items()}


def gaps(program: dict, reference: dict, leaves) -> dict:
    """{leaf: root mean square over k of (|program's projection| -
    |reference's|) / reference's norm of the leaf}.  Both sides:
    {leaf: norm, "PREFIX<k>/<leaf>": |projection|} (the tap norms every
    leaf it is handed, so projections arrive as magnitudes: where one is
    far from zero, as most are, the gap of the magnitudes is the gap).
    Empty where a side has no projections."""
    out = {}
    for name in leaves:
        total = 0.0
        for k in range(K):
            key = f"{PREFIX}{k}/{name}"
            if key not in program or key not in reference:
                return {}
            total += (program[key] - reference[key]) ** 2
        out[name] = math.sqrt(total / K) / max(reference[name], 1e-30)
    return out


def median_and_worst(leaf_gaps: dict):
    if not leaf_gaps:
        return math.inf, math.inf
    return statistics.median(leaf_gaps.values()), max(leaf_gaps.values())
