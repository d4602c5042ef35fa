"""Manifold-constrained hyper-connections (mHC, DeepSeek-AI,
arXiv:2512.24880, on hyper-connections, Zhu et al., arXiv:2409.19606):
a residual path of ``n`` streams, mixed around every sublayer by maps
that the streams themselves decide.  Any block can use them: the model
expands its embedding into the streams, runs each sublayer ``F`` between
``read`` and ``write`` with a ``Maps`` module of its own, and reduces the
streams before its final norm (``models/lm/xing.py``).

For one position, the streams ``x`` in R^{n x C} and one sublayer F:

    x~      = RMSNorm(vec(x))                  over the n C values, a
                                               scale a value
    H~_pre  = a_pre  (x~ phi_pre)  + b_pre     phi_pre  in R^{nC x n}
    H~_post = a_post (x~ phi_post) + b_post    phi_post in R^{nC x n}
    H~_res  = a_res  mat(x~ phi_res) + b_res   phi_res  in R^{nC x n^2},
                                               mat row-major: [i, j]
    H_pre   = sigmoid(H~_pre),  H_post = 2 sigmoid(H~_post)
    H_res   = Sinkhorn-Knopp(clamp(H~_res, lo, hi)): M = exp(.), then
              ``iters`` times rows to sum one, then columns to sum one,
              each denominator + eps
    h       = H_pre x          sum_i H_pre[i] x_i: what F reads (F keeps
                               its own pre-norm)
    x'      = H_res x + H_post^T F(h)
                               x'_i = sum_j H_res[i, j] x_j + H_post[i] y

``phi`` is one ``[nC, 2n + n^2]`` kernel (the three projections side by
side, 24 columns at n = 4), ``alpha`` the three gains, ``bias`` the
``2n + n^2`` offsets.  The model copies its embedding into every stream
(``expand``) and sums the streams before its final norm (``reduce``).

Init (``assumed.hc_init`` of the configuration that runs it): ``phi``
normal with sigma ``(n C)^-1/2 / 2`` (``phi_std``), so that ``x~ phi``,
of a unit-RMS ``x~``, has standard deviation one half at any width;
``alpha`` one and ``bias`` zero.  So every map depends on its input
from the first step: H_pre and H_post vary by stream and position and
H_res is a doubly stochastic matrix of its own for every position, to
which 20 iterations come within 1e-5 everywhere (at standard deviation
one, 0.4% of positions stay further off).

The maps, the reads and the writes run in float32 whatever the compute
dtype (the projection at full precision: it decides how the streams
mix, as the router's decides the experts); the streams are kept in the
compute dtype.  Scopes: ``mhc_pre`` (the norm, the projection, the
gates, Sinkhorn, ``H_pre x`` and the final sum) and ``mhc_post`` (the
stream update and the expansion), each a component of
``profiling/attribution.SCOPE_RULES``.
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from eksml_tpu.models.lm.model import Matrix, RMSNorm

# the step's counters (``counters``) and the host span that carries them
# at log steps (train.Trainer.fit)
COUNTER_SPANS = {"mhc": ("mhc_sinkhorn_err", "mhc_res_diag")}
# the maps' gains and offsets at init (``phi``'s sigma: ``phi_std``)
ALPHA_INIT = 1.0
BIAS_INIT = 0.0


def phi_std(streams: int, width: int) -> float:
    """sigma of ``phi``'s normal init: ``x~ phi`` of standard deviation
    one half at any width."""
    return 0.5 * (streams * width) ** -0.5


def sinkhorn(logits, iters: int, eps: float):
    """exp(logits) ``[..., n, n]`` projected towards the doubly
    stochastic matrices: ``iters`` alternations of a row step and a
    column step, each denominator + ``eps``; differentiable through
    every iteration."""
    def one(_, m):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=-2, keepdims=True) + eps)

    return jax.lax.fori_loop(0, iters, one, jnp.exp(logits))


class Param(nn.Module):
    """One float32 parameter named ``kind`` (``scale`` or ``bias``)."""
    shape: Tuple[int, ...]
    kind: str
    initializer: Callable

    @nn.compact
    def __call__(self):
        return self.param(self.kind, self.initializer, self.shape,
                          jnp.float32)


class Maps(nn.Module):
    """One sublayer's ``(H_pre [.., n], H_post [.., n], H_res [.., n,
    n])`` from the streams ``x`` ``[B, S, n, C]``, float32."""
    cfg: Any        # the LM block: HC_*, RMS_NORM_EPS

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        b, s, n, d = x.shape
        width = 2 * n + n * n
        with jax.named_scope("mhc_pre"):
            v = RMSNorm(c.RMS_NORM_EPS, jnp.float32, name="norm")(
                x.reshape(b, s, n * d))
            phi = Matrix((n * d, width), phi_std(n, d), name="phi")()
            alpha = Param((3,), "scale",
                          nn.initializers.constant(ALPHA_INIT),
                          name="alpha")()
            bias = Param((width,), "bias",
                         nn.initializers.constant(BIAS_INIT), name="bias")()
            z = jnp.dot(v, phi, precision=jax.lax.Precision.HIGHEST)
            pre = jax.nn.sigmoid(alpha[0] * z[..., :n] + bias[:n])
            post = 2.0 * jax.nn.sigmoid(alpha[1] * z[..., n:2 * n]
                                        + bias[n:2 * n])
            lo, hi = c.HC_RES_CLAMP
            res = alpha[2] * z[..., 2 * n:] + bias[2 * n:]
            res = sinkhorn(jnp.clip(res.reshape(b, s, n, n), lo, hi),
                           c.HC_SINKHORN_ITERS, c.HC_EPS)
        return pre, post, res


def expand(h, streams: int):
    """The embedding ``[B, S, C]`` copied into every stream."""
    with jax.named_scope("mhc_post"):
        return jnp.broadcast_to(h[..., None, :],
                                h.shape[:-1] + (streams, h.shape[-1]))


def read(x, pre):
    """``h = sum_i H_pre[i] x_i`` ``[B, S, C]``, in the streams' dtype."""
    with jax.named_scope("mhc_pre"):
        return jnp.sum(pre[..., None] * x.astype(jnp.float32),
                       axis=-2).astype(x.dtype)


def write(x, y, post, res):
    """``x'_i = sum_j H_res[i, j] x_j + H_post[i] y``, in the streams'
    dtype."""
    with jax.named_scope("mhc_post"):
        xf = x.astype(jnp.float32)[..., None, :, :]       # [.., 1, n, C]
        mixed = jnp.sum(res[..., None] * xf, axis=-2)     # [.., n, C]
        yf = y.astype(jnp.float32)[..., None, :]          # [.., 1, C]
        return (mixed + post[..., None] * yf).astype(x.dtype)


def reduce(x):
    """The streams summed, ``[B, S, C]``: what the final norm reads."""
    with jax.named_scope("mhc_pre"):
        return jnp.sum(x.astype(jnp.float32), axis=-2).astype(x.dtype)


def res_stats(res) -> dict:
    """Of one sublayer's ``H_res``: the largest ``|row or column sum -
    1|`` over its positions, and the mean of ``trace / n`` (the share
    each stream keeps of itself)."""
    res = jax.lax.stop_gradient(res)
    n = res.shape[-1]
    err = jnp.maximum(jnp.max(jnp.abs(jnp.sum(res, axis=-1) - 1.0)),
                      jnp.max(jnp.abs(jnp.sum(res, axis=-2) - 1.0)))
    diag = jnp.mean(jnp.trace(res, axis1=-2, axis2=-1)) / n
    return {"err": err, "diag": diag}


def counters(stats) -> dict:
    """The step's ``COUNTER_SPANS`` keys from its sublayers' stats: the
    worst projection and the mean kept share."""
    if not stats:
        return {}
    return {"mhc_sinkhorn_err": jnp.max(jnp.stack([s["err"] for s in stats])),
            "mhc_res_diag": jnp.mean(jnp.stack([s["diag"] for s in stats]))}
