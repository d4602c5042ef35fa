"""Xing4.0 (XingChen-AGI/Xing4.0-29B-A4B's ``config.json``), the fourth
sequence model of ``models/lm``, as a flax module with its training
loss.

JoyAI's block (DeepSeek-V3 family: ``model.MLA``; a SwiGLU in the first
``FIRST_K_DENSE`` layers, the expert layer ``model.MoE`` after them:
sigmoid scores over all routed experts, the top k a token by score +
the held selection-only bias, gates normalised over the selected and
scaled, a shared expert, the chip's held experts) on ``HC_MULT``
residual streams instead of one: each of a block's two sublayers reads
a mix of the streams and writes its output back into all of them
through the manifold-constrained hyper-connection of ``mhc.py``, whose
equations that module states.  For block l with streams x ``[B, S, n,
C]``:

    h = H_pre x;  x = H_res x + H_post^T MLA(RMSNorm(h))
    h = H_pre' x; x = H_res' x + H_post'^T MLP(RMSNorm(h))

with the maps of each sublayer its own (``attn_hc``, ``mlp_hc``) and
each sublayer's pre-norm kept.  The embedding is copied into every
stream; the streams are summed before the final norm, an untied head
over the held rows and the mean next-token cross-entropy.  No MTP
module: it follows the last layer, on the pipeline's last stage
(``LM.NUM_MTP`` is held at 0).

MLA's rotary is DeepSeek-V3's YaRN (``rotary``): ``model.yarn_inv_freq``
over the ``QK_ROPE_HEAD_DIM`` rotary width in JoyAI's interleaved
pairing and the softmax scale times ``mscale(factor,
mscale_all_dim)^2``, where ``mscale(f, m) = 0.1 m ln f + 1``.
DeepSeek-V3 also multiplies cos and sin by ``mscale(factor, mscale) /
mscale(factor, mscale_all_dim)``, which is 1 for the published
``mscale == mscale_all_dim`` (``finalize_configs`` holds the two equal).

``model.apply({"params": p}, batch, rng)`` returns ``total_loss`` and
``ce_loss`` plus the step's counters: JoyAI's ``moe_*`` and the
hyper-connections' ``mhc_sinkhorn_err`` / ``mhc_res_diag``
(``mhc.counters``).  Every parameter float32, compute in ``dtype``.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from eksml_tpu.models.lm import mhc
from eksml_tpu.models.lm.model import (  # noqa: F401  (decay_mask: the seam's)
    COUNTER_SPANS as MOE_COUNTER_SPANS, EMBED_INIT_STD, MLA, Matrix, MoE,
    RMSNorm, SwiGLU, chunked_cross_entropy, decay_mask, routing_counters,
    yarn_inv_freq)

# JoyAI's routing counters on their span, the streams' on theirs
COUNTER_SPANS = dict(MOE_COUNTER_SPANS, **mhc.COUNTER_SPANS)


def mscale(factor: float, m: float) -> float:
    """DeepSeek-V3's ``yarn_get_mscale``: 0.1 m ln(factor) + 1 (1 where
    the context is not stretched)."""
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rotary(lm) -> dict:
    """``MLA``'s rotary arguments of the config's ``ROPE_SCALING``:
    YaRN's frequencies and the softmax factor."""
    r = lm.ROPE_SCALING
    inv = yarn_inv_freq(lm.QK_ROPE_HEAD_DIM, lm.ROPE_THETA, r.FACTOR,
                        r.ORIGINAL_MAX_POSITION, r.BETA_FAST, r.BETA_SLOW)
    return {"rope_inv_freq": tuple(float(f) for f in inv),
            "softmax_factor": mscale(r.FACTOR, r.MSCALE_ALL_DIM) ** 2}


class Block(nn.Module):
    cfg: Any
    dtype: Any
    dense: bool

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        norm = lambda name: RMSNorm(c.RMS_NORM_EPS, self.dtype, name=name)
        pre, post, res = mhc.Maps(c, name="attn_hc")(x)
        y = MLA(c, self.dtype, **rotary(c), name="attn")(
            norm("attn_norm")(mhc.read(x, pre)))
        x = mhc.write(x, y, post, res)
        stats = [mhc.res_stats(res)]
        pre, post, res = mhc.Maps(c, name="mlp_hc")(x)
        h = norm("mlp_norm")(mhc.read(x, pre))
        counters = None
        if self.dense:
            with jax.named_scope("dense_mlp"):
                y = SwiGLU(c.INTERMEDIATE_SIZE, c.INIT_STD, self.dtype,
                           name="mlp")(h)
        else:
            y, counters = MoE(c, self.dtype, name="moe")(h)
        stats.append(mhc.res_stats(res))
        return mhc.write(x, y, post, res), counters, stats


class Xing(nn.Module):
    cfg: Any            # the LM config block
    dtype: Any = jnp.float32
    remat: bool = False

    @classmethod
    def from_config(cls, cfg) -> "Xing":
        return cls(cfg=cfg.LM, remat=bool(cfg.TRAIN.REMAT),
                   dtype=(jnp.bfloat16 if cfg.TRAIN.PRECISION == "bfloat16"
                          else jnp.float32))

    @nn.compact
    def __call__(self, batch, rng=None):
        del rng                      # nothing in this model is sampled
        c = self.cfg
        tokens = batch["tokens"]
        s = tokens.shape[1] - 1
        block_cls = nn.remat(Block) if self.remat else Block
        # the embedding at sigma 1.0 for JoyAI's reason: the token, not
        # attention's prefix average, decides the routing from random
        # weights (model.EMBED_INIT_STD)
        table = Matrix((c.VOCAB_ROWS, c.HIDDEN_SIZE), EMBED_INIT_STD,
                       name="embed")().astype(self.dtype)
        head_kernel = Matrix((c.HIDDEN_SIZE, c.VOCAB_ROWS), c.INIT_STD,
                             name="head")().astype(self.dtype)
        x = mhc.expand(jnp.take(table, tokens[:, :s], axis=0), c.HC_MULT)
        counters, stats = [], []
        for i in range(c.NUM_LAYERS):
            x, cnt, st = block_cls(c, self.dtype, dense=i < c.FIRST_K_DENSE,
                                   name=f"block{i}")(x)
            counters += [cnt] if cnt is not None else []
            stats += st
        h = mhc.reduce(x)
        with jax.named_scope("lm_loss"):
            h = RMSNorm(c.RMS_NORM_EPS, self.dtype, name="final_norm")(h)
            ce = chunked_cross_entropy(
                h, head_kernel, tokens[:, 1:],
                jnp.ones((tokens.shape[0], s), jnp.float32), c.LOSS_CHUNK)
        losses = {"ce_loss": ce, "total_loss": ce}
        losses.update(routing_counters(counters))
        losses.update(mhc.counters(stats))
        return losses
