"""Laguna (poolside/Laguna-XS.2's ``config.json``), the third sequence
model of ``models/lm``, as a flax module with its training loss.

Pre-norm blocks, ``x += Attn(RMSNorm(x))`` then ``x += MLP(RMSNorm(x))``,
whose attention differs layer by layer (``LM.LAYER_TYPES``,
``LM.HEADS_PER_LAYER``): a ``full_attention`` layer sees every key at or
before the query, a ``sliding_attention`` layer the query's own position
and the ``SLIDING_WINDOW - 1`` before it; each has its own number of
query heads over the same ``NUM_KV_HEADS`` key-value heads of
``HEAD_DIM`` (query head h reads key-value head ``h // (H / Hkv)``) and
its own rotary table (``ROPE_FULL``: YaRN over half the head;
``ROPE_WINDOW``: the plain table over all of it), in the half-split
pairing.  One sigmoid gate a head a position, a projection of the
normed input, scales the attention output before ``o`` (``gating``).
The first ``FIRST_K_DENSE`` layers carry a SwiGLU, the rest JoyAI's
expert layer (``model.MoE``: sigmoid scores over all routed experts,
the top k a token, gates normalised over the selected and scaled, a
shared expert, the chip's held experts) with no selection bias.  Final
norm, an untied head over the held rows, mean next-token cross-entropy;
no MTP module and no auxiliary loss.

``model.apply({"params": p}, batch, rng)`` returns ``total_loss`` and
``ce_loss`` plus the step's counters: JoyAI's ``moe_*`` and
``window_tile_share`` (``attention.window_tile_share``, a constant of
the compiled step: what the sliding layers' cores let through of what
they visit).  Every parameter float32, compute in ``dtype``.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from eksml_tpu.models.lm import attention
from eksml_tpu.models.lm.model import (  # noqa: F401  (decay_mask: the seam's)
    COUNTER_SPANS as MOE_COUNTER_SPANS, EMBED_INIT_STD, Matrix, MoE, RMSNorm,
    SwiGLU, chunked_cross_entropy, decay_mask, linear, plain_inv_freq,
    routing_counters, yarn_inv_freq)
from eksml_tpu.models.lm.ouro import rotate_half

# JoyAI's routing counters on their span, and the window's on its own
COUNTER_SPANS = dict(MOE_COUNTER_SPANS, attn_window=("window_tile_share",))

FULL, SLIDING = "full_attention", "sliding_attention"


def rotary_table(rope, head_dim: int):
    """(frequencies ``[r / 2]``, cos-and-sin factor or None) of one of
    the two rotary blocks of the config."""
    dim = int(head_dim * rope.PARTIAL_ROTARY_FACTOR)
    if rope.TYPE == "yarn":
        return (yarn_inv_freq(dim, rope.THETA, rope.FACTOR,
                              rope.ORIGINAL_MAX_POSITION, rope.BETA_FAST,
                              rope.BETA_SLOW),
                float(rope.ATTENTION_FACTOR))
    return plain_inv_freq(dim, rope.THETA).astype(np.float32), None


class Attention(nn.Module):
    """Grouped-query attention of layer ``layer``, full or windowed by
    its type, with the gate a head on its output."""
    cfg: Any
    dtype: Any
    layer: int

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        b, s, _ = h.shape
        nh, nkv, dh = c.HEADS_PER_LAYER[self.layer], c.NUM_KV_HEADS, c.HEAD_DIM
        full = c.LAYER_TYPES[self.layer] == FULL
        inv_freq, scale = rotary_table(
            c.ROPE_FULL if full else c.ROPE_WINDOW, dh)
        lin = lambda x, n, name: linear(x, n, c.INIT_STD, self.dtype, name)
        with jax.named_scope("gqa"):
            q = lin(h, nh * dh, "q").reshape(b, s, nh, dh)
            k = lin(h, nkv * dh, "k").reshape(b, s, nkv, dh)
            v = lin(h, nkv * dh, "v").reshape(b, s, nkv, dh)
            gate = jax.nn.sigmoid(
                lin(h, nh, "g").astype(jnp.float32)).astype(self.dtype)
            q = (rotate_half(q, inv_freq, scale)
                 * jnp.asarray(dh ** -0.5, q.dtype))
            k = rotate_half(k, inv_freq, scale)
        core_scope = (jax.named_scope("gqa_core_full") if full
                      else jax.named_scope("gqa_core_window"))
        with core_scope:
            o = attention.causal_attention(
                q, k, v, c.ATTENTION_BLOCK,
                window=None if full else c.SLIDING_WINDOW)
        with jax.named_scope("gqa"):
            o = o * gate[..., None]
            return lin(o.reshape(b, s, nh * dh), h.shape[-1], "o")


class Block(nn.Module):
    cfg: Any
    dtype: Any
    layer: int

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        norm = lambda name: RMSNorm(c.RMS_NORM_EPS, self.dtype, name=name)
        x = x + Attention(c, self.dtype, self.layer,
                          name="attn")(norm("attn_norm")(x))
        h = norm("mlp_norm")(x)
        if self.layer < c.FIRST_K_DENSE:
            with jax.named_scope("dense_mlp"):
                y = SwiGLU(c.INTERMEDIATE_SIZE, c.INIT_STD, self.dtype,
                           name="mlp")(h)
            return x + y, None
        y, counters = MoE(c, self.dtype, selection_bias=False,
                          name="moe")(h)
        return x + y, counters


def sliding_tile_share(cfg, seq: int) -> float:
    """``attention.window_tile_share`` of the sliding layers' cores (one
    number: they share window, sequence and block sizes; with more
    query heads the same table a head), or 1.0 where no layer slides
    or the window holds the whole sequence."""
    sliding = [h for h, kind in zip(cfg.HEADS_PER_LAYER, cfg.LAYER_TYPES)
               if kind == SLIDING]
    if not sliding or cfg.SLIDING_WINDOW >= seq:
        return 1.0
    return attention.window_tile_share(
        sliding[0], cfg.NUM_KV_HEADS, seq, cfg.SLIDING_WINDOW,
        cfg.ATTENTION_BLOCK)


class Laguna(nn.Module):
    cfg: Any            # the LM config block
    dtype: Any = jnp.float32
    remat: bool = False

    @classmethod
    def from_config(cls, cfg) -> "Laguna":
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
        counters = []
        x = jnp.take(table, tokens[:, :s], axis=0)
        for i in range(c.NUM_LAYERS):
            x, cnt = block_cls(c, self.dtype, i, name=f"block{i}")(x)
            counters += [cnt] if cnt is not None else []
        with jax.named_scope("lm_loss"):
            x = RMSNorm(c.RMS_NORM_EPS, self.dtype, name="final_norm")(x)
            ce = chunked_cross_entropy(
                x, head_kernel, tokens[:, 1:],
                jnp.ones((tokens.shape[0], s), jnp.float32), c.LOSS_CHUNK)
        losses = {"ce_loss": ce, "total_loss": ce,
                  "window_tile_share": jnp.float32(sliding_tile_share(c, s))}
        losses.update(routing_counters(counters))
        return losses
