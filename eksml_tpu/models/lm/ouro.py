"""Ouro (LoopLM, "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741 section 3; ByteDance/Ouro-2.6B's ``config.json`` and
``modeling_ouro.py``), the second sequence model of ``models/lm``, as a
flax module with the paper's stage-I training loss.

One stack of ``NUM_LAYERS`` blocks is applied ``UT_STEPS`` times with
the SAME parameters: pass t starts from the normed state h^{t-1} the
pass before it ended with (h^0 = the embedding rows).  A block is a
"sandwich": a norm before and a norm after each of its two sub-layers,
plain multi-head attention (q, k and v heads of one width, rotary in
the half-split pairing, causal) and a SwiGLU.  After every pass the one
final norm, the one head and the one halting gate give that pass's
per-position cross-entropy CE^t and exit probability lambda^t; the exit
distribution is p_t = lambda^t prod_{j<t} (1 - lambda^j), the last pass
taking what is left, and the loss is

    mean over positions of  sum_t p_t CE^t  -  beta H(p).

The loop over passes is a scan with the parameters broadcast
(``nn.scan`` over ``Pass``): the step holds one pass's instructions,
run ``UT_STEPS`` times, where a Python loop would hold every pass's
(four times the attention kernels to compile and to keep in the
compile cache; PERF.md section 6, PR 33, has both measured).  Each
weight's gradient is the sum over its ``UT_STEPS`` uses, and remat
keeps one input a block APPLICATION (passes x layers of them), not a
block.  The passes' normed states leave the scan stacked and go through
the head in ONE chunked scan (``model.chunked_position_losses``: no
``[positions, vocabulary]`` array outlives its chunk), whose
per-position losses the exit distribution then weights.

``model.apply({"params": p}, batch, rng)`` returns ``total_loss``,
``ce_pass<t>_loss`` (plain means of CE^t), ``expected_ce_loss``,
``exit_entropy_loss`` (= -beta mean H) and the step's counters
(``loop_exit_p<t>``, ``loop_exit_entropy``, ``loop_ce_pass<t>``).
Every parameter float32, compute in ``dtype``; the gate's logit, the
exit distribution, its entropy and the losses in float32.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from eksml_tpu.models.lm import attention
from eksml_tpu.models.lm.model import (  # noqa: F401  (decay_mask: the seam's)
    Matrix, RMSNorm, SwiGLU, chunked_position_losses, decay_mask, linear)


def counter_spans(passes: int) -> dict:
    """What leaves the step beside the losses, and the host span that
    carries it at log steps (as ``model.COUNTER_SPANS``; here the keys
    follow the number of passes)."""
    each = range(1, passes + 1)
    return {"loop_exit": tuple(
        [f"loop_exit_p{t}" for t in each] + ["loop_exit_entropy"]
        + [f"loop_ce_pass{t}" for t in each])}


def rotate_half(x, inv_freq, scale=None):
    """Rotary embedding by the frequencies ``inv_freq`` ``[r / 2]`` in
    the half-split pairing (x[j], x[j + r/2]) of the first r dimensions
    of the last axis, the rest unrotated; ``x`` ``[B, S, H, D]``,
    position = index along S; cos and sin times ``scale`` (YaRN's
    attention factor) where one is given."""
    r = 2 * inv_freq.shape[0]
    ang = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
           * inv_freq[None, :])
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    xf = x.astype(jnp.float32)
    a, b = xf[..., :r // 2], xf[..., r // 2:r]
    parts = [a * cos - b * sin, b * cos + a * sin]
    if r < x.shape[-1]:
        parts.append(xf[..., r:])
    return jnp.concatenate(parts, axis=-1).astype(x.dtype)


def rope_half(x, theta: float):
    """``rotate_half`` over the whole last axis at the plain table
    theta^(-2j/d) (the Llama convention, which Ouro's modelling file
    follows; ``model.rope`` pairs neighbours, which is JoyAI's)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    return rotate_half(x, inv)


class Attention(nn.Module):
    """Plain multi-head attention: as many key-value heads as query
    heads, one width, no bias."""
    cfg: Any
    dtype: Any

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        b, s, _ = h.shape
        nh, dh = c.NUM_HEADS, c.HEAD_DIM
        lin = lambda x, n, name: linear(x, n, c.INIT_STD, self.dtype, name)
        with jax.named_scope("loop_attn"):
            q, k, v = (lin(h, nh * dh, name).reshape(b, s, nh, dh)
                       for name in ("q", "k", "v"))
            q = rope_half(q, c.ROPE_THETA) * jnp.asarray(dh ** -0.5, q.dtype)
            k = rope_half(k, c.ROPE_THETA)
        with jax.named_scope("loop_attn_core"):
            o = attention.causal_attention(q, k, v, c.ATTENTION_BLOCK)
        with jax.named_scope("loop_attn"):
            return lin(o.reshape(b, s, nh * dh), h.shape[-1], "o")


class Block(nn.Module):
    """u + norm(attn(norm(u))), then u + norm(mlp(norm(u)))."""
    cfg: Any
    dtype: Any

    @nn.compact
    def __call__(self, u):
        c = self.cfg
        norm = lambda name: RMSNorm(c.RMS_NORM_EPS, self.dtype, name=name)
        y = Attention(c, self.dtype, name="attn")(norm("attn_norm")(u))
        with jax.named_scope("loop_attn"):
            u = u + norm("attn_post_norm")(y)
        with jax.named_scope("loop_mlp"):
            y = SwiGLU(c.INTERMEDIATE_SIZE, c.INIT_STD, self.dtype,
                       name="mlp")(norm("mlp_norm")(u))
            return u + norm("mlp_post_norm")(y)


class Gate(nn.Module):
    """The halting gate's logit w . h + b, float32 (a matrix of one
    column and its bias, zero at the start: lambda = 1/2)."""
    std: float

    @nn.compact
    def __call__(self, h):
        kernel = self.param("kernel", nn.initializers.normal(self.std),
                            (h.shape[-1], 1), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (1,), jnp.float32)
        z = jnp.dot(h.astype(jnp.float32), kernel,
                    precision=jax.lax.Precision.HIGHEST)
        return z[..., 0] + bias[0]


def exit_distribution(logits):
    """Gate logits ``[T, ...]`` -> (p ``[T, ...]``, entropy ``[...]``),
    float32.  p_t = lambda_t prod_{j<t} (1 - lambda_j) for t < T, and
    the last pass takes the rest (its own lambda enters nowhere); the
    products are sums of log-sigmoids."""
    stay = jax.nn.log_sigmoid(-logits[:-1])          # log(1 - lambda_j)
    before = jnp.concatenate(
        [jnp.zeros_like(logits[:1]), jnp.cumsum(stay, axis=0)], axis=0)
    log_p = before + jnp.concatenate(
        [jax.nn.log_sigmoid(logits[:-1]), jnp.zeros_like(logits[:1])],
        axis=0)
    p = jnp.exp(log_p)
    return p, -jnp.sum(p * log_p, axis=0)


class Pass(nn.Module):
    """One pass through the stack: the blocks, the final norm, the
    gate.  (carry h) -> (h normed, (h normed, gate logit)): scanned
    with its parameters broadcast, so every pass runs the same ones."""
    cfg: Any
    dtype: Any
    remat: bool

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        block_cls = nn.remat(Block) if self.remat else Block
        for i in range(c.NUM_LAYERS):
            h = block_cls(c, self.dtype, name=f"block{i}")(h)
        with jax.named_scope("loop_head"):
            h = RMSNorm(c.RMS_NORM_EPS, self.dtype, name="final_norm")(h)
        with jax.named_scope("loop_exit"):
            z = Gate(c.INIT_STD, name="gate")(h)
        return h, (h, z)        # the next pass starts from the normed h


class Ouro(nn.Module):
    cfg: Any            # the LM config block
    dtype: Any = jnp.float32
    remat: bool = False

    @classmethod
    def from_config(cls, cfg) -> "Ouro":
        return cls(cfg=cfg.LM, remat=bool(cfg.TRAIN.REMAT),
                   dtype=(jnp.bfloat16 if cfg.TRAIN.PRECISION == "bfloat16"
                          else jnp.float32))

    @nn.compact
    def __call__(self, batch, rng=None):
        del rng                      # nothing in this model is sampled
        c = self.cfg
        tokens = batch["tokens"]
        s = tokens.shape[1] - 1
        passes = c.UT_STEPS
        table = Matrix((c.VOCAB_ROWS, c.HIDDEN_SIZE), c.INIT_STD,
                       name="embed")().astype(self.dtype)
        head_kernel = Matrix((c.HIDDEN_SIZE, c.VOCAB_ROWS), c.INIT_STD,
                             name="head")().astype(self.dtype)
        loop = nn.scan(Pass, variable_broadcast="params",
                       split_rngs={"params": False}, length=passes)(
                           c, self.dtype, self.remat, name="loop")
        _, (states, logits) = loop(jnp.take(table, tokens[:, :s], axis=0))
        with jax.named_scope("loop_head"):
            targets = jnp.broadcast_to(tokens[:, 1:],
                                       (passes,) + tokens[:, 1:].shape)
            ce = chunked_position_losses(states, head_kernel, targets,
                                         c.LOSS_CHUNK)
        with jax.named_scope("loop_exit"):
            p, entropy = exit_distribution(logits)
            expected = jnp.mean(jnp.sum(p * ce, axis=0))
            exit_entropy = -c.EXIT_ENTROPY_WEIGHT * jnp.mean(entropy)
        losses = {"expected_ce_loss": expected,
                  "exit_entropy_loss": exit_entropy,
                  "total_loss": expected + exit_entropy,
                  "loop_exit_entropy": jnp.mean(entropy)}
        for t in range(passes):
            losses[f"ce_pass{t + 1}_loss"] = jnp.mean(ce[t])
            losses[f"loop_ce_pass{t + 1}"] = losses[f"ce_pass{t + 1}_loss"]
            losses[f"loop_exit_p{t + 1}"] = jnp.mean(p[t])
        return losses
