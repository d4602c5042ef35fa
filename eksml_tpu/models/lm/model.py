"""JoyAI-LLM-Flash (DeepSeek-V3 family, arXiv:2412.19437), one of the
four sequence models of ``models/lm`` (the others: ``ouro.py``,
``laguna.py`` and ``xing.py``, which share this file's ``Matrix``,
``linear``, ``RMSNorm``, ``SwiGLU`` and chunked cross-entropy; Laguna
its expert layer too, Xing its ``MLA`` and expert layer), as a flax
module with the training losses:
multi-head latent attention in every block, a dense SwiGLU layer first,
then expert layers (256-way sigmoid routing, 8 a token, one shared
expert, the chip's held experts), one multi-token-prediction module of
depth 1, and next-token + 0.3 x next-next-token cross-entropy over the
held slice of the vocabulary.

``model.apply({"params": p}, batch, rng)`` returns the dict the trainer
expects (``total_loss`` and ``*_loss`` terms) plus the step's routing
counters (``moe_*``); ``batch["tokens"]`` is ``int32[rows, S + 1]``.

Every parameter is the one parameter of its module (``kernel``,
``scale`` or ``bias``), float32; compute is ``dtype``.
"""

from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from eksml_tpu.models.lm import attention, moe

# what leaves the step beside the losses, and the host span that
# carries them at log steps (train.Trainer.fit)
COUNTER_SPANS = {"moe_route": ("moe_pairs_held", "moe_load_max_over_mean",
                               "moe_pairs_dropped", "moe_rows_moved_share")}

# Init beside the matrices' LM.INIT_STD.  The embedding is drawn at 1.0,
# not at the family's 0.006: from random weights at 0.006 the residual
# stream is attention's prefix average, every token picks the same
# experts, and a chip's share of them gets none or all of the batch by
# the luck of the seed; at 1.0 the token decides, as in a trained model
# (benchmark/configs/joyai-llm-flash-ep16.json, assumed.init).
EMBED_INIT_STD = 1.0
ROUTER_BIAS_STD = 0.01      # the held selection-only bias


class Matrix(nn.Module):
    """One float32 ``kernel`` of the given shape, normal(std)."""
    shape: Tuple[int, ...]
    std: float

    @nn.compact
    def __call__(self):
        return self.param("kernel", nn.initializers.normal(self.std),
                          self.shape, jnp.float32)


def linear(x, features: int, std: float, dtype, name: str):
    """``x . kernel`` in ``dtype``; the kernel is the module ``name``'s
    (call inside a compact ``__call__``)."""
    kernel = Matrix((x.shape[-1], features), std, name=name)()
    return jnp.dot(x.astype(dtype), kernel.astype(dtype))


class RouterBias(nn.Module):
    """The selection-only correction bias: held, never trained here."""
    features: int
    std: float

    @nn.compact
    def __call__(self):
        return self.param("bias", nn.initializers.normal(self.std),
                          (self.features,), jnp.float32)


class RMSNorm(nn.Module):
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        y = x.astype(jnp.float32)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                              + self.eps)
        return (y * scale).astype(self.dtype)


def plain_inv_freq(dim: int, theta: float):
    """theta^(-2j/dim) for the dim / 2 pairs of a rotary width, float64."""
    return theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float):
    """YaRN's frequencies for a rotary width ``dim`` (arXiv:2309.00071,
    as ``transformers.modeling_rope_utils._compute_yarn_parameters``
    computes them): dimension j turns ``original_max x theta^(-2j/dim) /
    2 pi`` times over the original context; those that turn more than
    ``beta_fast`` times keep theta^(-2j/dim), those under ``beta_slow``
    take it divided by ``factor``, a linear ramp over j blends between
    the two bounds (rounded outward)."""
    def dimension_of(turns):
        return (dim * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dimension_of(beta_fast)), 0)
    high = min(math.ceil(dimension_of(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    plain = plain_inv_freq(dim, theta)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (plain / factor * ramp + plain * (1.0 - ramp)).astype(np.float32)


def rope(x, theta: float, inv_freq=None):
    """Rotary embedding on interleaved pairs (x[2j], x[2j+1]) of the
    last axis; ``x`` ``[B, S, H, D]``, position = index along S.  The
    frequencies are theta^(-2j/D), or ``inv_freq`` (``[D / 2]``: a
    YaRN table)."""
    d = x.shape[-1]
    if inv_freq is None:
        inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    else:
        inv = jnp.asarray(inv_freq, jnp.float32)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class SwiGLU(nn.Module):
    width: int
    std: float
    dtype: Any

    @nn.compact
    def __call__(self, h):
        g = linear(h, self.width, self.std, self.dtype, "gate")
        u = linear(h, self.width, self.std, self.dtype, "up")
        return linear(jax.nn.silu(g) * u, h.shape[-1], self.std,
                      self.dtype, "down")


class MLA(nn.Module):
    """Multi-head latent attention: low-rank q and shared low-rank kv,
    one rotary key shared by all heads.  ``rope_inv_freq`` (a tuple of
    ``QK_ROPE_HEAD_DIM / 2`` frequencies, e.g. YaRN's) replaces the plain
    table, ``softmax_factor`` multiplies the softmax scale ``(qk
    width)^-1/2``; the defaults are JoyAI's."""
    cfg: Any
    dtype: Any
    rope_inv_freq: Optional[Tuple[float, ...]] = None
    softmax_factor: float = 1.0

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        b, s, _ = h.shape
        nh, dn, dr, dv = (c.NUM_HEADS, c.QK_NOPE_HEAD_DIM,
                          c.QK_ROPE_HEAD_DIM, c.V_HEAD_DIM)
        lin = lambda x, n, name: linear(x, n, c.INIT_STD, self.dtype, name)
        norm = lambda name: RMSNorm(c.RMS_NORM_EPS, self.dtype, name=name)
        rot = lambda x: rope(x, c.ROPE_THETA, self.rope_inv_freq)
        with jax.named_scope("mla"):
            cq = norm("q_a_norm")(lin(h, c.Q_LORA_RANK, "q_a"))
            q = lin(cq, nh * (dn + dr), "q_b").reshape(b, s, nh, dn + dr)
            kva = lin(h, c.KV_LORA_RANK + dr, "kv_a")
            ckv = norm("kv_a_norm")(kva[..., :c.KV_LORA_RANK])
            k_rope = rot(kva[..., None, c.KV_LORA_RANK:])
            kv = lin(ckv, nh * (dn + dv), "kv_b").reshape(b, s, nh, dn + dv)
            q = jnp.concatenate([q[..., :dn], rot(q[..., dn:])], axis=-1)
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(k_rope, (b, s, nh, dr))],
                axis=-1)
            v = kv[..., dn:]
            q = q * jnp.asarray((dn + dr) ** -0.5 * self.softmax_factor,
                                q.dtype)
        with jax.named_scope("mla_core"):
            o = attention.causal_attention(q, k, v, c.ATTENTION_BLOCK)
        with jax.named_scope("mla"):
            return lin(o.reshape(b, s, nh * dv), h.shape[-1], "o")


class MoE(nn.Module):
    """Shared expert + the held routed experts.  ``selection_bias``
    False: a router with no selection-only bias (Laguna's)."""
    cfg: Any
    dtype: Any
    selection_bias: bool = True

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        b, s, d = h.shape
        first, count = c.EXPERTS_HELD
        w = c.MOE_INTERMEDIATE_SIZE
        flat = h.reshape(b * s, d)
        router = Matrix((d, c.N_ROUTED_EXPERTS), c.INIT_STD,
                        name="router")()
        bias = (RouterBias(c.N_ROUTED_EXPERTS, ROUTER_BIAS_STD,
                           name="router_bias")()
                if self.selection_bias else None)
        ids, gates = moe.route(flat, router, bias, c.NUM_EXPERTS_PER_TOK,
                               c.ROUTED_SCALING_FACTOR)
        self.sow("intermediates", "routing", ids)
        bank = lambda shape, name: Matrix(
            shape, c.INIT_STD, name=name)().astype(self.dtype)
        banks = (bank((count, d, w), "experts_gate"),
                 bank((count, d, w), "experts_up"),
                 bank((count, w, d), "experts_down"))
        if count == c.N_ROUTED_EXPERTS:
            routed, counters = moe.all_experts(flat, ids, gates, *banks)
        else:
            routed, counters = moe.held_experts(flat, ids, gates, *banks,
                                                first)
        with jax.named_scope("moe_shared"):
            shared = SwiGLU(w * c.N_SHARED_EXPERTS, c.INIT_STD, self.dtype,
                            name="shared")(h)
        return shared + routed.reshape(b, s, d), counters


class Block(nn.Module):
    cfg: Any
    dtype: Any
    dense: bool

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        norm = lambda name: RMSNorm(c.RMS_NORM_EPS, self.dtype, name=name)
        x = x + MLA(c, self.dtype, name="attn")(norm("attn_norm")(x))
        h = norm("mlp_norm")(x)
        if self.dense:
            with jax.named_scope("dense_mlp"):
                y = SwiGLU(c.INTERMEDIATE_SIZE, c.INIT_STD, self.dtype,
                           name="mlp")(h)
            return x + y, None
        y, counters = MoE(c, self.dtype, name="moe")(h)
        return x + y, counters


class JoyAIFlash(nn.Module):
    cfg: Any            # the LM config block
    dtype: Any = jnp.float32
    remat: bool = False

    @classmethod
    def from_config(cls, cfg) -> "JoyAIFlash":
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
        table = Matrix((c.VOCAB_ROWS, c.HIDDEN_SIZE), EMBED_INIT_STD,
                       name="embed")().astype(self.dtype)
        embed = lambda ids: jnp.take(table, ids, axis=0)
        head_kernel = Matrix((c.HIDDEN_SIZE, c.VOCAB_ROWS), c.INIT_STD,
                             name="head")().astype(self.dtype)
        norm = lambda name: RMSNorm(c.RMS_NORM_EPS, self.dtype, name=name)

        counters = []
        x = embed(tokens[:, :s])
        for i in range(c.NUM_LAYERS):
            x, cnt = block_cls(c, self.dtype, dense=i < c.FIRST_K_DENSE,
                               name=f"block{i}")(x)
            counters += [cnt] if cnt is not None else []
        ones = jnp.ones((tokens.shape[0], s), jnp.float32)
        with jax.named_scope("lm_loss"):
            ce = chunked_cross_entropy(
                norm("final_norm")(x), head_kernel, tokens[:, 1:], ones,
                c.LOSS_CHUNK)
        losses = {"ce_loss": ce}
        total = ce
        if c.NUM_MTP:
            # depth 1: position i sees the trunk's h_i and the embedding
            # of token i+1 and predicts token i+2; the last position has
            # no such target and is left out of the mean
            with jax.named_scope("mtp"):
                nxt = embed(tokens[:, 1:])
                merged = jnp.concatenate(
                    [norm("mtp_hnorm")(x), norm("mtp_enorm")(nxt)], axis=-1)
                y = linear(merged, c.HIDDEN_SIZE, c.INIT_STD, self.dtype,
                           "mtp_eh_proj")
                y, cnt = block_cls(c, self.dtype, dense=False,
                                   name="mtp_block")(y)
                counters.append(cnt)
                y = norm("mtp_final_norm")(y)
            targets = jnp.concatenate(
                [tokens[:, 2:], jnp.zeros_like(tokens[:, :1])], axis=1)
            weights = ones.at[:, -1].set(0.0)
            with jax.named_scope("lm_loss"):
                mtp = chunked_cross_entropy(y, head_kernel, targets,
                                            weights, c.LOSS_CHUNK)
            losses["mtp_loss"] = mtp
            total = ce + c.MTP_LOSS_WEIGHT * mtp
        losses["total_loss"] = total
        losses.update(routing_counters(counters))
        return losses


def routing_counters(counters) -> dict:
    """The step's ``moe_*`` counters from its expert layers' own
    (``COUNTER_SPANS``' keys): pairs summed, the load of the worst
    layer, the permutations' rows copied over the buffers' rows."""
    if not counters:
        return {}
    return {
        "moe_pairs_held": sum(k["pairs_held"] for k in counters),
        "moe_pairs_dropped": sum(k["pairs_dropped"] for k in counters),
        "moe_rows_moved_share": (sum(k["rows_moved"] for k in counters)
                                 / sum(k["pairs"] for k in counters)),
        "moe_load_max_over_mean": jnp.max(jnp.stack(
            [k["load_max_over_mean"] for k in counters]))}


def _chunk_losses(hc, head_kernel, tc):
    """-log softmax(hc . head)[tc] of one chunk of positions, float32:
    the one place a ``[chunk, vocabulary]`` array of logits exists."""
    logits = jnp.dot(hc, head_kernel, preferred_element_type=jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
    return lse - picked


def _chunk_size(n: int, chunk: int) -> int:
    """``chunk``, cut to ``n`` positions; ``n`` must be a multiple."""
    chunk = min(chunk, n)
    if n % chunk:
        raise ValueError(f"{n} positions are no multiple of the "
                         f"loss chunk {chunk}")
    return chunk


def chunked_cross_entropy(h, head_kernel, targets, weights, chunk: int):
    """Weighted mean of -log softmax(h . head)[target] over positions,
    ``chunk`` positions at a time: no ``[positions, vocabulary]`` array
    of logits outlives its chunk, forward or backward."""
    d = h.shape[-1]
    h, targets, weights = (h.reshape(-1, d), targets.reshape(-1),
                           weights.reshape(-1))
    n = h.shape[0]
    chunk = _chunk_size(n, chunk)

    @jax.checkpoint
    def one(hc, tc, wc):
        return jnp.sum(_chunk_losses(hc, head_kernel, tc) * wc)

    def step(total, xs):
        return total + one(*xs), None

    k = n // chunk
    total, _ = jax.lax.scan(
        step, jnp.zeros((), jnp.float32),
        (h.reshape(k, chunk, d), targets.reshape(k, chunk),
         weights.reshape(k, chunk)))
    return total / jnp.sum(weights)


def chunked_position_losses(h, head_kernel, targets, chunk: int):
    """-log softmax(h . head)[target] of every position, float32 in
    ``targets``' shape, ``chunk`` positions at a time under the same
    rule: what a caller weights position by position (Ouro's exit
    distribution) without paying for the logits twice."""
    d = h.shape[-1]
    n = targets.size
    chunk = _chunk_size(n, chunk)
    one = jax.checkpoint(
        lambda hc, tc: _chunk_losses(hc, head_kernel, tc))
    _, out = jax.lax.scan(
        lambda carry, xs: (carry, one(*xs)), None,
        (h.reshape(n // chunk, chunk, d),
         targets.reshape(n // chunk, chunk)))
    return out.reshape(targets.shape)


def decay_mask(params):
    """AdamW's decay on the matrices only: nothing on norm scales,
    nothing on the held router bias."""
    def decays(path, _):
        return path[-1].key == "kernel"

    return jax.tree_util.tree_map_with_path(decays, params)
