"""The causal attention core of both sequence models: softmax(q kT) v
without an S x S array of scores.

Two formulations of the same arithmetic, chosen by the platform
(``resolve_impl``: the kernel on a TPU, ``jax.numpy`` elsewhere):

* ``xla``: plain ``jax.numpy``, one block of queries against the
  blocks of keys at or before it, with running maxima and sums in
  float32 (the online softmax); each block of queries is recomputed in
  the backward pass (``jax.checkpoint``), so what a layer keeps is q, k,
  v and its output.  Runs anywhere.
* ``splash``: jax's Pallas splash-attention kernel (forward, and one
  fused backward kernel for dq, dk and dv; a value width of its own),
  the TPU's path.  Its instructions are named ``splash_mha_fwd*`` and
  ``splash_mha_dkv*`` in the compiled step; the benchmark's roofline
  readers find them by name.

``q`` arrives already scaled by 1/sqrt(qk width).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG = -1e30     # the causal mask's fill: exp() of it is exactly 0


def resolve_impl(impl: str = "auto") -> str:
    if impl != "auto":
        return impl
    return "splash" if jax.default_backend() == "tpu" else "xla"


def full_scores_attention(q, k, v):
    """The S x S formulation, for tests and small sizes: q, k
    ``[B, S, H, Dqk]``, v ``[B, S, H, Dv]`` -> ``[B, S, H, Dv]``."""
    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, NEG)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def _query_block(qi, ks, vs, first_pos, block):
    """One block of queries ``qi`` ``[B, block, H, D]`` against its
    prefix of keys ``ks`` ``[n, B, block, H, D]`` (n blocks, the last
    one its own): running maximum m, sum l and accumulator acc."""
    b, _, h, _ = qi.shape
    dv = vs.shape[-1]
    rows = first_pos + jnp.arange(block)

    def step(carry, kv):
        m, l, acc = carry
        kj, vj, j = kv
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, kj,
                       preferred_element_type=jnp.float32)
        cols = j * block + jnp.arange(block)
        s = jnp.where(rows[:, None] >= cols[None, :], s, NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        scale = jnp.exp(m - m_new)
        l = l * scale + jnp.sum(p, axis=-1)
        acc = acc * scale[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(vj.dtype), vj,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    init = (jnp.full((b, h, block), NEG, jnp.float32),
            jnp.zeros((b, h, block), jnp.float32),
            jnp.zeros((b, h, block, dv), jnp.float32))
    (_, l, acc), _ = jax.lax.scan(
        step, init, (ks, vs, jnp.arange(ks.shape[0])))
    out = acc / l[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(vs.dtype)


def blockwise_attention(q, k, v, block: int):
    """Causal attention block by block; shapes as
    ``full_scores_attention``.  ``S`` must be a multiple of ``block``."""
    b, s, h, _ = q.shape
    if s % block:
        raise ValueError(f"sequence {s} is no multiple of the "
                         f"attention block {block}")
    n = s // block

    def blocks(x):      # [B, S, H, D] -> [n, B, block, H, D]
        return jnp.moveaxis(x.reshape(b, n, block, h, x.shape[-1]), 1, 0)

    qb, kb, vb = blocks(q), blocks(k), blocks(v)
    outs = []
    for i in range(n):
        fn = jax.checkpoint(functools.partial(
            _query_block, first_pos=i * block, block=block))
        outs.append(fn(qb[i], kb[:i + 1], vb[:i + 1]))
    return jnp.concatenate(outs, axis=1)


@functools.lru_cache(maxsize=8)
def _splash_kernel(heads: int, seq: int, interpret: bool):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)

    mask = sm.MultiHeadMask([sm.CausalMask((seq, seq))
                             for _ in range(heads)])
    # blocks of 1024 positions, 512 keys at a time in the inner loop,
    # one fused backward kernel: the fastest of the sizes that fit the
    # v5e's vmem at 192/128-wide heads (forward 4.45 ms against 29.97
    # at jax's default 128, forward + backward 14.53 against 93.1, for
    # 2 rows x 32 heads x 4096; PERF.md section 6, PR 28)
    block, inner = min(1024, seq), min(512, seq)
    sizes = sk.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=inner,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=inner,
        use_fused_bwd_kernel=True)
    # the kernel object holds the mask's block tables as arrays: made
    # concrete here, so that one object serves every trace that calls it
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mha(mask, head_shards=1, q_seq_shards=1,
                                  block_sizes=sizes, interpret=interpret)


def splash_attention(q, k, v):
    """jax's splash-attention kernel over ``[B, S, H, D]`` operands
    (it takes ``[H, S, D]`` per batch row); off a TPU it runs in
    Pallas's interpreter (tests)."""
    kernel = _splash_kernel(q.shape[2], q.shape[1],
                            jax.default_backend() != "tpu")
    t = functools.partial(jnp.transpose, axes=(0, 2, 1, 3))
    return t(jax.vmap(kernel)(t(q), t(k), t(v)))


def causal_attention(q, k, v, block: int, impl: str = "auto"):
    """``impl`` other than ``auto`` is for tests that hold one
    formulation against the other."""
    if resolve_impl(impl) == "splash":
        return splash_attention(q, k, v)
    return blockwise_attention(q, k, v, min(block, q.shape[1]))
