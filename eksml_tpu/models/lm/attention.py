"""The causal attention core of the sequence models: softmax(q kT) v
without an S x S array of scores, over all keys at or before the query
or, with a ``window`` w, over the query's own position and the w - 1
before it (``i - w < j <= i``), and with as many key-value heads as
query heads or fewer (grouped: query head h reads key-value head
``h // (H / Hkv)``).

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
  ``splash_mha_dkv*`` in the compiled step (``splash_mqa_*`` where the
  heads are grouped: one key-value head is read once for its group of
  query heads, no copy of K or V a query head); the benchmark's
  roofline readers find them by name.

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


def _per_query_head(q, k, v):
    """k and v with every key-value head repeated for its group of
    query heads (the formulations that hold no kernel)."""
    group = q.shape[2] // k.shape[2]
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)


def full_scores_attention(q, k, v, window=None):
    """The S x S formulation, for tests and small sizes: q
    ``[B, S, H, Dqk]``, k ``[B, S, Hkv, Dqk]``, v ``[B, S, Hkv, Dv]``
    -> ``[B, S, H, Dv]``."""
    s = q.shape[1]
    k, v = _per_query_head(q, k, v)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    causal = jnp.tril(jnp.ones((s, s), bool))
    if window is not None:
        causal &= ~jnp.tril(jnp.ones((s, s), bool), -window)
    scores = jnp.where(causal, scores, NEG)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def _query_block(qi, ks, vs, first_pos, block, first_key=0, window=None):
    """One block of queries ``qi`` ``[B, block, H, D]`` against the
    blocks of keys ``ks`` ``[n, B, block, H, D]`` it can see (n blocks
    from block ``first_key`` on, the last one its own): running maximum
    m, sum l and accumulator acc."""
    b, _, h, _ = qi.shape
    dv = vs.shape[-1]
    rows = first_pos + jnp.arange(block)

    def step(carry, kv):
        m, l, acc = carry
        kj, vj, j = kv
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, kj,
                       preferred_element_type=jnp.float32)
        cols = j * block + jnp.arange(block)
        seen = rows[:, None] >= cols[None, :]
        if window is not None:
            seen &= rows[:, None] - cols[None, :] < window
        s = jnp.where(seen, s, NEG)
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
        step, init,
        (ks, vs, jnp.arange(first_key, first_key + ks.shape[0])))
    out = acc / l[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(vs.dtype)


def first_key_block(i: int, block: int, window) -> int:
    """The first block of keys that the ``i``-th block of queries can
    see: block 0, or with a window the block that holds the first
    query's farthest key."""
    if window is None:
        return 0
    return max(0, (i * block - window + 1) // block)


def blockwise_attention(q, k, v, block: int, window=None):
    """Causal attention block by block; shapes as
    ``full_scores_attention``.  ``S`` must be a multiple of ``block``.
    No block of keys that lies wholly outside the window is visited."""
    b, s, h, _ = q.shape
    if s % block:
        raise ValueError(f"sequence {s} is no multiple of the "
                         f"attention block {block}")
    n = s // block
    k, v = _per_query_head(q, k, v)

    def blocks(x):      # [B, S, H, D] -> [n, B, block, H, D]
        return jnp.moveaxis(x.reshape(b, n, block, h, x.shape[-1]), 1, 0)

    qb, kb, vb = blocks(q), blocks(k), blocks(v)
    outs = []
    for i in range(n):
        first = first_key_block(i, block, window)
        fn = jax.checkpoint(functools.partial(
            _query_block, first_pos=i * block, block=block,
            first_key=first, window=window))
        outs.append(fn(qb[i], kb[first:i + 1], vb[first:i + 1]))
    return jnp.concatenate(outs, axis=1)


@functools.lru_cache(maxsize=8)
def _splash_kernel(heads: int, kv_heads: int, seq: int, window,
                   interpret: bool):
    """The kernel of one (query heads, key-value heads, sequence,
    window).  Equal head counts: ``make_splash_mha`` over ``heads``
    masks, called with ``[H, S, D]`` operands.  Fewer key-value heads:
    ``make_splash_mqa`` over one group's ``heads / kv_heads`` masks,
    called with q ``[H / Hkv, S, D]`` and ONE key-value head's k and v
    ``[S, D]``, which the kernel reads once for the whole group."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)

    def head_mask():
        if window is None:
            return sm.CausalMask((seq, seq))
        # the query's own position and the window - 1 before it
        return sm.LocalMask((seq, seq), window_size=(window - 1, 0),
                            offset=0)

    grouped = kv_heads != heads
    mask = sm.MultiHeadMask([head_mask()
                             for _ in range(heads // kv_heads)])
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
    make = sk.make_splash_mqa if grouped else sk.make_splash_mha
    # the kernel object holds the mask's block tables as arrays: made
    # concrete here, so that one object serves every trace that calls it
    with jax.ensure_compile_time_eval():
        return make(mask, head_shards=1, q_seq_shards=1,
                    block_sizes=sizes, interpret=interpret)


def splash_attention(q, k, v, window=None):
    """jax's splash-attention kernel over ``[B, S, H, D]`` operands
    (it takes ``[H, S, D]`` per batch row, or with grouped heads one
    group's q and one key-value head's k and v per row and group); off
    a TPU it runs in Pallas's interpreter (tests)."""
    b, s, h, _ = q.shape
    hkv = k.shape[2]
    kernel = _splash_kernel(h, hkv, s, window,
                            jax.default_backend() != "tpu")
    t = functools.partial(jnp.transpose, axes=(0, 2, 1, 3))
    if hkv == h:
        return t(jax.vmap(kernel)(t(q), t(k), t(v)))
    # [B, S, H, D] -> [B x Hkv, H / Hkv, S, D]: a group a kernel call
    o = jax.vmap(kernel)(
        t(q).reshape(b * hkv, h // hkv, s, q.shape[-1]),
        t(k).reshape(b * hkv, s, k.shape[-1]),
        t(v).reshape(b * hkv, s, v.shape[-1]))
    return t(o.reshape(b, h, s, v.shape[-1]))


def window_tile_share(heads: int, kv_heads: int, seq: int, window: int,
                      block: int, impl: str = "auto") -> float:
    """Score entries the window lets through over the entries of the
    score blocks the formulation visits (1.0 = no masked entry is
    computed): from the kernel's own block table at its block sizes
    (every block the table does not mark empty is computed whole), or
    from the blocks of keys ``blockwise_attention`` scans."""
    w = min(window, seq)
    through = w * (w + 1) // 2 + (seq - w) * w
    window = None if window >= seq else window     # as causal_attention
    if resolve_impl(impl) == "splash":
        import numpy as np

        kernel = _splash_kernel(heads, kv_heads, seq, window,
                                jax.default_backend() != "tpu")
        table = np.asarray(kernel.fwd_mask_info.block_mask)
        sizes = kernel.kwargs["block_sizes"]
        visited = (np.count_nonzero(table) / table.shape[0]
                   * sizes.block_q * sizes.block_kv)
    else:
        block = min(block, seq)
        visited = block * block * sum(
            i - first_key_block(i, block, window) + 1
            for i in range(seq // block))
    return through / visited


def causal_attention(q, k, v, block: int, impl: str = "auto", window=None):
    """``impl`` other than ``auto`` is for tests that hold one
    formulation against the other.  ``window`` None: every key at or
    before the query; w: the query's own position and the w - 1 before
    it.  k and v may have fewer heads than q (grouped)."""
    if q.shape[2] % k.shape[2]:
        raise ValueError(f"{q.shape[2]} query heads are no multiple of "
                         f"the {k.shape[2]} key-value heads")
    if window is not None and window >= q.shape[1]:
        window = None       # every key at or before the query is inside
    if resolve_impl(impl) == "splash":
        return splash_attention(q, k, v, window)
    return blockwise_attention(q, k, v, min(block, q.shape[1]), window)
