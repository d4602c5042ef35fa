"""The attention core with a window and grouped key-value heads
(eksml_tpu/models/lm/attention.py): both formulations
``causal_attention`` chooses between, the blockwise ``jax.numpy`` one
and jax's splash kernel in Pallas's interpreter, against a full-scores
oracle written here (an explicit ``i - w < j <= i`` mask, K and V
repeated a query head), forward and gradients; what a window of the
whole sequence, a misfit head count and equal heads with no window
reduce to; and the share of the visited score blocks the window lets
through.

Tolerances: float32 everywhere, so a gap is the order of summation of
the online softmax (seen: 1.2e-6 forward, 2e-5 on a gradient)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eksml_tpu.models.lm import attention

S, D, BLOCK = 256, 128, 64
# none; smaller than the xla block; no multiple of it (and over one
# block); at least the sequence
WINDOWS = {"none": None, "under_a_block": 48, "off_the_blocks": 200,
           "whole_sequence": 256, "past_the_sequence": 1000}
HEADS = [(4, 4), (6, 2), (8, 2)]


def oracle(q, k, v, window):
    """softmax over ``i - window < j <= i`` of q . k, every query head
    reading key-value head ``h // (H / Hkv)``, in float64 numpy."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    group = q.shape[2] // k.shape[2]
    s = q.shape[1]
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = j <= i
    if window is not None:
        seen &= j > i - window
    out = np.zeros(q.shape[:3] + (v.shape[-1],))
    for h in range(q.shape[2]):
        scores = np.einsum("bqd,bkd->bqk", q[:, :, h], k[:, :, h // group])
        scores = np.where(seen[None], scores, -np.inf)
        p = np.exp(scores - scores.max(axis=-1, keepdims=True))
        p /= p.sum(axis=-1, keepdims=True)
        out[:, :, h] = np.einsum("bqk,bkd->bqd", p, v[:, :, h // group])
    return out


def operands(heads, kv_heads, seed=0, s=S):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.normal(size=(1, s, heads, D)) * 0.1,
                        jnp.float32),
            jnp.asarray(rng.normal(size=(1, s, kv_heads, D)), jnp.float32),
            jnp.asarray(rng.normal(size=(1, s, kv_heads, D)), jnp.float32))


@pytest.mark.parametrize("heads, kv_heads", HEADS)
@pytest.mark.parametrize("window", list(WINDOWS))
@pytest.mark.parametrize("impl", ["xla", "splash"])
def test_both_formulations_equal_the_full_scores_oracle(impl, window,
                                                        heads, kv_heads):
    w = WINDOWS[window]
    q, k, v = operands(heads, kv_heads)

    def run(*a):
        return attention.causal_attention(*a, BLOCK, impl=impl, window=w)

    want = oracle(q, k, v, w)
    np.testing.assert_allclose(jax.jit(run)(q, k, v), want, atol=5e-6)
    # the repo's own S x S formulation is the same function
    np.testing.assert_allclose(
        attention.full_scores_attention(q, k, v, w), want, atol=5e-6)


@pytest.mark.parametrize("heads, kv_heads", HEADS)
@pytest.mark.parametrize("window", ["none", "under_a_block",
                                    "off_the_blocks"])
@pytest.mark.parametrize("impl", ["xla", "splash"])
def test_gradients_equal_the_full_scores_formulations(impl, window, heads,
                                                      kv_heads):
    """dq, dk and dv: a key-value head's gradient is the sum over its
    group of query heads, in both formulations."""
    w = WINDOWS[window]
    q, k, v = operands(heads, kv_heads, seed=1)

    def loss(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a)))

    got = jax.jit(jax.grad(loss(lambda *a: attention.causal_attention(
        *a, BLOCK, impl=impl, window=w)), argnums=(0, 1, 2)))(q, k, v)
    want = jax.grad(loss(lambda *a: attention.full_scores_attention(
        *a, w)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=1e-4)
    assert float(jnp.max(jnp.abs(want[1]))) > 1e-3


def test_a_window_hides_what_lies_before_it():
    """Moving a key that lies outside every later query's window
    changes nothing after it; inside, it does."""
    q, k, v = operands(4, 2, seed=2)
    run = jax.jit(lambda *a: attention.causal_attention(
        *a, BLOCK, impl="xla", window=48))
    base = run(q, k, v)
    moved = run(q, k.at[:, 10].add(3.0), v.at[:, 10].add(3.0))
    assert float(jnp.max(jnp.abs(moved[:, 58:] - base[:, 58:]))) == 0.0
    assert float(jnp.max(jnp.abs(moved[:, 10:58] - base[:, 10:58]))) > 1e-3
    # position 57 sees 10 (57 - 48 < 10), position 58 does not
    assert float(jnp.max(jnp.abs(moved[:, 57] - base[:, 57]))) > 0.0


def _kernel(heads, kv_heads, seq, window):
    return attention._splash_kernel(heads, kv_heads, seq, window, True)


def test_equal_heads_and_no_window_build_the_kernel_the_other_models_run():
    """JoyAI's and Ouro's calls (as many key-value heads as query
    heads, no window): one cached ``make_splash_mha`` kernel over causal
    masks at the blocks they ran before this module knew a window, and
    the very jaxpr of that call written out by hand."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)

    attention._splash_kernel.cache_clear()
    kernel = _kernel(4, 4, 256, None)
    assert _kernel(4, 4, 256, None) is kernel           # cached
    assert kernel.kwargs["is_mqa"] is False
    sizes = kernel.kwargs["block_sizes"]
    assert (sizes.block_q, sizes.block_kv, sizes.block_kv_compute,
            sizes.use_fused_bwd_kernel) == (256, 256, 256, True)
    at_size = _kernel(16, 16, 4096, None).kwargs["block_sizes"]
    assert (at_size.block_q, at_size.block_kv, at_size.block_kv_compute,
            at_size.block_q_dkv, at_size.block_kv_dkv,
            at_size.block_kv_dkv_compute) == (1024, 1024, 512, 1024, 1024,
                                              512)
    # a window or grouped heads are other kernels, not this one changed
    assert _kernel(4, 2, 256, None) is not kernel
    assert _kernel(4, 2, 256, None).kwargs["is_mqa"] is True
    assert _kernel(4, 4, 256, 48) is not kernel

    mask = sm.MultiHeadMask([sm.CausalMask((256, 256)) for _ in range(4)])
    with jax.ensure_compile_time_eval():
        before = sk.make_splash_mha(
            mask, head_shards=1, q_seq_shards=1, block_sizes=sizes,
            interpret=True)

    def as_before(q, k, v):
        t = lambda x: jnp.transpose(x, (0, 2, 1, 3))
        return t(jax.vmap(before)(t(q), t(k), t(v)))

    rng = np.random.RandomState(3)
    for dqk, dv in ((192, 128), (128, 128)):    # JoyAI's widths, Ouro's
        q, k = (jnp.asarray(rng.normal(size=(2, 256, 4, dqk)) * 0.3,
                            jnp.float32) for _ in range(2))
        v = jnp.asarray(rng.normal(size=(2, 256, 4, dv)), jnp.float32)
        now = lambda *a: attention.causal_attention(*a, 64, impl="splash")
        assert (str(jax.make_jaxpr(now)(q, k, v))
                == str(jax.make_jaxpr(as_before)(q, k, v)))
        grad = lambda f: jax.grad(lambda *a: jnp.sum(f(*a)),
                                  argnums=(0, 1, 2))
        assert (str(jax.make_jaxpr(grad(now))(q, k, v))
                == str(jax.make_jaxpr(grad(as_before))(q, k, v)))
    # the jax.numpy formulation likewise: no window argument, no repeat
    text = str(jax.make_jaxpr(lambda *a: attention.causal_attention(
        *a, 64, impl="xla"))(q, k, v))
    assert "repeat" not in text and "concatenate" in text
    assert text == str(jax.make_jaxpr(lambda *a: attention.causal_attention(
        *a, 64, impl="xla", window=256))(q, k, v))
    attention._splash_kernel.cache_clear()


def test_a_misfit_head_count_is_an_error():
    q, k, v = operands(6, 4)
    for impl in ("xla", "splash"):
        with pytest.raises(ValueError, match="no multiple"):
            attention.causal_attention(q, k, v, BLOCK, impl=impl)


@pytest.mark.parametrize("seq, window, block, want_xla, want_splash", [
    # 8 query blocks of 1,024 under a 512 window visit 2 key blocks
    # each (the first: 1): 15 of 64, and 4,063,488 scores let through
    (8192, 512, 512, 4063488 / (512 * 512 * 31), 4063488 / (1024 * 1024 * 15)),
    # a window no multiple of the block: S 256, w 200, blocks of 64 see
    # 1, 2, 3, 4 key blocks; the kernel's one block of 256 sees it all
    (256, 200, 64, (200 * 201 // 2 + 56 * 200) / (64 * 64 * 10),
     (200 * 201 // 2 + 56 * 200) / (256 * 256)),
])
def test_window_tile_share_counts_what_each_formulation_visits(
        seq, window, block, want_xla, want_splash):
    args = (8, 2, seq, window, block)
    assert attention.window_tile_share(*args, impl="xla") == pytest.approx(
        want_xla)
    assert attention.window_tile_share(
        *args, impl="splash") == pytest.approx(want_splash)
    assert 0.2 < want_splash < want_xla <= 1.0
    # a direct count of the blocks blockwise_attention scans
    visited = sum(i - attention.first_key_block(i, block, window) + 1
                  for i in range(seq // block))
    through = sum(min(i + 1, window) for i in range(seq))
    assert attention.window_tile_share(*args, impl="xla") == pytest.approx(
        through / (visited * block * block))
    # the first key block is the one that holds the farthest key seen
    assert attention.first_key_block(3, 64, 48) == 2
    assert attention.first_key_block(3, 64, 65) == 2
    assert attention.first_key_block(3, 64, 66) == 1
    assert attention.first_key_block(3, 64, None) == 0
