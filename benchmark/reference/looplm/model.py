"""Ouro's (LoopLM's) forward pass and stage-I training loss in plain
``jax.numpy`` and float32, following "Scaling Latent Reasoning via
Looped Language Models" (arXiv:2510.25741, section 3) and the
``modeling_ouro.py`` published beside ByteDance/Ouro-2.6B's
``config.json``, at the configuration's share: ``layers_held`` blocks,
applied ``total_ut_steps`` times with the same parameters.  No kernel:
attention one row after another with a full ``[heads, S, S]`` array of
scores a head block, logits a block of positions at a time.  It takes
no array and no code from the program.

The equations (T passes, N blocks, row ``x[:S]``, targets ``x[1:]``):

* ``h0 = E[x]``; pass t: ``u = h(t-1)``, for each block
  ``u += RMS(Attn(RMS(u; g1)); g2)``, ``u += RMS(MLP(RMS(u; g3)); g4)``;
  ``h(t) = RMS(u; g_final)``, and h(t), the normed state, is what pass
  t + 1 starts from.
* ``Attn``: q, k, v = n Wq, n Wk, n Wv, rotary on q and k (theta, pairs
  ``(x[j], x[j + d/2])``), causal ``softmax(q kT / sqrt(d)) v``, then Wo;
  ``MLP``: ``Wdown(silu(Wgate n) * Wup n)``.  No bias.
* per pass: ``logits = h(t) Whead``, ``CE(t)`` the per-position
  cross-entropy, ``lambda(t) = sigmoid(wg . h(t) + bg)``.
* ``p(t) = lambda(t) prod_{j<t} (1 - lambda(j))`` for t < T and
  ``p(T) = prod_{j<T} (1 - lambda(j))``.
* loss ``= mean over positions of [sum_t p(t) CE(t) - beta H(p)]``,
  ``H(p) = -sum_t p(t) log p(t)``.

Departures from the paper and the published code:

* The loop over passes is a ``jax.lax.scan`` over one pass's function
  (the same function of the same parameters ``total_ut_steps`` times,
  which is what the model is), not a Python loop: ISSUE 33 asked for the
  Python loop, and its gradient's executable came to 213 MB, over the
  192 MiB the chip machine's compile cache takes, so every run of the
  benchmark compiled it anew (about 150 s of a 215 s reference; PERF.md
  section 6, PR 33).  As in the published code, ``lambda(T)`` is
  computed and enters nothing.
* The loss is the paper's first pre-training stage with the
  configuration's beta; the later stages (a lower beta, the gate
  trained against the loss's improvement with the model frozen) and
  ``early_exit_threshold`` (inference) reach no code here.
* ``log p`` for the entropy is the logarithm of the product as computed
  (clamped at float32's smallest normal before the logarithm, so that
  ``0 log 0`` reads 0); the program sums log-sigmoids instead.
* For memory only, with no change of arithmetic: each block
  application, each (row, head block) of attention and each block of
  logits is recomputed in the backward pass (``jax.checkpoint``).
* ``quant`` (the control): every matrix product's two operands pass
  through it first, the gate's included.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.init import param_key

HEAD_BLOCK = 8
LOGIT_BLOCK = 1024      # positions a block of logits


def param_shapes(spec):
    """{module path: (shape, kind)}; a module holds one parameter named
    by its kind (``kernel``, ``scale``), the gate a ``bias`` beside.
    What every pass shares lies under ``loop`` (the program's name for
    the stack it applies ``total_ut_steps`` times)."""
    d, v, w = (spec["hidden_size"], spec["vocab_rows"],
               spec["intermediate_size"])
    a = spec["num_attention_heads"] * spec["head_dim"]
    out = {("embed",): ((v, d), "kernel"), ("head",): ((d, v), "kernel"),
           ("loop", "final_norm"): ((d,), "scale"),
           ("loop", "gate"): ((d, 1), "kernel")}
    for i in range(spec["layers_held"]):
        b = ("loop", f"block{i}")
        for name in ("attn_norm", "attn_post_norm", "mlp_norm",
                     "mlp_post_norm"):
            out[b + (name,)] = ((d,), "scale")
        for name in ("q", "k", "v"):
            out[b + ("attn", name)] = ((d, a), "kernel")
        out[b + ("attn", "o")] = ((a, d), "kernel")
        out[b + ("mlp", "gate")] = ((d, w), "kernel")
        out[b + ("mlp", "up")] = ((d, w), "kernel")
        out[b + ("mlp", "down")] = ((w, d), "kernel")
    return out


def init_params(spec, seed):
    """Nested {module: {..: {kind: array}}} float32: every kernel
    (embedding, head and the gate's column too) normal (0, init_std),
    norm scales one, the gate's bias zero; each drawn from the root key
    folded with its module's path (flax's rule,
    ``benchmark/reference/init.py``)."""
    shapes = param_shapes(spec)

    def build(root):
        params = {}
        for path, (shape, kind) in shapes.items():
            node = params
            for part in path:
                node = node.setdefault(part, {})
            if kind == "scale":
                node[kind] = jnp.ones(shape, jnp.float32)
            else:
                node[kind] = spec["init_std"] * jax.random.normal(
                    param_key(root, path), shape, jnp.float32)
        params["loop"]["gate"]["bias"] = jnp.zeros((1,), jnp.float32)
        return params

    return jax.jit(build)(jax.random.PRNGKey(seed))


def decay_mask(params):
    """Decoupled weight decay on the matrices only: not on the norm
    scales, not on the gate's bias."""
    def decays(path, _):
        return path[-1].key == "kernel"

    return jax.tree_util.tree_map_with_path(decays, params)


# ------------------------------------------------------------- forward


def mm(x, w, quant):
    if quant is not None:
        x, w = quant(x), quant(w)
    return jnp.matmul(x, w)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rotary(x, theta):
    """``x`` ``[S, heads, d]``: ``x cos + rotate_half(x) sin`` with
    ``rotate_half(x) = [-x[d/2:], x[:d/2]]`` (Hugging Face's
    ``apply_rotary_pos_emb``)."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(emb) + rotated * jnp.sin(emb)


def _attend(q, k, v):
    """``[heads, S, d]`` operands of one row and one head block, the
    whole ``[heads, S, S]`` scores at once."""
    s = q.shape[1]
    scores = jnp.einsum("hqd,hkd->hqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, axis=-1), v)


def attention(p, h, spec, quant):
    """``h`` ``[rows, S, hidden]`` (already normed) -> the attention
    sub-layer's output before its second norm, one row at a time."""
    nh, dh = spec["num_attention_heads"], spec["head_dim"]

    def one_row(x):                                  # [S, hidden]
        s = x.shape[0]
        q, k, v = (mm(x, p[name]["kernel"], quant).reshape(s, nh, dh)
                   for name in ("q", "k", "v"))
        q, k = rotary(q, spec["rope_theta"]), rotary(k, spec["rope_theta"])

        def heads(a):       # [S, nh, d] -> [blocks, HEAD_BLOCK, S, d]
            hb = min(HEAD_BLOCK, nh)
            return a.transpose(1, 0, 2).reshape(nh // hb, hb, s, dh)

        o = jax.lax.map(lambda qkv: jax.checkpoint(_attend)(*qkv),
                        (heads(q), heads(k), heads(v)))
        o = o.reshape(nh, s, dh).transpose(1, 0, 2).reshape(s, nh * dh)
        return mm(o, p["o"]["kernel"], quant)

    return jax.lax.map(one_row, h)


def swiglu(p, x, quant):
    return mm(jax.nn.silu(mm(x, p["gate"]["kernel"], quant))
              * mm(x, p["up"]["kernel"], quant), p["down"]["kernel"], quant)


def block(p, u, spec, quant):
    """The sandwich block on ``u`` ``[rows, S, hidden]``."""
    eps = spec["rms_norm_eps"]
    u = u + rms_norm(
        attention(p["attn"], rms_norm(u, p["attn_norm"]["scale"], eps),
                  spec, quant), p["attn_post_norm"]["scale"], eps)
    return u + rms_norm(
        swiglu(p["mlp"], rms_norm(u, p["mlp_norm"]["scale"], eps), quant),
        p["mlp_post_norm"]["scale"], eps)


def cross_entropies(h, head, targets, quant):
    """Per-position ``-log softmax(h head)[target]``, in ``targets``'
    shape, a block of positions at a time."""
    d = h.shape[-1]
    n = targets.size
    size = min(LOGIT_BLOCK, n)

    @jax.checkpoint
    def one(xs):
        hb, tb = xs
        logits = mm(hb, head, quant)
        return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, tb[:, None], axis=-1)[:, 0]

    return jax.lax.map(one, (h.reshape(n // size, size, d),
                             targets.reshape(n // size, size))
                       ).reshape(targets.shape)


def pass_terms(params, tokens, spec, quant):
    """``tokens`` ``int32[rows, S + 1]`` -> (CE ``[T, rows, S]``,
    p ``[T, rows, S]``, H ``[rows, S]``)."""
    eps, passes = spec["rms_norm_eps"], spec["total_ut_steps"]
    loop, head = params["loop"], params["head"]["kernel"]
    gate = loop["gate"]

    def one_pass(h, _):
        u = h
        for i in range(spec["layers_held"]):
            u = jax.checkpoint(lambda p, u: block(p, u, spec, quant))(
                loop[f"block{i}"], u)
        h = rms_norm(u, loop["final_norm"]["scale"], eps)
        lam = jax.nn.sigmoid(
            mm(h, gate["kernel"], quant)[..., 0] + gate["bias"][0])
        return h, (cross_entropies(h, head, tokens[:, 1:], quant), lam)

    _, (ce, lam) = jax.lax.scan(
        one_pass, params["embed"]["kernel"][tokens[:, :-1]], None,
        length=passes)
    p, left = [], jnp.ones_like(ce[0])
    for t in range(passes - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    p = jnp.stack(p + [left])
    tiny = jnp.finfo(jnp.float32).tiny
    entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, tiny)), axis=0)
    return ce, p, entropy


def losses(params, tokens, spec, quant=None):
    """``tokens`` ``int32[rows, S + 1]`` -> {``ce_pass<t>_loss``,
    ``expected_ce_loss``, ``exit_entropy_loss``, ``total_loss``}, means
    over every position of every row."""
    ce, p, entropy = pass_terms(params, tokens, spec, quant)
    expected = jnp.mean(jnp.sum(p * ce, axis=0))
    exit_entropy = -spec["exit_entropy_weight"] * jnp.mean(entropy)
    out = {f"ce_pass{t + 1}_loss": jnp.mean(ce[t])
           for t in range(spec["total_ut_steps"])}
    out.update(expected_ce_loss=expected, exit_entropy_loss=exit_entropy,
               total_loss=expected + exit_entropy)
    return out
