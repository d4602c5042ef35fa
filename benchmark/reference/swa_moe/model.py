"""Laguna-XS.2's forward pass and training loss in plain ``jax.numpy``
and float32, following poolside/Laguna-XS.2's ``config.json`` at the
configuration's share: ``layers_held`` layers, the experts
``experts_held`` = [first, count] of ``num_experts``, ``vocab_rows``
ids.  No kernel, no sorting, no block skipped: attention one row after
another with a full ``[heads, S, S]`` array of scores a head block
under an explicit boolean mask, K and V repeated for every query head;
a dense ``[tokens, experts]`` matrix of gates, masked to the held
experts, one expert after another over every token.  It takes no array
and no code from the program.

The equations (row ``x[:S]``, targets ``x[1:]``, ``n = RMS(x; g)``):

* block l: ``x += Attn_l(RMS(x; g1))``, ``x += MLP_l(RMS(x; g2))``.
* ``Attn_l``: ``H = num_attention_heads_per_layer[l]`` query heads over
  ``num_key_value_heads`` key-value heads of ``head_dim``; q, k, v =
  n Wq, n Wk, n Wv; rotary on q and k by the layer's type
  (``rope_parameters[layer_types[l]]``); query head h reads key-value
  head ``h // (H / Hkv)``; ``o[i, h] = sum_j softmax_j(q[i, h] . k[j] /
  sqrt(head_dim)) v[j]`` over ``j <= i`` (``full_attention``) or
  ``i - sliding_window < j <= i`` (``sliding_attention``); the output is
  ``concat_h(sigmoid(n Wg)[i, h] o[i, h]) Wo``.
* rotary: over the first ``r = partial_rotary_factor x head_dim``
  dimensions, ``x cos + rotate_half(x) sin`` with ``rotate_half(x) =
  [-x[r/2:r], x[:r/2]]``, the rest unrotated; ``rope_type`` ``default``:
  frequencies ``theta^(-2j/r)``; ``yarn``: ``yarn_frequencies`` below,
  cos and sin times ``attention_factor``.
* ``MLP_l``: ``mlp_layer_types[l]`` ``dense``: SwiGLU of
  ``intermediate_size``; ``sparse``: ``s = sigmoid(n Wr)`` over all
  ``num_experts``, the ``num_experts_per_tok`` largest, gates
  ``moe_routed_scaling_factor x s_e / sum of the selected s``, ``y =
  SwiGLU_shared(n) + sum over the selected held e of gate_e
  SwiGLU_e(n)``.
* loss: mean over positions of ``-log softmax(RMS(x; g) Whead)[target]``.

Departures from the published config (its ``assumed`` block says why):

* ``gating`` is read as one sigmoid gate a head a position; the router
  as sigmoid scores normalised over the selected (the convention
  ``moe_routed_scaling_factor`` belongs to); no bias, no norm on q or
  k, no selection bias: no key names one.
* For memory only, with no change of arithmetic: each layer, each
  (row, head block) of attention and each expert is recomputed in the
  backward pass (``jax.checkpoint``).
* ``quant`` (the control): every matrix product's two operands pass
  through it first (the router's stays float32, as in the sequence
  task's reference).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.init import param_key

HEAD_BLOCK = 4      # [4, S, S] float32 scores: 1.07 GB at S 8,192


def _layer_shapes(spec, layer):
    d, dh = spec["hidden_size"], spec["head_dim"]
    nh = spec["num_attention_heads_per_layer"][layer]
    nkv = spec["num_key_value_heads"]
    out = {
        ("attn_norm",): ((d,), "scale"),
        ("attn", "q"): ((d, nh * dh), "kernel"),
        ("attn", "k"): ((d, nkv * dh), "kernel"),
        ("attn", "v"): ((d, nkv * dh), "kernel"),
        ("attn", "g"): ((d, nh), "kernel"),
        ("attn", "o"): ((nh * dh, d), "kernel"),
        ("mlp_norm",): ((d,), "scale"),
    }
    if spec["mlp_layer_types"][layer] == "dense":
        w = spec["intermediate_size"]
        out.update({("mlp", "gate"): ((d, w), "kernel"),
                    ("mlp", "up"): ((d, w), "kernel"),
                    ("mlp", "down"): ((w, d), "kernel")})
        return out
    w, ws = spec["moe_intermediate_size"], spec[
        "shared_expert_intermediate_size"]
    count = spec["experts_held"][1]
    out.update({
        ("moe", "router"): ((d, spec["num_experts"]), "kernel"),
        ("moe", "experts_gate"): ((count, d, w), "kernel"),
        ("moe", "experts_up"): ((count, d, w), "kernel"),
        ("moe", "experts_down"): ((count, w, d), "kernel"),
        ("moe", "shared", "gate"): ((d, ws), "kernel"),
        ("moe", "shared", "up"): ((d, ws), "kernel"),
        ("moe", "shared", "down"): ((ws, d), "kernel"),
    })
    return out


def param_shapes(spec):
    """{module path: (shape, kind)}; every module holds one parameter,
    named by its kind (``kernel``, ``scale``)."""
    d, v = spec["hidden_size"], spec["vocab_rows"]
    out = {("embed",): ((v, d), "kernel"), ("head",): ((d, v), "kernel"),
           ("final_norm",): ((d,), "scale")}
    for i in range(spec["layers_held"]):
        for path, what in _layer_shapes(spec, i).items():
            out[(f"block{i}",) + path] = what
    return out


def init_params(spec, seed):
    """Nested {module: {..: {kind: array}}} float32: kernels normal
    (0, init_std) (the embedding: embed_init_std), norm scales one;
    each from the root key folded with its module's path (flax's rule,
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
                std = (spec["embed_init_std"] if path == ("embed",)
                       else spec["init_std"])
                node[kind] = std * jax.random.normal(
                    param_key(root, path), shape, jnp.float32)
        return params

    return jax.jit(build)(jax.random.PRNGKey(seed))


def decay_mask(params):
    """Decoupled weight decay on the matrices only: not on the norm
    scales."""
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


def yarn_frequencies(r, rope):
    """YaRN (arXiv:2309.00071) for a rotary width ``r``, one pair of
    dimensions after another.  Pair j's plain frequency is ``f =
    theta^(-2j/r)``, so over the original context L it turns ``L f /
    2 pi`` times; solved for j, the pair that turns n times has the
    (real) index ``r ln(L / (2 pi n)) / (2 ln theta)``.  Pairs at or
    before the one that turns ``beta_fast`` times (rounded down) keep
    f; pairs at or after the one that turns ``beta_slow`` times
    (rounded up) take ``f / factor``; a straight line in j between
    them (``transformers``' ``_compute_yarn_parameters``, whose ramp is
    in the index, with both ends truncated outward)."""
    theta, length = rope["rope_theta"], rope["original_max_position_embeddings"]

    def index_turning(n):
        return r * math.log(length / (2 * math.pi * n)) / (
            2 * math.log(theta))

    first = max(math.floor(index_turning(rope["beta_fast"])), 0)
    last = min(math.ceil(index_turning(rope["beta_slow"])), r - 1)
    if first == last:
        last += 0.001
    out = []
    for j in range(r // 2):
        f = theta ** (-2.0 * j / r)
        scaled = min(max((j - first) / (last - first), 0.0), 1.0)
        out.append((1.0 - scaled) * f + scaled * f / rope["factor"])
    return out


def rotary(x, rope, head_dim):
    """``x`` ``[S, heads, head_dim]`` -> the same, its first ``r``
    dimensions rotated (Hugging Face's ``apply_rotary_pos_emb`` with a
    partial rotary width)."""
    s = x.shape[0]
    r = int(head_dim * rope["partial_rotary_factor"])
    if rope["rope_type"] == "yarn":
        inv, factor = yarn_frequencies(r, rope), rope["attention_factor"]
    else:
        inv = [rope["rope_theta"] ** (-2.0 * j / r) for j in range(r // 2)]
        factor = 1.0
    freqs = (jnp.arange(s, dtype=jnp.float32)[:, None]
             * jnp.asarray(inv, jnp.float32)[None, :])
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    cos, sin = jnp.cos(emb) * factor, jnp.sin(emb) * factor
    turned, kept = x[..., :r], x[..., r:]
    rotated = jnp.concatenate([-turned[..., r // 2:], turned[..., :r // 2]],
                              axis=-1)
    return jnp.concatenate([turned * cos + rotated * sin, kept], axis=-1)


def visible(s, window):
    """bool ``[S, S]``: may query i (rows) read key j (columns)."""
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (j > i - window)
    return seen


def _attend(q, k, v, seen):
    """``[heads, S, d]`` operands of one row and one head block, the
    whole ``[heads, S, S]`` scores at once."""
    scores = jnp.einsum("hqd,hkd->hqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    scores = jnp.where(seen[None], scores, -jnp.inf)
    return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, axis=-1), v)


def attention(p, h, spec, layer, quant):
    """``h`` ``[rows, S, hidden]`` (already normed) -> layer ``layer``'s
    attention output before the residual, one row at a time."""
    nh = spec["num_attention_heads_per_layer"][layer]
    nkv, dh = spec["num_key_value_heads"], spec["head_dim"]
    kind = spec["layer_types"][layer]
    rope = spec["rope_parameters"][kind]
    window = spec["sliding_window"] if kind == "sliding_attention" else None

    def one_row(x):                                  # [S, hidden]
        s = x.shape[0]
        q = mm(x, p["q"]["kernel"], quant).reshape(s, nh, dh)
        k = mm(x, p["k"]["kernel"], quant).reshape(s, nkv, dh)
        v = mm(x, p["v"]["kernel"], quant).reshape(s, nkv, dh)
        gate = jax.nn.sigmoid(mm(x, p["g"]["kernel"], quant))   # [S, nh]
        q, k = rotary(q, rope, dh), rotary(k, rope, dh)
        # every query head its own copy of its key-value head
        k = jnp.repeat(k, nh // nkv, axis=1)
        v = jnp.repeat(v, nh // nkv, axis=1)
        seen = visible(s, window)

        def heads(a):       # [S, nh, d] -> [blocks, a head block, S, d]
            hb = max(b for b in range(1, HEAD_BLOCK + 1) if nh % b == 0)
            return a.transpose(1, 0, 2).reshape(nh // hb, hb, s, dh)

        o = jax.lax.map(
            lambda qkv: jax.checkpoint(_attend)(*qkv, seen),
            (heads(q), heads(k), heads(v)))
        o = o.reshape(nh, s, dh).transpose(1, 0, 2) * gate[..., None]
        return mm(o.reshape(s, nh * dh), p["o"]["kernel"], quant)

    return jax.lax.map(one_row, h)


def swiglu(x, gate, up, down, quant):
    return mm(jax.nn.silu(mm(x, gate, quant)) * mm(x, up, quant), down,
              quant)


def routing(p, x, spec):
    """(ids ``[T, k]``, dense gates ``[T, num_experts]``): sigmoid
    scores, the k largest, gates = scaling x score / sum of the
    selected scores."""
    scores = jax.nn.sigmoid(jnp.matmul(x, p["router"]["kernel"]))
    _, ids = jax.lax.top_k(scores, spec["num_experts_per_tok"])
    chosen = jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], ids].set(1.0)
    picked = scores * chosen
    gates = spec["moe_routed_scaling_factor"] * picked / jnp.sum(
        picked, axis=-1, keepdims=True)
    return ids, gates


def moe(p, h, spec, quant):
    """Shared expert + the gated sum over the held experts, one expert
    after another over every token, for ``h`` ``[rows, S, hidden]``."""
    shape = h.shape
    x = h.reshape(-1, shape[-1])
    _, gates = routing(p, x, spec)
    first, count = spec["experts_held"]

    @jax.checkpoint
    def one(total, e):
        w_gate, w_up, w_down, gate = e
        return total + gate[:, None] * swiglu(x, w_gate, w_up, w_down,
                                              quant), None

    total, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["experts_gate"]["kernel"], p["experts_up"]["kernel"],
        p["experts_down"]["kernel"], gates[:, first:first + count].T))
    sh = p["shared"]
    total = total + swiglu(x, sh["gate"]["kernel"], sh["up"]["kernel"],
                           sh["down"]["kernel"], quant)
    return total.reshape(shape)


def block(p, x, spec, layer, quant):
    eps = spec["rms_norm_eps"]
    x = x + attention(p["attn"], rms_norm(x, p["attn_norm"]["scale"], eps),
                      spec, layer, quant)
    h = rms_norm(x, p["mlp_norm"]["scale"], eps)
    if spec["mlp_layer_types"][layer] == "dense":
        m = p["mlp"]
        return x + swiglu(h, m["gate"]["kernel"], m["up"]["kernel"],
                          m["down"]["kernel"], quant)
    return x + moe(p["moe"], h, spec, quant)


def losses(params, tokens, spec, quant=None):
    """``tokens`` ``int32[rows, S + 1]`` -> {``ce_loss``,
    ``total_loss``}: the mean next-token cross-entropy over every
    position of every row."""
    x = params["embed"]["kernel"][tokens[:, :-1]]
    for i in range(spec["layers_held"]):
        x = jax.checkpoint(
            lambda p, x, i=i: block(p, x, spec, i, quant))(
                params[f"block{i}"], x)
    x = rms_norm(x, params["final_norm"]["scale"], spec["rms_norm_eps"])

    @jax.checkpoint
    def row_loss(xs):           # one row's logits at a time
        hr, tr = xs
        logits = mm(hr, params["head"]["kernel"], quant)
        return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, tr[:, None], axis=-1)[:, 0]

    ce = jnp.mean(jax.lax.map(row_loss, (x, tokens[:, 1:])))
    return {"ce_loss": ce, "total_loss": ce}
