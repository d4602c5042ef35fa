"""Xing4.0-29B-A4B's forward pass and training loss in plain
``jax.numpy`` and float32, following XingChen-AGI/Xing4.0-29B-A4B's
``config.json`` at the configuration's share: DeepSeek-V3's block
(arXiv:2412.19437 sections 2.1.1 and 2.1.2: MLA, sigmoid routing with a
selection-only bias, a shared expert) on ``hc_mult`` residual streams
mixed by manifold-constrained hyper-connections (mHC, arXiv:2512.24880,
on hyper-connections, arXiv:2409.19606).  ``layers_held`` layers, the
first ``dense_layers_held`` of them dense; the experts ``experts_held``
= [first, count] of ``n_routed_experts``; ``vocab_rows`` ids.  No
kernel, no sorting: attention one row after another with a full
``[heads, S, S]`` array of scores a head block; a dense ``[tokens,
experts]`` matrix of gates, masked to the held experts, one expert after
another over every token (``benchmark/reference/lm/model.py``'s
routing, experts and loss: the same family's).  It takes no array and
no code from the program.

The equations (one row; x the streams ``[S, n, C]``, n = ``hc_mult``):

* ``x = [e, e, .., e]``, e the embedding of the row's tokens.
* block l, for F = MLA then F = the MLP, each with maps of its own:
  ``v = RMSNorm(vec(x))`` over the n C values of a position (a scale a
  value); ``z = v phi`` (``phi`` ``[nC, 2n + n^2]``);
  ``H_pre = sigmoid(a_0 z[:n] + b[:n])``,
  ``H_post = 2 sigmoid(a_1 z[n:2n] + b[n:2n])``,
  ``H_res = SK(clamp(a_2 mat(z[2n:]) + b[2n:], lo, hi))`` with ``mat``
  row-major and SK: ``M = exp(.)``, ``hc_sinkhorn_iters`` times
  ``M /= rowsum(M) + hc_eps`` then ``M /= colsum(M) + hc_eps``;
  ``h = sum_i H_pre[i] x_i``; ``y = F(RMSNorm(h))``;
  ``x_i = sum_j H_res[i, j] x_j + H_post[i] y``.
* MLA: q = RMS(n Wqa) Wqb, kv = RMS(n Wkva[:r]) Wkvb, k_rope = n Wkva[r:]
  one head, rotary on q's and k's last ``qk_rope_head_dim`` dimensions
  with DeepSeek-V3's YaRN (``yarn_rotary``), ``softmax(q k^T s) v`` over
  the keys at or before the query with ``s = (qk_nope + qk_rope)^-1/2
  mscale(factor, mscale_all_dim)^2``, ``mscale(f, m) = 0.1 m ln f + 1``.
* MLP: SwiGLU of ``intermediate_size`` (dense) or the expert layer.
* loss: mean over positions of ``-log softmax(RMS(sum_i x_i) Whead)[t]``.

Departures from the published config (the configuration's ``assumed``
block says why):

* Rotary as the Hugging Face DeepSeek-V3 modelling file applies it (no
  ``rope_interleave`` key: the family's interleaved pairs, moved to
  halves and rotated by halves, the same on q and k).
* The streams are copies of the embedding at the start and summed
  before the final norm (hyper-connections' expansion and reduction);
  the row step of Sinkhorn comes before the column step; the clamp is
  on H~_res before the exponential.
* The selection-only bias is held (``reduced.router_bias_update``);
  no MTP module (``reduced.depth``).
* For memory only, with no change of arithmetic: each layer, each
  (row, head block) of attention and each expert is recomputed in the
  backward pass (``jax.checkpoint``).
* ``quant`` (the control): every matrix product's two operands pass
  through it first (the router's and the streams' mixes stay float32).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.init import param_key
from benchmark.reference.lm.model import (cross_entropy, mm, moe, rms_norm,
                                          swiglu)
from benchmark.reference.swa_moe.model import yarn_frequencies

HEAD_BLOCK = 8      # [8, S, S] float32 scores: 537 MB at S 4,096


def _maps_shapes(spec):
    n, d = spec["hc_mult"], spec["hidden_size"]
    return {("norm",): ((n * d,), "scale"),
            ("phi",): ((n * d, 2 * n + n * n), "kernel"),
            ("alpha",): ((3,), "scale"),
            ("bias",): ((2 * n + n * n,), "bias")}


def _block_shapes(spec, dense):
    d, h = spec["hidden_size"], spec["num_attention_heads"]
    dn, dr, dv = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                  spec["v_head_dim"])
    out = {
        ("attn_norm",): ((d,), "scale"),
        ("attn", "q_a"): ((d, spec["q_lora_rank"]), "kernel"),
        ("attn", "q_a_norm"): ((spec["q_lora_rank"],), "scale"),
        ("attn", "q_b"): ((spec["q_lora_rank"], h * (dn + dr)), "kernel"),
        ("attn", "kv_a"): ((d, spec["kv_lora_rank"] + dr), "kernel"),
        ("attn", "kv_a_norm"): ((spec["kv_lora_rank"],), "scale"),
        ("attn", "kv_b"): ((spec["kv_lora_rank"], h * (dn + dv)), "kernel"),
        ("attn", "o"): ((h * dv, d), "kernel"),
        ("mlp_norm",): ((d,), "scale"),
    }
    for sub in ("attn_hc", "mlp_hc"):
        out.update({(sub,) + k: v for k, v in _maps_shapes(spec).items()})
    if dense:
        w = spec["intermediate_size"]
        out.update({("mlp", "gate"): ((d, w), "kernel"),
                    ("mlp", "up"): ((d, w), "kernel"),
                    ("mlp", "down"): ((w, d), "kernel")})
        return out
    w = spec["moe_intermediate_size"]
    count = spec["experts_held"][1]
    ws = w * spec["n_shared_experts"]
    out.update({
        ("moe", "router"): ((d, spec["n_routed_experts"]), "kernel"),
        ("moe", "router_bias"): ((spec["n_routed_experts"],), "bias"),
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
    named by its kind (``kernel``, ``scale``, ``bias``)."""
    d, v = spec["hidden_size"], spec["vocab_rows"]
    out = {("embed",): ((v, d), "kernel"), ("head",): ((d, v), "kernel"),
           ("final_norm",): ((d,), "scale")}
    for i in range(spec["layers_held"]):
        dense = i < spec["dense_layers_held"]
        for path, what in _block_shapes(spec, dense).items():
            out[(f"block{i}",) + path] = what
    return out


def init_params(spec, seed):
    """Nested {module: {..: {kind: array}}} float32, each from the root
    key folded with its module's path (flax's rule,
    ``benchmark/reference/init.py``): kernels normal (0, init_std), the
    embedding (0, embed_init_std), the maps' ``phi`` (0, hc_phi_std);
    norm scales and the maps' gains ``hc_alpha_init``; the routing bias
    normal (0, router_bias_std), the maps' offsets ``hc_bias_init``."""
    shapes = param_shapes(spec)

    def std_of(path):
        if path == ("embed",):
            return spec["embed_init_std"]
        if path[-1] == "phi":
            return spec["hc_phi_std"]
        if path[-1] == "router_bias":
            return spec["router_bias_std"]
        return spec["init_std"]

    def build(root):
        params = {}
        for path, (shape, kind) in shapes.items():
            node = params
            for part in path:
                node = node.setdefault(part, {})
            if path[-1] == "alpha":
                node[kind] = jnp.full(shape, spec["hc_alpha_init"],
                                      jnp.float32)
            elif kind == "scale":
                node[kind] = jnp.ones(shape, jnp.float32)
            elif path[-1] == "bias":
                node[kind] = jnp.full(shape, spec["hc_bias_init"],
                                      jnp.float32)
            else:
                node[kind] = std_of(path) * jax.random.normal(
                    param_key(root, path), shape, jnp.float32)
        return params

    return jax.jit(build)(jax.random.PRNGKey(seed))


def decay_mask(params):
    """Decoupled weight decay on the matrices only (``phi`` among them):
    not on the norm scales, the maps' gains and offsets or the held
    routing bias."""
    def decays(path, _):
        return path[-1].key == "kernel"

    return jax.tree_util.tree_map_with_path(decays, params)


# ------------------------------------------------------------- forward


def mscale(factor, m):
    """DeepSeek-V3's ``yarn_get_mscale``."""
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_rotary(spec):
    """(frequencies of the rotary width, cos-and-sin factor, softmax
    factor) of ``rope_scaling``: DeepSeek-V3's ``YarnRotaryEmbedding``,
    whose table is ``transformers``' YaRN table (``yarn_frequencies``)."""
    r, dr = spec["rope_scaling"], spec["qk_rope_head_dim"]
    if r is None:
        theta = spec["rope_theta"]
        return [theta ** (-2.0 * j / dr) for j in range(dr // 2)], 1.0, 1.0
    inv = yarn_frequencies(dr, {
        "rope_theta": spec["rope_theta"], "factor": r["factor"],
        "original_max_position_embeddings": r[
            "original_max_position_embeddings"],
        "beta_fast": r["beta_fast"], "beta_slow": r["beta_slow"]})
    all_dim = mscale(r["factor"], r["mscale_all_dim"])
    return inv, mscale(r["factor"], r["mscale"]) / all_dim, all_dim ** 2


def rotary(x, inv, factor):
    """``x`` ``[S, heads, d]``: interleaved pairs to halves, then the
    rotation by halves at the frequencies ``inv`` (Hugging Face
    ``apply_rotary_pos_emb`` after DeepSeek-V3's permutation), cos and
    sin times ``factor``."""
    s, h, d = x.shape
    x = x.reshape(s, h, d // 2, 2).transpose(0, 1, 3, 2).reshape(s, h, d)
    freqs = (jnp.arange(s, dtype=jnp.float32)[:, None]
             * jnp.asarray(inv, jnp.float32)[None, :])
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return (x * jnp.cos(emb) + rotated * jnp.sin(emb)) * factor


def _attend(q, k, v, scale):
    """``[heads, S, d]`` operands of one row and one head block, the
    whole ``[heads, S, S]`` scores at once."""
    s = q.shape[1]
    scores = jnp.einsum("hqd,hkd->hqk", q, k) * scale
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, axis=-1), v)


def mla(p, h, spec, quant):
    """``h`` ``[rows, S, hidden]`` (already normed) -> attention output."""
    nh = spec["num_attention_heads"]
    dn, dr, dv = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                  spec["v_head_dim"])
    rank, eps = spec["kv_lora_rank"], spec["rms_norm_eps"]
    inv, factor, softmax_factor = yarn_rotary(spec)
    scale = (dn + dr) ** -0.5 * softmax_factor

    def one_row(x):                                  # [S, hidden]
        s = x.shape[0]
        cq = rms_norm(mm(x, p["q_a"]["kernel"], quant),
                      p["q_a_norm"]["scale"], eps)
        q = mm(cq, p["q_b"]["kernel"], quant).reshape(s, nh, dn + dr)
        kva = mm(x, p["kv_a"]["kernel"], quant)
        ckv = rms_norm(kva[:, :rank], p["kv_a_norm"]["scale"], eps)
        k_rope = rotary(kva[:, None, rank:], inv, factor)      # one head
        kv = mm(ckv, p["kv_b"]["kernel"], quant).reshape(s, nh, dn + dv)
        q = jnp.concatenate(
            [q[..., :dn], rotary(q[..., dn:], inv, factor)], -1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_rope, (s, nh, dr))], -1)
        v = kv[..., dn:]

        def heads(a):       # [S, nh, d] -> [blocks, HEAD_BLOCK, S, d]
            hb = min(HEAD_BLOCK, nh)
            return a.transpose(1, 0, 2).reshape(nh // hb, hb, s, a.shape[-1])

        o = jax.lax.map(
            lambda qkv: jax.checkpoint(_attend)(*qkv, scale),
            (heads(q), heads(k), heads(v)))
        o = o.reshape(nh, s, dv).transpose(1, 0, 2).reshape(s, nh * dv)
        return mm(o, p["o"]["kernel"], quant)

    return jax.lax.map(one_row, h)


def sinkhorn(logits, iters, eps):
    """``exp(logits)``, then ``iters`` times: rows to sum one, then
    columns, each denominator + ``eps``."""
    def one(_, m):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=-2, keepdims=True) + eps)

    return jax.lax.fori_loop(0, iters, one, jnp.exp(logits))


def maps(p, x, spec, quant):
    """(H_pre ``[.., n]``, H_post ``[.., n]``, H_res ``[.., n, n]``) of
    the streams ``x`` ``[.., n, C]``."""
    n = spec["hc_mult"]
    v = rms_norm(x.reshape(x.shape[:-2] + (-1,)), p["norm"]["scale"],
                 spec["rms_norm_eps"])
    z = mm(v, p["phi"]["kernel"], quant)
    a, b = p["alpha"]["scale"], p["bias"]["bias"]
    pre = jax.nn.sigmoid(a[0] * z[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * z[..., n:2 * n] + b[n:2 * n])
    res = (a[2] * z[..., 2 * n:] + b[2 * n:]).reshape(z.shape[:-1] + (n, n))
    res = sinkhorn(jnp.clip(res, spec["mhc_h_res_clamp_min"],
                            spec["mhc_h_res_clamp_max"]),
                   spec["hc_sinkhorn_iters"], spec["hc_eps"])
    return pre, post, res


def hyper_connected(p, x, spec, quant, f):
    """One sublayer ``f`` (of the normed mix) around the streams."""
    pre, post, res = maps(p, x, spec, quant)
    y = f(jnp.einsum("...i,...ic->...c", pre, x))
    return (jnp.einsum("...ij,...jc->...ic", res, x)
            + post[..., None] * y[..., None, :])


def block(p, x, spec, quant, dense):
    eps = spec["rms_norm_eps"]
    x = hyper_connected(p["attn_hc"], x, spec, quant, lambda h: mla(
        p["attn"], rms_norm(h, p["attn_norm"]["scale"], eps), spec, quant))

    def mlp(h):
        h = rms_norm(h, p["mlp_norm"]["scale"], eps)
        if dense:
            m = p["mlp"]
            return swiglu(h, m["gate"]["kernel"], m["up"]["kernel"],
                          m["down"]["kernel"], quant)
        return moe(p["moe"], h, spec, quant)

    return hyper_connected(p["mlp_hc"], x, spec, quant, mlp)


def losses(params, tokens, spec, quant=None):
    """``tokens`` ``int32[rows, S + 1]`` -> {``ce_loss``,
    ``total_loss``}: the mean next-token cross-entropy over every
    position of every row."""
    s = tokens.shape[1] - 1
    e = params["embed"]["kernel"][tokens[:, :s]]
    x = jnp.repeat(e[:, :, None, :], spec["hc_mult"], axis=2)
    for i in range(spec["layers_held"]):
        dense = i < spec["dense_layers_held"]
        x = jax.checkpoint(
            lambda p, x, dense=dense: block(p, x, spec, quant, dense))(
                params[f"block{i}"], x)
    h = rms_norm(jnp.sum(x, axis=2), params["final_norm"]["scale"],
                 spec["rms_norm_eps"])
    ce = cross_entropy(h, params["head"]["kernel"], tokens[:, 1:],
                       jnp.ones(tokens[:, 1:].shape, jnp.float32), quant)
    return {"ce_loss": ce, "total_loss": ce}
