"""JoyAI-LLM-Flash's forward pass and training losses in plain
``jax.numpy`` and float32, following DeepSeek-V3 (arXiv:2412.19437)
sections 2.1.1 (MLA), 2.1.2 (DeepSeekMoE with auxiliary-loss-free
routing) and 2.2 (multi-token prediction), at the configuration's
share: the router scores all ``n_routed_experts`` and picks
``num_experts_per_tok``; only the experts ``experts_held`` = [first,
count] add to the output; embedding, head and loss are over
``vocab_rows`` ids.  No kernel, no sorting: a dense ``[tokens,
experts]`` matrix of gates, masked to the held experts, one expert
after another over every token.  It takes no array and no code from
the program.

Departures from the paper and the published checkpoint's layout:

* Rotary embedding as the Hugging Face modelling file applies it under
  ``rope_interleave``: the interleaved pairs are moved to halves and
  rotated by halves.  The same permutation on q and k, so every score
  equals rotating the pairs in place (what the program does).
* The MTP module's output norm is its own parameter (the checkpoint's
  ``shared_head.norm``); embedding and head are the trunk's.
* The router's selection-only bias is held (the configuration's
  ``reduced``: ``router_bias_update``): never updated, and there is no
  sequence-wise auxiliary loss.  The router's weights train like any
  matrix, on this chip's part of their gradient.
* For memory only, with no change of arithmetic: each layer, each
  (row, head block) of attention and each expert is recomputed in the
  backward pass (``jax.checkpoint``); the scores of a head block are a
  full ``[heads, S, S]`` array.
* ``quant`` (the control): every matrix product's two operands pass
  through it first.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.init import param_key

HEAD_BLOCK = 8


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
        dense = i < spec["first_k_dense_replace"]
        for path, what in _block_shapes(spec, dense).items():
            out[(f"block{i}",) + path] = what
    if spec["num_nextn_predict_layers"]:
        out[("mtp_hnorm",)] = ((d,), "scale")
        out[("mtp_enorm",)] = ((d,), "scale")
        out[("mtp_eh_proj",)] = ((2 * d, d), "kernel")
        out[("mtp_final_norm",)] = ((d,), "scale")
        for path, what in _block_shapes(spec, False).items():
            out[("mtp_block",) + path] = what
    return out


def init_params(spec, seed):
    """Nested {module: {..: {kind: array}}} float32: kernels normal
    (0, init_std) (the embedding: embed_init_std), norm scales one, the
    routing bias normal (0, router_bias_std); each from the root key
    folded with its module's path (flax's rule,
    ``benchmark/reference/init.py``)."""
    shapes = param_shapes(spec)
    std = {"kernel": spec["init_std"], "bias": spec["router_bias_std"]}

    def build(root):
        params = {}
        for path, (shape, kind) in shapes.items():
            node = params
            for part in path:
                node = node.setdefault(part, {})
            if kind == "scale":
                node[kind] = jnp.ones(shape, jnp.float32)
            else:
                scale = (spec["embed_init_std"] if path == ("embed",)
                         else std[kind])
                node[kind] = scale * jax.random.normal(
                    param_key(root, path), shape, jnp.float32)
        return params

    return jax.jit(build)(jax.random.PRNGKey(seed))


def decay_mask(params):
    """Decoupled weight decay on the matrices only: not on the norm
    scales, not on the held routing bias."""
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
    """``x`` ``[S, heads, d]``: interleaved pairs to halves, then the
    rotation by halves (Hugging Face ``apply_rotary_pos_emb_interleave``)."""
    s, h, d = x.shape
    x = x.reshape(s, h, d // 2, 2).transpose(0, 1, 3, 2).reshape(s, h, d)
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


def mla(p, h, spec, quant):
    """``h`` ``[rows, S, hidden]`` (already normed) -> attention output
    before the residual."""
    nh = spec["num_attention_heads"]
    dn, dr, dv = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                  spec["v_head_dim"])
    rank, eps, theta = (spec["kv_lora_rank"], spec["rms_norm_eps"],
                        spec["rope_theta"])

    def one_row(x):                                  # [S, hidden]
        s = x.shape[0]
        cq = rms_norm(mm(x, p["q_a"]["kernel"], quant),
                      p["q_a_norm"]["scale"], eps)
        q = mm(cq, p["q_b"]["kernel"], quant).reshape(s, nh, dn + dr)
        kva = mm(x, p["kv_a"]["kernel"], quant)
        ckv = rms_norm(kva[:, :rank], p["kv_a_norm"]["scale"], eps)
        k_rope = rotary(kva[:, None, rank:], theta)          # one head
        kv = mm(ckv, p["kv_b"]["kernel"], quant).reshape(s, nh, dn + dv)
        q = jnp.concatenate([q[..., :dn], rotary(q[..., dn:], theta)], -1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_rope, (s, nh, dr))], -1)
        v = kv[..., dn:]

        def heads(a):       # [S, nh, d] -> [blocks, HEAD_BLOCK, S, d]
            hb = min(HEAD_BLOCK, nh)
            return a.transpose(1, 0, 2).reshape(nh // hb, hb, s, a.shape[-1])

        o = jax.lax.map(lambda qkv: jax.checkpoint(_attend)(*qkv),
                        (heads(q), heads(k), heads(v)))
        o = o.reshape(nh, s, dv).transpose(1, 0, 2).reshape(s, nh * dv)
        return mm(o, p["o"]["kernel"], quant)

    return jax.lax.map(one_row, h)


def swiglu(x, gate, up, down, quant):
    return mm(jax.nn.silu(mm(x, gate, quant)) * mm(x, up, quant), down,
              quant)


def routing(p, x, spec):
    """(ids ``[T, k]``, dense gates ``[T, n_routed]``): sigmoid scores,
    selection by score + bias, gates = scaling x score / sum of the
    selected scores."""
    scores = jax.nn.sigmoid(jnp.matmul(x, p["router"]["kernel"]))
    _, ids = jax.lax.top_k(scores + p["router_bias"]["bias"],
                           spec["num_experts_per_tok"])
    chosen = jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], ids].set(1.0)
    picked = scores * chosen
    gates = spec["routed_scaling_factor"] * picked / jnp.sum(
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

    held_gates = gates[:, first:first + count].T
    total, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["experts_gate"]["kernel"], p["experts_up"]["kernel"],
        p["experts_down"]["kernel"], held_gates))
    sh = p["shared"]
    total = total + swiglu(x, sh["gate"]["kernel"], sh["up"]["kernel"],
                           sh["down"]["kernel"], quant)
    return total.reshape(shape)


def block(p, x, spec, quant, dense):
    eps = spec["rms_norm_eps"]
    x = x + mla(p["attn"], rms_norm(x, p["attn_norm"]["scale"], eps),
                spec, quant)
    h = rms_norm(x, p["mlp_norm"]["scale"], eps)
    if dense:
        m = p["mlp"]
        return x + swiglu(h, m["gate"]["kernel"], m["up"]["kernel"],
                          m["down"]["kernel"], quant)
    return x + moe(p["moe"], h, spec, quant)


def cross_entropy(h, head, targets, weights, quant):
    logits = mm(h, head, quant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum((lse - picked) * weights) / jnp.sum(weights)


def losses(params, tokens, spec, quant=None):
    """``tokens`` ``int32[rows, S + 1]`` -> {``ce_loss``, ``mtp_loss``,
    ``total_loss``}: next-token cross-entropy of the trunk, and of the
    depth-1 MTP module for the token after next (the last position has
    no such target), means over the predicted positions."""
    eps = spec["rms_norm_eps"]
    s = tokens.shape[1] - 1
    embed, head = params["embed"]["kernel"], params["head"]["kernel"]
    x = embed[tokens[:, :s]]
    for i in range(spec["layers_held"]):
        dense = i < spec["first_k_dense_replace"]
        x = jax.checkpoint(
            lambda p, x, dense=dense: block(p, x, spec, quant, dense))(
                params[f"block{i}"], x)
    ones = jnp.ones(tokens[:, :s].shape, jnp.float32)
    ce = cross_entropy(rms_norm(x, params["final_norm"]["scale"], eps),
                       head, tokens[:, 1:], ones, quant)
    out = {"ce_loss": ce, "total_loss": ce}
    if spec["num_nextn_predict_layers"]:
        merged = jnp.concatenate(
            [rms_norm(x, params["mtp_hnorm"]["scale"], eps),
             rms_norm(embed[tokens[:, 1:]], params["mtp_enorm"]["scale"],
                      eps)], axis=-1)
        y = mm(merged, params["mtp_eh_proj"]["kernel"], quant)
        y = jax.checkpoint(lambda p, y: block(p, y, spec, quant, False))(
            params["mtp_block"], y)
        y = rms_norm(y, params["mtp_final_norm"]["scale"], eps)
        targets = jnp.concatenate(
            [tokens[:, 2:], jnp.zeros_like(tokens[:, :1])], axis=1)
        mtp = cross_entropy(y, head, targets, ones.at[:, -1].set(0.0),
                            quant)
        out["mtp_loss"] = mtp
        out["total_loss"] = ce + spec["mtp_loss_weight"] * mtp
    return out
