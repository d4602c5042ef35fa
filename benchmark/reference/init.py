"""The model's initial weights, remade from the seed alone.

The program builds its model with flax and initialises it with
``model.init(PRNGKey(seed), ...)``: every kernel is LeCun-normal
(truncated normal, variance 1/fan_in), every bias zero, and the key of
a parameter is the root key folded with the first four bytes of the
SHA-1 of its module path and its creation index in its module (flax's
``LazyRng``; a kernel is its module's first parameter).  This file
restates that rule; it takes no array from the program.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp


def param_key(root, path, index=1):
    m = hashlib.sha1()
    for part in tuple(path) + (index,):
        if isinstance(part, str):
            m.update(part.encode("utf-8"))
        else:
            m.update(part.to_bytes((part.bit_length() + 7) // 8, "big"))
    return jax.random.fold_in(
        root, jnp.uint32(int.from_bytes(m.digest()[:4], "big")))


def layer_shapes(spec):
    """{module path: (kernel shape, has bias)} for the whole model."""
    out = {}
    blocks = spec["resnet_blocks"]
    out[("backbone", "conv0")] = ((7, 7, 3, 64), False)
    c_in = 64
    for stage, n in enumerate(blocks):
        ch = 64 * 2 ** stage
        for b in range(n):
            base = ("backbone", f"group{stage}_block{b}")
            out[base + ("conv1",)] = ((1, 1, c_in, ch), False)
            out[base + ("conv2",)] = ((3, 3, ch, ch), False)
            out[base + ("conv3",)] = ((1, 1, ch, ch * 4), False)
            if c_in != ch * 4:
                out[base + ("convshortcut",)] = ((1, 1, c_in, ch * 4), False)
            c_in = ch * 4
    f = spec["fpn_channels"]
    for i in range(4):
        out[("fpn", f"lateral_{i + 2}")] = ((1, 1, 256 * 2 ** i, f), True)
        out[("fpn", f"posthoc_{i + 2}")] = ((3, 3, f, f), True)
    a = len(spec["anchor_ratios"])
    out[("rpn", "conv0")] = ((3, 3, f, f), True)
    out[("rpn", "class")] = ((1, 1, f, a), True)
    out[("rpn", "box")] = ((1, 1, f, 4 * a), True)
    fc, k = spec["fc_head_dim"], spec["num_classes"]
    out[("fastrcnn", "fc6")] = ((7 * 7 * f, fc), True)
    out[("fastrcnn", "fc7")] = ((fc, fc), True)
    out[("fastrcnn", "class")] = ((fc, k), True)
    out[("fastrcnn", "box")] = ((fc, 4 * k), True)
    if spec["mask"]:
        d = spec["mask_head_dim"]
        for i in range(4):
            out[("maskrcnn", f"fcn{i}")] = ((3, 3, f if i == 0 else d, d),
                                           True)
        out[("maskrcnn", "deconv")] = ((2, 2, d, d), True)
        out[("maskrcnn", "conv")] = ((1, 1, d, k), True)
    return out


def init_params(spec, seed):
    """Nested {module: {..: {"kernel", "bias"}}} float32, made in one
    jitted call on the default device."""
    shapes = layer_shapes(spec)
    lecun = jax.nn.initializers.lecun_normal()

    def build(root):
        params = {}
        for path, (shape, has_bias) in shapes.items():
            node = params
            for part in path:
                node = node.setdefault(part, {})
            node["kernel"] = lecun(param_key(root, path), shape, jnp.float32)
            if has_bias:
                node["bias"] = jnp.zeros((shape[-1],), jnp.float32)
        return params

    return jax.jit(build)(jax.random.PRNGKey(seed))


def decay_mask(params, freeze_at):
    """Weight decay on trainable kernels only: no bias, and nothing of
    the frozen stem (freeze_at >= 1) and stages (stage + 2 <= freeze_at)."""
    def mask(path, _leaf):
        keys = [p.key for p in path]
        if keys[-1] != "kernel":
            return False
        if keys[0] == "backbone":
            if keys[1] == "conv0":
                return freeze_at < 1
            if keys[1].startswith("group"):
                return int(keys[1][len("group")]) + 2 > freeze_at
        return True

    return jax.tree_util.tree_map_with_path(mask, params)
