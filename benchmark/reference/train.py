"""The reference's first training steps: per-image gradients of
``model.image_losses``, averaged over the batch, through SGD with
momentum, decoupled-from-nothing weight decay added to the gradient,
and linear warm-up -- the tensorpack example's optimizer.

Key schedule (the program's, restated): step ``s`` (0-based) uses
``fold_in(PRNGKey(seed), s)`` split into ``(batch, 2)`` keys; image
``i`` samples its RPN anchors with key ``[i, 0]`` and its ROIs with
key ``[i, 1]``.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from . import init as init_mod
from . import model

EXAMPLE_KEYS = ("images", "image_hw", "gt_boxes", "gt_classes", "gt_valid",
                "gt_crowd", "gt_masks")


def as_int8(x):
    """The control's storage: int8, one symmetric scale per tensor to
    its range (the chip's int8 path runs at twice its bfloat16 peak: the
    step that would tempt a later PR), gradient passed straight through."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, 127.0 / amax, 1.0)
    rounded = jnp.clip(jnp.round(x * scale), -127.0, 127.0) / scale
    return x + jax.lax.stop_gradient(rounded - x)


QUANT = {"float32": None, "int8": as_int8}


def learning_rate(step, hyper):
    base = hyper["base_lr"] * hyper["global_batch"] / 8.0
    warm = hyper["warmup_steps"]
    first_drop = min(hyper["lr_schedule"]) * 8 // hyper["global_batch"]
    if step >= first_drop:
        raise ValueError("the reference follows the first steps only")
    if step >= warm:
        return base
    init = base * hyper["warmup_init_factor"]
    return init + (base - init) * step / warm


def host_leaves(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(p.key for p in path): np.asarray(leaf, np.float32)
            for path, leaf in flat}


def leaf_norms(tree):
    return {k: float(np.sqrt(np.sum(np.square(v.astype(np.float64)))))
            for k, v in host_leaves(tree).items()}


def make_image_grad(spec, quant=None):
    def fn(params, ex, keys):
        def loss(p):
            terms = model.image_losses(p, ex, keys, spec, quant)
            return terms["total_loss"], terms
        (_, terms), grads = jax.value_and_grad(loss, has_aux=True)(params)
        return terms, grads
    return jax.jit(fn)


@jax.jit
def _tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def _make_update(hyper, mask):
    def update(params, trace, gsum, n, lr):
        grads = jax.tree.map(
            lambda g, p, decayed: (g / n + hyper["weight_decay"] * p
                                   if decayed else g / n),
            gsum, params, mask)
        trace = jax.tree.map(lambda g, t: g + hyper["momentum"] * t,
                             grads, trace)
        return jax.tree.map(lambda p, t: p - lr * t, params, trace), trace
    return jax.jit(update)


def run_steps(spec, hyper, seed, batches, precision="float32", rows=None):
    """Follow ``len(batches)`` steps from the seed.  ``batches``: host
    batches as the step receives them (numpy, leading batch axis).
    ``rows``: the images of each batch that take part (None = all; the
    half-batch fault passes the first half).  Returns the readings the
    comparison uses, all plain Python numbers."""
    grad_fn = make_image_grad(spec, QUANT[precision])
    params = init_mod.init_params(spec, seed)
    p0 = params
    mask = init_mod.decay_mask(params, spec["freeze_at"])
    update = _make_update(hyper, mask)
    trace = jax.tree.map(jnp.zeros_like, params)
    root = jax.random.PRNGKey(seed)
    out = {"loss": [], "terms": []}
    for s, batch in enumerate(batches):
        b = batch["images"].shape[0]
        keys = jax.random.split(jax.random.fold_in(root, s), (b, 2))
        use = list(range(b)) if rows is None else list(rows)
        gsum, terms_sum = None, None
        for i in use:
            ex = {k: jnp.asarray(batch[k][i]) for k in EXAMPLE_KEYS
                  if k in batch and (k != "gt_masks" or spec["mask"])}
            terms, grads = grad_fn(params, ex, keys[i])
            gsum = grads if gsum is None else _tree_add(gsum, grads)
            terms_sum = (terms if terms_sum is None
                         else _tree_add(terms_sum, terms))
        terms = {k: float(v) / len(use) for k, v in terms_sum.items()}
        out["loss"].append(terms["total_loss"])
        out["terms"].append(terms)
        if s == 0:
            out["grad_norm"] = leaf_norms(
                jax.tree.map(lambda g: g / len(use), gsum))
        params, trace = update(params, trace, gsum, float(len(use)),
                               learning_rate(s, hyper))
        if s == 0:
            out["first_trace_norm"] = leaf_norms(trace)
        del gsum
    out["delta_norm"] = leaf_norms(jax.tree.map(jnp.subtract, params, p0))
    return out
