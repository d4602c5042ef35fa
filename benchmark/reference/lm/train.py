"""The reference's first training steps: ``jax.grad`` of
``model.losses`` over the batch, a hand-written global-norm clip and
AdamW (Adam moments with bias correction, decoupled weight decay on
the matrices, linear warm-up): DeepSeek-V3 section 4.2's optimizer,
restated.

The gradient is taken on the device at full float32 precision
(``jax.default_matmul_precision("highest")``); the clip and the update
run on the host in numpy, leaf by leaf and in place, so that Adam's two
moments (two more copies of the weights) never sit on the device
beside the float32 weights, gradients and activations.
"""

from __future__ import annotations

import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import jax
import jax.numpy as jnp

from benchmark import lm_direction
from benchmark.reference.train import as_int8, learning_rate
from . import model

QUANT = {"float32": None, "int8": as_int8}


def _leaves(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(p.key for p in path): leaf for path, leaf in flat}


def _norm(x):
    return float(np.sqrt(np.sum(np.square(x, dtype=np.float64))))


def adamw_leaf(p, g, mu, nu, decayed, scale, lr, t, hyper):
    """One leaf's update, everything in place (``g`` is consumed);
    ``scale`` is the clip's factor, ``t`` the 1-based step."""
    b1, b2 = hyper["adam_b1"], hyper["adam_b2"]
    g *= np.float32(scale)
    mu *= np.float32(b1)
    mu += np.float32(1 - b1) * g
    np.square(g, out=g)
    nu *= np.float32(b2)
    nu += np.float32(1 - b2) * g
    # g becomes the update: mu_hat / (sqrt(nu_hat) + eps) [+ decay x p]
    np.divide(nu, np.float32(1 - b2 ** t), out=g)
    np.sqrt(g, out=g)
    g += np.float32(hyper["adam_eps"])
    np.divide(mu, g, out=g)
    g /= np.float32(1 - b1 ** t)
    if decayed:
        g += np.float32(hyper["weight_decay"]) * p
    g *= np.float32(lr)
    p -= g


def _release():
    """Hand freed host memory back to the system: the TPU runtime
    alone maps ~15 of the chip machine's 40 GiB, and glibc keeps what
    numpy frees."""
    import ctypes
    import gc

    gc.collect()
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except OSError:
        pass


def _say(what):
    """Progress on standard error: the full-size reference takes
    minutes and most of the host's memory."""
    rss = 0.0
    try:
        with open("/proc/self/statm") as f:
            rss = int(f.read().split()[1]) * 4096 / 2 ** 30
    except OSError:
        pass
    print(f"reference/lm: {what} (host rss {rss:.1f} GiB)", file=sys.stderr,
          flush=True)


def run_steps(spec, hyper, seed, batches, precision="float32", rows=None):
    """Follow ``len(batches)`` steps from the seed.  ``batches``: host
    batches as the step receives them (``tokens`` ``[rows, S + 1]``).
    ``rows``: the rows of each batch that take part (None = all; the
    half-batch fault passes the first half).  Returns the readings the
    comparison uses, all plain Python numbers.

    Host memory is the scarce thing at full size (680 M parameters are
    2.7 GB a copy): the host holds the weights and Adam's two moments,
    one gradient at a time, and remakes the initial weights from the
    seed for the parameters' change instead of keeping them."""
    quant = QUANT[precision]

    @jax.jit
    def grad_fn(params, tokens):
        def loss(p):
            terms = model.losses(p, tokens, spec, quant)
            return terms["total_loss"], terms
        (_, terms), grads = jax.value_and_grad(loss, has_aux=True)(params)
        return terms, grads

    def host_copy(tree):
        """{leaf name: writable numpy copy}, leaf by leaf, leaving no
        cached host copy behind on the device arrays."""
        out = {}
        for name, leaf in _leaves(tree).items():
            out[name] = np.array(leaf)
            leaf.delete()
        return out

    def to_device(host):
        return jax.tree_util.tree_unflatten(
            structure, [jnp.asarray(host[k]) for k in host])

    _release()
    init = model.init_params(spec, seed)
    structure = jax.tree_util.tree_structure(init)
    decayed = _leaves(model.decay_mask(init))
    host = host_copy(init)
    del init
    mu = {k: np.zeros_like(v) for k, v in host.items()}
    nu = {k: np.zeros_like(v) for k, v in host.items()}
    out = {"loss": [], "terms": []}
    with jax.default_matmul_precision("highest"), \
            ThreadPoolExecutor(max_workers=4) as pool:
        for s, batch in enumerate(batches):
            tokens = np.asarray(batch["tokens"])
            if rows is not None:
                tokens = tokens[list(rows)]
            device_params = to_device(host)
            terms, grads = grad_fn(device_params, jnp.asarray(tokens))
            terms = {k: float(v) for k, v in terms.items()}
            del device_params
            out["loss"].append(terms["total_loss"])
            out["terms"].append(terms)
            grads = host_copy(grads)
            norms = {k: _norm(g) for k, g in grads.items()}
            if s == 0:
                out["grad_norm"] = norms
            total = float(np.sqrt(sum(n * n for n in norms.values())))
            clip = hyper["gradient_clip"]
            scale = clip / total if clip > 0 and total > clip else 1.0
            lr = learning_rate(s, hyper)
            list(pool.map(lambda k: adamw_leaf(
                host[k], grads[k], mu[k], nu[k], decayed[k], scale, lr,
                s + 1, hyper), host))
            del grads
            if s == 0:
                out["first_trace_norm"] = dict(
                    {k: _norm(v) for k, v in mu.items()},
                    **lm_direction.magnitudes(mu))
            _say(f"step {s + 1} ({precision}, {len(tokens)} rows) loss "
                 f"{terms['total_loss']:.6f}, gradient norm {total:.4f}")
    del mu, nu
    _release()
    p0 = _leaves(model.init_params(spec, seed))
    out["delta_norm"] = {}
    for k in host:
        out["delta_norm"][k] = _norm(host[k] - np.asarray(p0[k]))
        p0[k].delete()
    return out
