"""The window-and-experts reference's first training steps:
``jax.grad`` of ``model.losses`` over the batch, a hand-written
global-norm clip and AdamW (``adamw_leaf`` of the sequence task's
reference: Adam moments with bias correction, decoupled weight decay on
the matrices, linear warm-up).

The gradient is taken on the device at full float32 precision
(``jax.default_matmul_precision("highest")``); the clip and the update
run on the host in numpy, leaf by leaf and in place, so that Adam's two
moments never sit on the device beside the float32 weights, gradients
and activations (692 M parameters are 2.8 GB a copy at the cell's
size).
"""

from __future__ import annotations

import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import jax
import jax.numpy as jnp

from benchmark import lm_direction
from benchmark.reference.lm.train import (_leaves, _norm, _release,
                                          adamw_leaf)
from benchmark.reference.train import as_int8, learning_rate
from . import model

QUANT = {"float32": None, "int8": as_int8}


def run_steps(spec, hyper, seed, batches, precision="float32", rows=None):
    """Follow ``len(batches)`` steps from the seed.  ``batches``: host
    batches as the step receives them (``tokens`` ``[rows, S + 1]``).
    ``rows``: the rows of each batch that take part (None = all; the
    half-batch fault passes the first half).  Returns the readings the
    comparison uses, all plain Python numbers.  The host holds the
    weights and Adam's two moments and one gradient at a time; the
    initial weights are remade from the seed for the parameters'
    change."""
    quant = QUANT[precision]
    t0 = time.perf_counter()

    def say(what):
        """Progress on standard error: at the cell's size the steps
        take minutes."""
        print(f"reference/swa_moe: {what} ({precision}, "
              f"{time.perf_counter() - t0:.1f} s)", file=sys.stderr,
              flush=True)

    @jax.jit
    def grad_fn(params, tokens):
        def loss(p):
            terms = model.losses(p, tokens, spec, quant)
            return terms["total_loss"], terms
        (_, terms), grads = jax.value_and_grad(loss, has_aux=True)(params)
        return terms, grads

    def host_copy(tree):
        out = {}
        for name, leaf in _leaves(tree).items():
            out[name] = np.array(leaf)
            leaf.delete()
        return out

    _release()
    init = model.init_params(spec, seed)
    structure = jax.tree_util.tree_structure(init)
    decayed = _leaves(model.decay_mask(init))
    host = host_copy(init)
    del init
    mu = {k: np.zeros_like(v) for k, v in host.items()}
    nu = {k: np.zeros_like(v) for k, v in host.items()}
    out = {"loss": [], "terms": []}
    say(f"{len(host)} leaves on the host")
    with jax.default_matmul_precision("highest"), \
            ThreadPoolExecutor(max_workers=4) as pool:
        for s, batch in enumerate(batches):
            tokens = np.asarray(batch["tokens"])
            if rows is not None:
                tokens = tokens[list(rows)]
            device_params = jax.tree_util.tree_unflatten(
                structure, [jnp.asarray(host[k]) for k in host])
            terms, grads = grad_fn(device_params, jnp.asarray(tokens))
            terms = {k: float(v) for k, v in terms.items()}
            say(f"step {s + 1} gradient of {len(tokens)} rows, loss "
                f"{terms['total_loss']:.6f}")
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
            say(f"step {s + 1} update, gradient norm {total:.4f}")
    del mu, nu
    _release()
    p0 = _leaves(model.init_params(spec, seed))
    out["delta_norm"] = {}
    for k in host:
        out["delta_norm"][k] = _norm(host[k] - np.asarray(p0[k]))
        p0[k].delete()
    return out
