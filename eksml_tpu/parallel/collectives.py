"""Collective helpers + comm-layer tuning + SPMD debug checks.

The reference's collective layer is NCCL ring-allreduce orchestrated by
Horovod with env-var tuning (HOROVOD_FUSION_THRESHOLD=64MB,
NCCL_MIN_NRINGS=8 — charts/maskrcnn/values.yaml:24-28).  Under XLA the
allreduce is *emitted by the compiler* from sharding annotations; what
remains of that layer is (a) explicit collectives for host-side logic
and (b) the debug check the reference cannot do: asserting replicas
actually agree (SURVEY.md §5.2).  The fusion knob has no analogue here:
libtpu 0.0.34 has no ``xla_tpu_all_reduce_combine_threshold_bytes``
(it exits on the unknown flag), so XLA's own collective combining runs
untuned and nothing in this package edits ``LIBTPU_INIT_ARGS``.
"""

from __future__ import annotations

import logging
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

log = logging.getLogger(__name__)


def warm_mesh_collectives(mesh: Mesh) -> None:
    """Establish THIS mesh's cross-host collective context with one
    trivial all-reduce, executed at init while every host is aligned
    from the rendezvous barrier.

    Collective channels connect lazily at the first executed collective
    with a fixed deadline (XLA:CPU's Gloo pairs: ~30 s).  In training,
    that first execution sits right after each host's train-step
    compile — and any compile-time skew (cache hit on one host, miss on
    another; a loaded CI box) lands inside the connect window and kills
    the run with "Gloo context initialization failed".  Horovod solved
    the same problem with its init-time allreduce; this is that, per
    mesh.  No-op single-process.  One retry absorbs a transient
    first-connect timeout; a second failure raises — failing fast at
    init beats failing minutes later at step 1."""
    if jax.process_count() == 1:
        return
    from jax.sharding import NamedSharding

    n = int(np.prod(mesh.devices.shape))
    x = jax.device_put(
        jnp.ones((n,), jnp.float32),
        NamedSharding(mesh, P(tuple(mesh.axis_names))))
    total = jax.jit(jnp.sum,
                    out_shardings=NamedSharding(mesh, P()))
    for attempt in (1, 2):
        try:
            out = float(np.asarray(total(x)))
            if out != float(n):  # explicit: must survive python -O
                raise AssertionError(
                    f"mesh warm-up all-reduce returned {out}, "
                    f"expected {n} — collective context is broken")
            return
        except Exception as e:  # noqa: BLE001 — one retry, then surface
            if attempt == 2:
                raise
            # ADVICE r3: log the first failure (and back off briefly)
            # so a transient-then-fatal connect failure leaves a record
            # of the retry in the multihost logs, not just the second
            # exception.
            log.warning("mesh warm-up all-reduce failed "
                        "(%s: %s); retrying once in 2s",
                        type(e).__name__, e)
            time.sleep(2.0)


def cross_host_sum(tree):
    """Sum a pytree of *host-local* metric values across all processes
    (loss sums, eval detection counts) — the role Horovod's allreduce
    served outside the gradient path.  Uses a host-side allgather, not
    an in-program collective: each process may pass different values,
    which a replicated shard_map input could not express.  Identity in
    single-process runs."""
    tree = jax.tree.map(jnp.asarray, tree)
    if jax.process_count() == 1:
        return tree
    from jax.experimental import multihost_utils

    gathered = multihost_utils.process_allgather(tree)
    return jax.tree.map(lambda x: x.sum(axis=0), gathered)


def param_fingerprint(params, rng: jax.Array | None = None) -> jnp.ndarray:
    """Order- and position-sensitive fingerprint of a param tree (plus,
    optionally, the training PRNG key).  Equal across replicas ⇔
    replicas in sync.

    Three mixing terms per leaf, so the divergences a plain mean
    misses still move the fingerprint:
    - Weyl-weighted sum (weights ``frac(i·φ)+0.5`` over the flattened
      leaf): position-sensitive, so permuting values within a leaf —
      which preserves mean AND sum of squares — changes it;
    - second moment: catches sign flips / rescalings that preserve a
      weighted sum;
    - leaf-index multiplier: catches two leaves swapping contents.

    Returns a VECTOR fingerprint: component 0 is the param mix
    (compared to ``atol``); when ``rng`` is given, each key word's
    high and low 16 bits follow as separate components.  Each half-word
    is < 2^16 and therefore EXACTLY representable in float32, so key
    comparison is bit-exact and never dilutes the param component's
    sensitivity (a lossy ``uint32→f32`` cast would round away low-bit
    key divergence AND swamp atol with ~1e9-scale magnitudes).  A
    diverged key stream corrupts training silently long before the
    params drift apart (SURVEY.md §5.2)."""
    phi = 0.6180339887498949  # Weyl increment: irrational ⇒ no period
    acc = jnp.zeros((), jnp.float32)
    for i, leaf in enumerate(jax.tree.leaves(params)):
        flat = leaf.astype(jnp.float32).reshape(-1)
        w = jnp.mod(jnp.arange(flat.shape[0], dtype=jnp.float32) * phi,
                    1.0) + 0.5
        n = jnp.float32(flat.shape[0])
        mix = jnp.dot(w, flat) / n + 0.7 * jnp.dot(flat, flat) / n
        acc = acc + jnp.float32((i % 97) + 1) * mix
    parts = [acc.reshape(1)]
    if rng is not None:
        words = jax.random.key_data(rng).astype(jnp.uint32).reshape(-1)
        parts.append((words >> 16).astype(jnp.float32))
        parts.append((words & 0xFFFF).astype(jnp.float32))
    return jnp.concatenate(parts)


def assert_replicas_in_sync(params, mesh: Mesh, axis: str = "data",
                            atol: float = 1e-5,
                            rng: jax.Array | None = None) -> bool:
    """Debug mode (SURVEY.md §5.2): verify every data-parallel replica
    holds identical parameters (and, when given, the same PRNG key) —
    the silent-divergence failure the reference's Horovod stack can't
    detect.  Returns True when in sync; raises otherwise.

    Why this works even though ``params`` claims replication: in
    multi-process SPMD a "replicated" jax.Array's per-host shards can
    genuinely differ (each host materialized them from diverged local
    state — bad restore, nondeterministic host preprocessing, a
    donation bug).  The fingerprint is computed per-device from the
    LOCAL shard, then pmax/pmin over the mesh exposes any spread.
    Negative-path proof: tests/test_parallel.py injects a divergent
    buffer into a replicated array and asserts this raises."""
    from jax import shard_map

    fp = param_fingerprint(params, rng=rng)

    def check(x):
        mine = x
        theirs = jax.lax.pmax(x, axis)
        low = jax.lax.pmin(x, axis)
        return jnp.stack([mine, theirs, low])

    out = shard_map(check, mesh=mesh, in_specs=P(), out_specs=P(None),
                    check_vma=False)(fp)
    mine, high, low = np.asarray(out)
    # component 0: param mix (float tolerance); components 1..: exact
    # 16-bit PRNG key halves (any spread at all is divergence)
    spread = np.abs(high - low)
    if spread[0] > atol or (spread.shape[0] > 1
                            and np.any(spread[1:] > 0)):
        what = ("params" if spread[0] > atol else "PRNG key stream")
        raise AssertionError(
            f"data-parallel replicas diverged ({what}): fingerprint "
            f"spread {spread.max()} (mine={mine.tolist()}, "
            f"low={low.tolist()}, high={high.tolist()})")
    return True
