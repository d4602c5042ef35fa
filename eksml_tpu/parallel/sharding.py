"""Pluggable sharding-plan compiler: partition rules → compiled steps.

The reference stack has exactly one parallelism strategy — a full
model replica per accelerator (Horovod DP, SURVEY.md §2c) — and until
this module so did we: ``Trainer.compiled_step`` hard-coded
``PartitionSpec("data")`` batches against fully-replicated state, and
the ``model`` mesh axis sat reserved at size 1.  This module makes the
layout a *config knob* instead of a code path:

- **Partition-rule engine** (``match_partition_rules``): an ordered
  list of ``(regex, action)`` rules matched with ``re.search`` against
  ``/``-joined pytree paths (``backbone/conv0/kernel``,
  ``0/trace/fpn/lateral_2/kernel`` — optimizer momentum mirrors the
  param paths, so one rule set claims both).  First match wins; the
  list MUST end with a catch-all; scalars never partition.  The same
  idea as the ``match_partition_rules`` regex→PartitionSpec engines in
  the LLM-training world (SNIPPETS.md [1]), adapted for a convnet's
  heterogeneous ranks: besides a literal ``PartitionSpec`` tuple, an
  action may be the string ``"fsdp"`` (place the fsdp axis on the
  largest evenly-divisible dim; fall back to replicated when none
  divides) or ``"replicated"``.

- **``ShardingPlan``** (SNIPPETS.md [3]'s compile-with-plan layer):
  one object that owns the strategy name, the rules, the batch spec,
  and the jit wrapper, so train/predict/dryrun ask the *plan* for
  in/out shardings instead of hard-coding them.  Strategies:

  * ``replicated`` — today's behavior, the default.  Specs are all
    ``P()``; ``compute_params``/``storage_grads`` are identity, so
    the compiled program is unchanged (loss streams stay
    bit-identical with existing runs).
  * ``fsdp`` — params AND optimizer state shard over the ``fsdp``
    mesh axis (ZeRO-style).  Inside the step the params are gathered
    just-in-time via a sharding constraint, gradients are constrained
    back to the storage layout (XLA emits the all-gather /
    reduce-scatter pair), and the optimizer update runs on shards.
    Per-device *persistent* state drops by ~the axis size; transient
    gather buffers are scheduled by XLA near their use.
  * ``tensor`` — the big FPN/head weights (lateral + output convs,
    RPN conv0, box-head fc6/fc7, mask fcn/deconv) store their OUTPUT
    features sharded over the ``model`` mesh axis; everything else
    stays replicated.  Inside the step the same constraint pair fsdp
    uses applies on the model axis: ``compute_params`` is the
    matching input-side constraint (XLA lowers it to all-gathers of
    the weight shards next to their matmuls) and ``storage_grads``
    scatters the gradients back (reduce-scatter on ``model``) so the
    optimizer updates shards.  Compute is replication-equivalent, so
    loss streams stay at parity with ``replicated``.
  * ``2d`` — the fsdp × tensor composition: the tensor-target
    weights place ``("fsdp", "model")`` jointly (model on the output
    features, fsdp on the largest remaining divisible dim) and every
    other leaf falls through to fsdp auto-placement.  Per-device
    state tracks the **axis product** — the memory plan that unlocks
    R101/cascade backbones at 1344px.

``plan_mesh`` turns the ``TRAIN.SHARDING.*`` knobs into a
``(mesh_shape, axis_names)`` pair for :func:`build_mesh`, inserting
the ``fsdp`` axis between ``data`` and ``model`` and validating the
axis sizes (and for ``2d`` their product) against the per-slice
device count — the fsdp/model all-gathers are per-step traffic and
must ride ICI, never a DCN hop.

At ``TPU.NUM_SLICES > 1`` with ``TRAIN.SHARDING.EXCHANGE=
"hierarchical"`` the sharded strategies grow a leading ``slice``
mesh axis and ``storage_grads`` stages the gradient exchange —
reduce-scatter on ICI within each slice, all-reduce of the
1/per-slice partials over **DCN**, all-gather back on ICI — so the
thin inter-slice NIC only ever carries one slice-reduced copy of
the gradients instead of bounding a flat all-replica ring
(TPU Multislice / MegaScale-style hierarchical reduction).
"""

from __future__ import annotations

import functools
import logging
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# ONE divisor-list definition with build_mesh's model-axis error
# (mesh.py imports nothing from this module — no cycle)
from eksml_tpu.parallel.mesh import divisors as _divisors

log = logging.getLogger(__name__)

STRATEGIES = ("replicated", "fsdp", "tensor", "2d")

#: gradient-exchange layouts across slices (TRAIN.SHARDING.EXCHANGE).
#: "flat" prices/runs one ring over every replica; "hierarchical"
#: stages it as ICI reduce-scatter within each slice, DCN all-reduce
#: of the 1/per-slice partials, ICI all-gather back — only matters at
#: TPU.NUM_SLICES > 1 (a single slice has no DCN hop to protect).
EXCHANGES = ("flat", "hierarchical")

#: rule actions (besides a literal PartitionSpec tuple)
REPLICATED = "replicated"
FSDP_AUTO = "fsdp"
TENSOR_AUTO = "tensor"   # model axis on the output-feature (last) dim
TWOD_AUTO = "2d"         # model on output features + fsdp elsewhere

#: the tensor-parallel weight targets: FPN lateral/output convs, the
#: shared RPN conv, the box-head fc6/fc7 matmuls (plain and cascade),
#: and the mask-head fcn/deconv stack.  Flax Conv/Dense kernels keep
#: output features LAST, which is the dim the auto actions shard;
#: tiny per-class output layers (rpn class/box, fastrcnn class/box,
#: the mask logit conv) stay replicated — their widths are class
#: counts, not hidden dims, and rarely divide a model axis.
TENSOR_TARGETS = (
    r"(fpn/(lateral|posthoc)_\d+"
    r"|rpn/conv0"
    r"|(fastrcnn|cascade\d*)/(fc6|fc7)"
    r"|maskrcnn/(fcn\d+|deconv))/kernel$")

# Strategy-default rule sets (TRAIN.SHARDING.RULES=() selects these).
# fsdp shards EVERY leaf with a divisible dim — biases and norm scales
# included, exactly like ZeRO — because the catch-all's auto placement
# already degrades to replicated for the leaves that cannot split.
# tensor shards only the TENSOR_TARGETS output features on the model
# axis; 2d composes both — targets place (fsdp, model) jointly and
# every other leaf falls through to fsdp auto-placement.
DEFAULT_RULES: Dict[str, Tuple[Tuple[str, Any], ...]] = {
    "replicated": ((r".*", REPLICATED),),
    "fsdp": ((r".*", FSDP_AUTO),),
    "tensor": (
        (TENSOR_TARGETS, TENSOR_AUTO),
        (r".*", REPLICATED),
    ),
    "2d": (
        (TENSOR_TARGETS, TWOD_AUTO),
        (r".*", FSDP_AUTO),
    ),
}

# two probes approximating "matches any path": a multi-segment
# nonsense path and a bare leaf name.  A last rule that misses either
# is not a catch-all (e.g. "kernel$"), and the engine would raise on
# the first unclaimed leaf deep inside trainer init — fail at plan
# construction instead, naming the fix.
_CATCHALL_PROBES = ("zz9/plural/z/alpha", "leaf")


def _key_str(k) -> str:
    """One pytree KeyEntry → path segment."""
    for attr in ("key", "idx", "name"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def tree_path_str(path: Sequence) -> str:
    """Pytree key path → ``a/b/c`` string the rule regexes match."""
    return "/".join(_key_str(k) for k in path)


def validate_rules(rules) -> Tuple[Tuple[str, Any], ...]:
    """Normalize + validate an ordered rule list.

    Each rule is ``(pattern, action)`` with action one of
    ``"replicated"``, ``"fsdp"``, ``"tensor"``, ``"2d"``, or a tuple
    of PartitionSpec entries (``None`` / axis name / tuple of axis
    names).  The last rule must be a catch-all — every leaf must be
    *claimed*, never defaulted.
    """
    try:
        rules = tuple(
            (str(p), a if isinstance(a, str) else tuple(a))
            for p, a in rules)
    except (TypeError, ValueError) as e:
        raise ValueError(
            f"partition rules must be (pattern, action) pairs, got "
            f"{rules!r}") from e
    if not rules:
        raise ValueError(
            "partition rules are empty — need at least a catch-all "
            "like ('.*', 'replicated')")
    for pat, action in rules:
        try:
            re.compile(pat)
        except re.error as e:
            raise ValueError(
                f"partition rule pattern {pat!r} is not a valid "
                f"regex: {e}") from e
        if isinstance(action, str):
            if action not in (REPLICATED, FSDP_AUTO, TENSOR_AUTO,
                              TWOD_AUTO):
                raise ValueError(
                    f"partition rule {pat!r}: string action must be "
                    f"'replicated', 'fsdp', 'tensor' or '2d', got "
                    f"{action!r}")
        else:
            for entry in action:
                ok = entry is None or isinstance(entry, str) or (
                    isinstance(entry, tuple)
                    and all(isinstance(x, str) for x in entry))
                if not ok:
                    raise ValueError(
                        f"partition rule {pat!r}: spec entry "
                        f"{entry!r} must be None, an axis name, or a "
                        "tuple of axis names")
    last = rules[-1][0]
    if not all(re.search(last, probe) for probe in _CATCHALL_PROBES):
        raise ValueError(
            f"partition rules must end with a catch-all pattern that "
            f"claims every remaining leaf (e.g. ('.*', 'replicated')); "
            f"the last rule {last!r} does not match everything")
    return rules


def _auto_axis_dim(shape: Tuple[int, ...], axis_size: int,
                   exclude: Tuple[int, ...] = ()) -> Optional[int]:
    """Index of the largest dim divisible by ``axis_size`` (ties →
    lowest index), skipping ``exclude``; None when nothing divides
    (caller replicates that axis)."""
    order = sorted((i for i in range(len(shape)) if i not in exclude),
                   key=lambda i: (-shape[i], i))
    for i in order:
        if shape[i] >= axis_size and shape[i] % axis_size == 0:
            return i
    return None


def _spec_from_entries(entries: List[Optional[str]]) -> P:
    # trailing Nones dropped: P('fsdp') == the canonical form
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def _match_leaf(path: str, leaf, rules, mesh_axes: Dict[str, int],
                fsdp_axis: str, model_axis: str) -> Tuple[P, str]:
    """→ (PartitionSpec, why) for one leaf.  ``why`` names the rule
    (or guard) that claimed it — the explain() payload."""
    shape = tuple(getattr(leaf, "shape", ()))
    fsdp_size = int(mesh_axes.get(fsdp_axis, 1))
    model_size = int(mesh_axes.get(model_axis, 1))
    if len(shape) == 0 or int(np.prod(shape)) == 1:
        return P(), "(scalar)"
    for pat, action in rules:
        if re.search(pat, path) is None:
            continue
        if action == REPLICATED:
            return P(), pat
        if action == FSDP_AUTO:
            dim = _auto_axis_dim(shape, fsdp_size)
            if dim is None:
                return P(), f"{pat} (no dim divisible by " \
                            f"{fsdp_axis}={fsdp_size}; replicated)"
            entries: List[Optional[str]] = [None] * len(shape)
            entries[dim] = fsdp_axis
            return _spec_from_entries(entries), pat
        if action in (TENSOR_AUTO, TWOD_AUTO):
            # output features are LAST in flax Conv/Dense kernels —
            # that is the dim the model axis shards (column-parallel
            # weight storage); the matching input-side constraint
            # (compute_params) makes XLA gather the shards next to
            # their matmuls and scatter the grads back
            entries = [None] * len(shape)
            last = len(shape) - 1
            if shape[last] >= model_size and shape[last] % model_size == 0:
                entries[last] = model_axis
            if action == TWOD_AUTO:
                dim = _auto_axis_dim(
                    shape, fsdp_size,
                    exclude=(last,) if entries[last] else ())
                if dim is not None:
                    entries[dim] = fsdp_axis
            if all(e is None for e in entries):
                return P(), (f"{pat} (no dim divisible by "
                             f"{model_axis}={model_size}"
                             + (f"/{fsdp_axis}={fsdp_size}"
                                if action == TWOD_AUTO else "")
                             + "; replicated)")
            return _spec_from_entries(entries), pat
        # literal PartitionSpec tuple
        if len(action) > len(shape):
            raise ValueError(
                f"partition rule {pat!r} spec {action!r} has "
                f"{len(action)} entries but {path!r} has rank "
                f"{len(shape)} (shape {shape})")
        for dim, entry in enumerate(action):
            if entry is None:
                continue
            axes = (entry,) if isinstance(entry, str) else entry
            div = 1
            for a in axes:
                if a not in mesh_axes:
                    raise ValueError(
                        f"partition rule {pat!r} names mesh axis "
                        f"{a!r} but the mesh has axes "
                        f"{tuple(mesh_axes)}")
                div *= mesh_axes[a]
            if shape[dim] % div:
                raise ValueError(
                    f"partition rule {pat!r}: {path!r} dim {dim} "
                    f"(size {shape[dim]}) does not divide over "
                    f"{entry!r} (axis size {div})")
        return P(*action), pat
    raise ValueError(
        f"no partition rule matched leaf {path!r} — the rule list "
        "must end with a catch-all like ('.*', 'replicated')")


def match_partition_rules(rules, tree, mesh: Mesh,
                          fsdp_axis: str = "fsdp",
                          model_axis: str = "model"):
    """Pytree of PartitionSpec from ordered rules (first match wins).

    Accepts arrays or ShapeDtypeStructs.  Raises on an unclaimed leaf;
    pre-validate with :func:`validate_rules` for the earlier,
    friendlier catch-all error.
    """
    mesh_axes = dict(mesh.shape)

    def one(path, leaf):
        spec, _ = _match_leaf(tree_path_str(path), leaf, rules,
                              mesh_axes, fsdp_axis, model_axis)
        return spec

    return jax.tree_util.tree_map_with_path(one, tree)


def tree_bytes_per_device(tree) -> int:
    """Per-device bytes of a (possibly sharded) array pytree.

    Committed jax.Arrays report their actual shard shape; abstract
    leaves without a sharding count their full size (= replicated).
    """
    total = 0
    for leaf in jax.tree.leaves(tree):
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = getattr(leaf, "dtype", None)
        if dtype is None:
            continue
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None and hasattr(sharding, "shard_shape"):
            shape = sharding.shard_shape(shape)
        total += int(np.prod(shape)) * np.dtype(dtype).itemsize
    return total


def publish_state_byte_gauges(params, opt_state) -> Tuple[int, int]:
    """Per-device param/optimizer-state bytes → the
    ``eksml_train_param_bytes`` / ``eksml_train_opt_state_bytes``
    gauges.  ONE definition of the names + help strings for trainer
    and dryrun alike (a rename in one site must not desynchronize
    /metrics).  Returns ``(param_bytes, opt_bytes)``."""
    from eksml_tpu import telemetry

    pb = tree_bytes_per_device(params)
    ob = tree_bytes_per_device(opt_state)
    registry = telemetry.default_registry()
    registry.gauge(
        "eksml_train_param_bytes",
        "per-device parameter bytes under the active sharding "
        "plan").set(float(pb))
    registry.gauge(
        "eksml_train_opt_state_bytes",
        "per-device optimizer-state bytes under the active "
        "sharding plan").set(float(ob))
    return pb, ob


def sharding_knobs(cfg) -> Dict[str, Any]:
    """``TRAIN.SHARDING.*`` values over the canonical defaults —
    config trees predating the knobs keep working (the shared
    ``knobs_with_defaults`` merge, config.py)."""
    from eksml_tpu.config import SHARDING_DEFAULTS, knobs_with_defaults

    return knobs_with_defaults(
        getattr(getattr(cfg, "TRAIN", None), "SHARDING", None),
        SHARDING_DEFAULTS)




def plan_mesh(cfg, n_devices: Optional[int] = None
              ) -> Tuple[Tuple[int, ...], Tuple[str, ...]]:
    """``TRAIN.SHARDING.*`` + ``TPU.MESH_*`` → (mesh_shape, axes) for
    :func:`build_mesh`.

    ``replicated`` keeps the legacy mesh untouched.  ``fsdp`` inserts
    the fsdp axis between ``data`` and the rest, sized by
    ``FSDP_AXIS_SIZE`` (0 = every device of one slice).  ``tensor``
    sizes the existing ``model`` axis from ``MODEL_AXIS_SIZE`` (0 =
    every device of one slice).  ``2d`` composes both: the model axis
    must be set explicitly (>0) and ``FSDP_AXIS_SIZE=0`` resolves to
    the rest of the slice.  Every shard axis — and for ``2d`` the
    fsdp × model product — must divide the per-slice device count:
    parameter all-gathers are per-step traffic and must stay on ICI,
    so a shard group may never straddle a DCN hop.  An explicit
    operator ``TPU.MESH_SHAPE`` always wins (but must name the axes
    the strategy shards over).

    Under ``EXCHANGE="hierarchical"`` at ``TPU.NUM_SLICES > 1`` the
    sharded strategies additionally get a leading ``slice`` mesh axis
    sized to the slice count (the data axis then counts per-slice
    replicas), which is what lets ``ShardingPlan.storage_grads``
    stage the gradient exchange instead of pricing one flat ring at
    the DCN link.
    """
    knobs = sharding_knobs(cfg)
    strategy = str(knobs["STRATEGY"])
    if strategy not in STRATEGIES:
        raise ValueError(
            f"TRAIN.SHARDING.STRATEGY={strategy!r} is not one of "
            f"{STRATEGIES}")
    exchange = str(knobs.get("EXCHANGE", "flat"))
    if exchange not in EXCHANGES:
        raise ValueError(
            f"TRAIN.SHARDING.EXCHANGE={exchange!r} is not one of "
            f"{EXCHANGES}")
    shape = tuple(int(s) for s in cfg.TPU.MESH_SHAPE)
    axes = tuple(cfg.TPU.MESH_AXES)
    if strategy == "replicated":
        return shape, axes
    needs_fsdp = strategy in ("fsdp", "2d")
    needs_model = strategy in ("tensor", "2d")
    if needs_fsdp and "fsdp" not in axes:
        if shape:
            raise ValueError(
                f"TRAIN.SHARDING.STRATEGY={strategy} needs an 'fsdp' "
                f"mesh axis, but the explicit TPU.MESH_SHAPE={shape} /"
                f" TPU.MESH_AXES={axes} does not name one — add it "
                "(e.g. MESH_AXES=('data','fsdp','model')) or clear "
                "MESH_SHAPE to derive the mesh from the knobs")
        axes = axes[:1] + ("fsdp",) + axes[1:]
    if needs_model and "model" not in axes:
        if shape:
            raise ValueError(
                f"TRAIN.SHARDING.STRATEGY={strategy} needs a 'model' "
                f"mesh axis, but the explicit TPU.MESH_SHAPE={shape} /"
                f" TPU.MESH_AXES={axes} does not name one — add it "
                "(e.g. MESH_AXES=('data','fsdp','model')) or clear "
                "MESH_SHAPE to derive the mesh from the knobs")
        axes = axes + ("model",)
    if shape:
        return shape, axes
    n = n_devices if n_devices else len(jax.devices())
    num_slices = max(1, int(getattr(cfg.TPU, "NUM_SLICES", 1)))
    if n % num_slices:
        raise ValueError(
            f"{n} device(s) do not split into TPU.NUM_SLICES="
            f"{num_slices}")
    per_slice = n // num_slices
    m = 1
    if needs_model:
        m = int(knobs["MODEL_AXIS_SIZE"])
        if m == 0 and strategy == "tensor":
            m = per_slice  # the fsdp-knob semantics, on the model axis
        if m < 1 or per_slice % m:
            raise ValueError(
                f"TRAIN.SHARDING.MODEL_AXIS_SIZE={m} is invalid for "
                f"{n} device(s) in {num_slices} slice(s) ({per_slice} "
                f"per slice): the model axis must divide the per-slice"
                f" device count so weight shards never straddle a DCN "
                f"hop (and the 2d strategy needs it set explicitly, "
                f"> 0); valid sizes here: {_divisors(per_slice)}")
    f = 1
    if needs_fsdp:
        f = int(knobs["FSDP_AXIS_SIZE"]) or per_slice // m
        if f < 1 or per_slice % f:
            raise ValueError(
                f"TRAIN.SHARDING.FSDP_AXIS_SIZE={f} is invalid for {n} "
                f"device(s) in {num_slices} slice(s) ({per_slice} per "
                f"slice): the fsdp axis must divide the per-slice device "
                f"count so parameter shards never straddle a DCN hop; "
                f"valid sizes here: {_divisors(per_slice)}")
    if per_slice % (f * m):
        raise ValueError(
            f"TRAIN.SHARDING.FSDP_AXIS_SIZE={f} x "
            f"TRAIN.SHARDING.MODEL_AXIS_SIZE={m} = {f * m} does not "
            f"divide the per-slice device count ({per_slice}): a 2d "
            f"shard group must fit inside one slice so its collectives "
            f"never straddle a DCN hop; the axis product must be one "
            f"of {_divisors(per_slice)}")
    if exchange == "hierarchical" and num_slices > 1:
        # explicit leading "slice" axis: the DCN decomposition becomes
        # a mesh dimension the plan can stage gradients over (ICI
        # reduce-scatter in-slice, DCN all-reduce of partials, ICI
        # all-gather back — ShardingPlan.storage_grads).  The data
        # axis then counts PER-SLICE replicas; slice-major device
        # order (build_mesh) puts each mesh slice on one hardware
        # slice so the trailing axes never straddle the DCN hop.
        axes = ("slice",) + tuple(a for a in axes if a != "slice")
        return (num_slices,) + tuple(
            per_slice // (f * m) if a == "data"
            else f if a == "fsdp"
            else m if a == "model" else 1
            for a in axes[1:]), axes
    # size axes BY NAME: an operator MESH_AXES ordering the fsdp axis
    # anywhere but index 1 must still get its size (positional sizing
    # silently left fsdp at 1 — a fully-replicated run claiming fsdp)
    return tuple(n // (f * m) if a == "data"
                 else f if a == "fsdp"
                 else m if a == "model" else 1
                 for a in axes), axes


class ShardingPlan:
    """Strategy + rules + mesh → shardings and compiled steps.

    The Titanax-style compile-with-plan layer (SNIPPETS.md [3]): the
    trainer never names a PartitionSpec — it asks the plan.
    """

    def __init__(self, strategy: str, mesh: Mesh, rules=(),
                 fsdp_axis: str = "fsdp", model_axis: str = "model",
                 exchange: str = "flat"):
        if strategy not in STRATEGIES:
            raise ValueError(
                f"unknown sharding strategy {strategy!r}; valid: "
                f"{STRATEGIES} (TRAIN.SHARDING.STRATEGY)")
        if exchange not in EXCHANGES:
            raise ValueError(
                f"unknown gradient exchange {exchange!r}; valid: "
                f"{EXCHANGES} (TRAIN.SHARDING.EXCHANGE)")
        self.strategy = strategy
        self.mesh = mesh
        self.fsdp_axis = fsdp_axis
        self.model_axis = model_axis
        self.exchange = exchange
        mesh_axes = dict(mesh.shape)
        if strategy in ("fsdp", "2d") and fsdp_axis not in mesh_axes:
            raise ValueError(
                f"sharding strategy {strategy!r} needs a "
                f"{fsdp_axis!r} mesh axis; this mesh has "
                f"{tuple(mesh.axis_names)} — build it via "
                "plan_mesh(cfg) (train.py does)")
        if strategy in ("tensor", "2d") and model_axis not in mesh_axes:
            raise ValueError(
                f"sharding strategy {strategy!r} needs a "
                f"{model_axis!r} mesh axis; this mesh has "
                f"{tuple(mesh.axis_names)} — build it via "
                "plan_mesh(cfg) (train.py does)")
        self.axis_size = int(mesh_axes.get(fsdp_axis, 1))
        self.model_axis_size = int(mesh_axes.get(model_axis, 1))
        #: >1 only on a hierarchical-exchange mesh (plan_mesh emits
        #: the explicit "slice" axis); 1 everywhere else, so every
        #: existing mesh behaves exactly as before
        self.slice_axis_size = int(mesh_axes.get("slice", 1))
        self.rules = validate_rules(rules or DEFAULT_RULES[strategy])
        batch_axes = tuple(a for a in ("slice", "data", fsdp_axis,
                                       model_axis) if a in mesh_axes)
        #: batch rows split over EVERY mesh axis — each chip carries
        #: its own rows under every strategy (the strategies change
        #: the STORAGE layout, never the replica count), which is
        #: what keeps per-image compute — and therefore the loss
        #: stream — bit-identical to replicated; the spec
        #: _globalize_batch and profiling/predict.py both use
        self.batch_spec = (P(batch_axes[0]) if len(batch_axes) == 1
                           else P(batch_axes))

    @classmethod
    def from_config(cls, cfg, mesh: Mesh) -> "ShardingPlan":
        k = sharding_knobs(cfg)
        return cls(str(k["STRATEGY"]), mesh,
                   rules=tuple(k["RULES"] or ()),
                   exchange=str(k.get("EXCHANGE", "flat")))

    # -- specs / shardings --------------------------------------------

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def batch_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.batch_spec)

    def specs(self, tree):
        """PartitionSpec pytree for params / optimizer state / grads.
        Paths are matched as-is — momentum leaves carry the param path
        as a suffix, so one rule set claims both."""
        if self.strategy == "replicated":
            return jax.tree.map(lambda _: P(), tree)
        return match_partition_rules(self.rules, tree, self.mesh,
                                     fsdp_axis=self.fsdp_axis,
                                     model_axis=self.model_axis)

    def shardings(self, tree):
        """NamedSharding pytree (what jit/device_put consume)."""
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), self.specs(tree))

    def init_sharded(self, fn, *args, deterministic: bool = False):
        """Run ``fn(*args)`` jitted with the plan's shardings over its
        abstract output → ``(value, shardings)``.  State is BORN in
        its storage layout — no device ever holds a replicated copy
        it would immediately shard (the PR 6 idiom, parity-pinned).

        One exception: an RNG-bearing ``fn`` (the model init) under a
        model-axis plan (``tensor``/``2d``).  The repo's pinned RNG
        mode is non-partitionable threefry, and partitioning the init
        program over a mesh with a model axis > 1 changes the
        generated bits themselves (the partitioner re-lowers the RNG
        ops — reproduced as different weights on 15 leaves of the R50
        tree, which would break the tensor-vs-replicated loss pin at
        the first step).  Those init with fully REPLICATED
        out-shardings instead — zero partitioning freedom ⇒ canonical
        values by construction — then MOVE the shards onto the
        storage layout (device_put preserves values); the transient
        replicated copy exists only during init.  Pass
        ``deterministic=True`` for RNG-free builders (``tx.init`` —
        zeros shaped like the params) to keep even the model-axis
        plans born sharded: there are no random bits to perturb, and
        a replicated momentum tree at init is exactly the HBM the 2d
        memory plan exists to shed.

        ONE definition of the eval_shape→shardings→out_shardings
        idiom for trainer, predict and dryrun (three hand-rolled copies
        could drift and measure different layouts under the same plan
        name)."""
        sh = self.shardings(jax.eval_shape(fn, *args))
        if self.model_axis_size > 1 and not deterministic:
            repl = self.replicated()
            out = jax.jit(fn, out_shardings=jax.tree.map(
                lambda _: repl, sh))(*args)
            return jax.device_put(out, sh), sh
        return jax.jit(fn, out_shardings=sh)(*args), sh

    # -- inside-the-step constraints ----------------------------------

    def compute_params(self, params):
        """Gather the param shards just-in-time for compute — the
        matching input-side constraint of the storage sharding (a
        replication constraint XLA lowers to all-gathers near use:
        on the fsdp axis under ``fsdp``, the model axis under
        ``tensor``, both under ``2d``).  Identity under
        ``replicated`` — the program is unchanged."""
        if self.strategy == "replicated":
            return params
        return jax.lax.with_sharding_constraint(params,
                                                self.replicated())

    def exchange_specs(self, tree):
        """Intermediate PartitionSpec pytree of the hierarchical
        exchange: each gradient leaf sharded over EVERY in-slice mesh
        axis jointly (on the largest evenly-divisible dim) and
        replicated over ``slice``.  Constraining grads here first
        makes the partitioner reduce within each slice on ICI
        (reduce-scatter to 1/per-slice shards) and sum only those
        partials across slices on DCN; the follow-up constraint back
        to the storage layout is the in-slice all-gather.  Leaves
        with no dim divisible by the in-slice device product fall
        back to their storage spec (= the flat exchange for that
        leaf — correctness never depends on the staging)."""
        mesh_axes = dict(self.mesh.shape)
        inner = tuple(a for a in ("data", self.fsdp_axis,
                                  self.model_axis)
                      if int(mesh_axes.get(a, 1)) > 1)
        group = 1
        for a in inner:
            group *= int(mesh_axes[a])
        storage = self.specs(tree)
        if not inner or group <= 1:
            return storage

        def one(leaf, spec):
            shape = tuple(getattr(leaf, "shape", ()))
            if len(shape) == 0 or int(np.prod(shape)) == 1:
                return spec
            dim = _auto_axis_dim(shape, group)
            if dim is None:
                return spec
            entries: List[Optional[Any]] = [None] * len(shape)
            entries[dim] = inner if len(inner) > 1 else inner[0]
            return _spec_from_entries(entries)

        return jax.tree.map(one, tree, storage)

    def storage_grads(self, grads):
        """Constrain gradients back to the storage layout (XLA
        lowers the psum+slice to reduce-scatters on the storage
        axes), so the optimizer update runs on shards.  Identity
        under ``replicated``.

        Under ``exchange="hierarchical"`` on a multi-slice mesh the
        constraint is staged: first to :meth:`exchange_specs` (ICI
        reduce-scatter within each slice + DCN all-reduce of the
        1/per-slice partials), then to the storage layout (ICI
        all-gather back) — the gradient values are identical either
        way (constraints never change values, only layouts), so loss
        streams stay bit-compatible with the flat exchange."""
        if self.strategy == "replicated":
            return grads
        if self.exchange == "hierarchical" and self.slice_axis_size > 1:
            inter = jax.tree.map(
                lambda s: NamedSharding(self.mesh, s),
                self.exchange_specs(grads))
            grads = jax.lax.with_sharding_constraint(grads, inter)
        return jax.lax.with_sharding_constraint(grads,
                                                self.shardings(grads))

    # -- compile ------------------------------------------------------

    def jit(self, fn, **jit_kwargs):
        """``jax.jit`` behind the plan: the single place strategy
        executability is enforced (SNIPPETS.md [3]).  The trace runs
        under ``batch_partition`` so the Pallas ROIAlign dispatch can
        run once per batch shard — the SPMD partitioner refuses a bare
        Mosaic kernel on a multi-device mesh."""
        from eksml_tpu.ops.roi_align import batch_partition

        @functools.wraps(fn)
        def traced(*args, **kwargs):
            with batch_partition(self.mesh, self.batch_spec):
                return fn(*args, **kwargs)

        return jax.jit(traced, **jit_kwargs)

    # -- introspection ------------------------------------------------

    def explain(self, tree, title: str = "tree") -> str:
        """Which rule claimed each leaf, with per-device bytes — the
        dump that answers 'why is this leaf replicated?'."""
        mesh_axes = dict(self.mesh.shape)
        rows = []
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                tree)[0]:
            p = tree_path_str(path)
            if self.strategy == "replicated":
                spec, why = P(), "(strategy: replicated)"
            else:
                spec, why = _match_leaf(p, leaf, self.rules,
                                        mesh_axes, self.fsdp_axis,
                                        self.model_axis)
            shape = tuple(getattr(leaf, "shape", ()))
            div = 1
            for entry in spec:
                for a in ((entry,) if isinstance(entry, str)
                          else entry or ()):
                    div *= mesh_axes.get(a, 1)
            nbytes = (int(np.prod(shape))
                      * np.dtype(leaf.dtype).itemsize
                      if hasattr(leaf, "dtype") else 0)
            rows.append((p, str(spec), why, nbytes // max(1, div)))
        width = max((len(r[0]) for r in rows), default=4)
        out = [f"sharding plan '{self.strategy}' over mesh "
               f"{dict(self.mesh.shape)} — {title} "
               f"({len(rows)} leaves):"]
        for p, spec, why, b in rows:
            out.append(f"  {p:<{width}}  {spec:<24} "
                       f"{b / 2**20:8.2f} MiB/dev  <- {why}")
        return "\n".join(out)

    def describe(self) -> str:
        """One-line summary for logs and diagnostics."""
        # slices only show when the mesh actually carries the axis —
        # every single-slice plan keeps its historical string
        extra = (f", slices={self.slice_axis_size}, "
                 f"exchange={self.exchange}"
                 if self.slice_axis_size > 1 else "")
        if self.strategy == "fsdp":
            return (f"fsdp(axis={self.axis_size}, "
                    f"rules={len(self.rules)}{extra})")
        if self.strategy == "tensor":
            return (f"tensor(model={self.model_axis_size}, "
                    f"rules={len(self.rules)}{extra})")
        if self.strategy == "2d":
            return (f"2d(fsdp={self.axis_size}, "
                    f"model={self.model_axis_size}, "
                    f"rules={len(self.rules)}{extra})")
        return self.strategy
