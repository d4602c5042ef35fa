"""Device-mesh construction + slice-topology validation.

The mesh is the TPU-native replacement for the reference's
``gpus`` / ``gpus_per_node`` arithmetic: the MPIJob CRD validated
``gpus ∈ {1,2,4} ∪ 8ℤ`` via an OpenAPI schema
(charts/mpijob/templates/mpijob.yaml:16-50) and the mpi-operator split
jobs with ``--gpus-per-node 8``
(charts/maskrcnn/charts/mpi-operator/templates/mpi-operator.yaml:126-128).
Here :func:`validate_topology` is that schema check re-expressed for
v5e slices, and :func:`build_mesh` produces the
``jax.sharding.Mesh`` all training code shards over.

Data parallelism is the parity strategy (SURVEY.md §2c); the mesh
always carries a ``model`` axis (size 1 by default), and the
``tensor``/``2d`` sharding plans (parallel/sharding.py) size it >1
to shard the FPN/head weights' output features across chips.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# v5e slice inventory: topology name → (chips, hosts).  A v5e host
# carries 4 chips (the analogue of "8 GPUs per p3.16xlarge node",
# eks-cluster/terraform/.../aws-eks-cluster-and-nodegroup.tf:75-79).
V5E_TOPOLOGIES = {
    "v5e-1": (1, 1),
    "v5e-4": (4, 1),
    "v5e-8": (8, 2),
    "v5e-16": (16, 4),
    "v5e-32": (32, 8),
    "v5e-64": (64, 16),
    "v5e-128": (128, 32),
    "v5e-256": (256, 64),
}

# Physical chip grid per slice (mirrors the C++ inventory,
# native_src/topology.cc kSlices).  ``{x}x{y}`` is exactly the
# ``cloud.google.com/gke-tpu-topology`` node label GKE puts on v5e
# podslice nodes — the one the workload chart's nodeSelector must
# match, or pods sit Pending forever.  Single source of truth for the
# chart helper map, the terraform default and the values schema
# (asserted against all three in tests/test_orchestration.py).
V5E_TOPOLOGY_GRIDS = {
    "v5e-1": (1, 1),
    "v5e-4": (2, 2),
    "v5e-8": (2, 4),
    "v5e-16": (4, 4),
    "v5e-32": (4, 8),
    "v5e-64": (8, 8),
    "v5e-128": (8, 16),
    "v5e-256": (16, 16),
}

# v6e (Trillium) slice inventory: same 2D-torus slice shapes and
# 4-chip hosts as v5e (machine type ct6e-standard-4t — the terraform
# tpu_machine_type for a v6e pool), ~4.7x the bf16 peak per chip
# (benchmark/peaks.json).  Topology names follow the same
# ``cloud.google.com/gke-tpu-topology`` label scheme.
V6E_TOPOLOGIES = {name.replace("v5e-", "v6e-"): ch
                  for name, ch in V5E_TOPOLOGIES.items()}
V6E_TOPOLOGY_GRIDS = {name.replace("v5e-", "v6e-"): grid
                      for name, grid in V5E_TOPOLOGY_GRIDS.items()}

# canonical inventory across generations — validate_topology,
# topology_label, the chart enum and the C++ shim all track THIS
TOPOLOGIES = {**V5E_TOPOLOGIES, **V6E_TOPOLOGIES}
TOPOLOGY_GRIDS = {**V5E_TOPOLOGY_GRIDS, **V6E_TOPOLOGY_GRIDS}


def divisors(n: int) -> list:
    """Valid axis sizes for ``n`` devices — the payload of every
    "axis size does not divide" error (ONE definition for build_mesh
    and sharding.plan_mesh, so the suggested sizes can never drift
    from the check that rejects them)."""
    return [d for d in range(1, n + 1) if n % d == 0]


def topology_label(topology: str) -> str:
    """GKE ``gke-tpu-topology`` node-label string for a slice name
    (``v5e-32`` → ``"4x8"``)."""
    if topology not in TOPOLOGY_GRIDS:
        raise ValueError(
            f"unknown TPU topology {topology!r}; valid: "
            f"{sorted(TOPOLOGY_GRIDS)}")
    x, y = TOPOLOGY_GRIDS[topology]
    return f"{x}x{y}"


def validate_topology(topology: str = "", num_chips: Optional[int] = None,
                      chips_per_host: int = 4,
                      num_slices: int = 1) -> Tuple[int, int]:
    """Validate a requested slice the way the MPIJob CRD schema
    validated ``gpus`` — fail before any pod/job is created.

    Multislice (``num_slices > 1``): ``topology`` names EACH slice and
    ``num_chips`` is the TOTAL across slices (the chart's values
    semantics), so the expected total is ``slice_chips · num_slices``.

    Returns ``(num_chips, num_hosts)`` — totals across all slices.
    """
    if num_slices < 1:
        raise ValueError(f"num_slices={num_slices} must be >= 1")
    if topology:
        if topology not in TOPOLOGIES:
            raise ValueError(
                f"unknown TPU topology {topology!r}; valid: "
                f"{sorted(TOPOLOGIES)}")
        chips, hosts = TOPOLOGIES[topology]
        chips, hosts = chips * num_slices, hosts * num_slices
        if num_chips not in (None, chips):
            raise ValueError(
                f"TRAIN.NUM_CHIPS={num_chips} contradicts "
                f"{num_slices}x{topology} ({chips} chips total)")
        return chips, hosts
    if num_chips is None:
        num_chips = len(jax.devices())
    valid = num_chips in (1, 2) or (
        num_chips % chips_per_host == 0 and num_chips > 0)
    if not valid:
        raise ValueError(
            f"num_chips={num_chips} is not a valid slice: need 1, 2, "
            f"or a multiple of chips_per_host={chips_per_host}")
    hosts = max(1, num_chips // chips_per_host)
    return num_chips, hosts


def slice_groups(devices) -> Optional[dict]:
    """Group devices by hardware slice (``device.slice_index``, present
    on multi-slice TPU deployments).  Returns ``{slice_index: [device]}``
    ordered by slice index, or ``None`` when the platform exposes no
    slice information (single slice, CPU, virtual devices)."""
    groups: dict = {}
    for d in devices:
        idx = getattr(d, "slice_index", None)
        if idx is None:
            return None
        groups.setdefault(idx, []).append(d)
    if len(groups) <= 1:
        return None
    return {k: groups[k] for k in sorted(groups)}


def build_mesh(mesh_shape: Sequence[int] = (),
               axis_names: Sequence[str] = ("data", "model"),
               devices=None, num_slices: int = 1) -> Mesh:
    """Build the training mesh.

    Default shape: all devices on the ``data`` axis, ``model`` axis 1 —
    the DP layout that matches the reference's only strategy
    (SURVEY.md §2c), with the model axis reserved for TP growth.

    Multi-slice (``num_slices > 1`` or hardware ``slice_index``
    present): devices are ordered SLICE-MAJOR before the reshape, so
    the leading (data) axis decomposes as [slice0 | slice1 | ...] and
    the trailing axes (model/TP) always stay inside one slice.  Batch
    sharding and the gradient psum are unchanged — XLA lowers the
    all-reduce over the data axis hierarchically: reduce-scatter /
    all-gather on ICI within each slice, one small all-reduce over
    **DCN** between slices (SURVEY.md §5.8 — this is the NCCL
    inter-node TCP ring's TPU-native replacement; the reference's
    2-node × 8-GPU layout maps to 2 slices of one v5e host each).
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    axis_names = tuple(axis_names)
    mesh_shape = tuple(int(s) for s in mesh_shape)
    if not mesh_shape:
        mesh_shape = (n,) + (1,) * (len(axis_names) - 1)
    # Validate the requested axes against the real device count HERE,
    # with errors naming the knobs — a bad model/fsdp axis size used
    # to surface as a reshape/shape error deep inside jit.
    if len(mesh_shape) != len(axis_names):
        raise ValueError(
            f"mesh shape {mesh_shape} has {len(mesh_shape)} entries "
            f"for {len(axis_names)} axes {axis_names} — "
            "TPU.MESH_SHAPE and TPU.MESH_AXES must be the same "
            "length (one size per axis)")
    if any(s < 1 for s in mesh_shape):
        raise ValueError(
            f"mesh shape {mesh_shape}: every axis size must be >= 1 "
            f"(axes {axis_names}); use 1 for an unused axis")
    need = int(np.prod(mesh_shape))
    groups = slice_groups(devices)
    if groups is not None:
        # always order slice-major so any subset is slice-contiguous
        devices = [d for g in groups.values() for d in g]
        if need < n:
            # subset smoke mesh on multi-slice hardware: keep it inside
            # ONE slice (the first); a straddling subset would put a
            # DCN hop inside what the mesh labels a single slice
            first = len(next(iter(groups.values())))
            if num_slices > 1 or need > first:
                raise ValueError(
                    f"subset mesh ({need} of {n} devices) on "
                    f"multi-slice hardware must fit one slice "
                    f"({first} devices) and be single-slice")
            num_slices = 1
        else:
            if num_slices not in (1, len(groups)):
                raise ValueError(
                    f"num_slices={num_slices} contradicts hardware "
                    f"slice count {len(groups)}")
            sizes = {len(g) for g in groups.values()}
            if len(sizes) != 1:
                # uneven groups (a partial device subset was passed):
                # slice boundaries would not line up with the data axis
                raise ValueError(
                    f"slices contribute unequal device counts "
                    f"{sorted(len(g) for g in groups.values())}; pass "
                    f"whole slices")
            num_slices = len(groups)
    elif num_slices > 1:
        # no hardware slice info (CPU simulation / single-slice
        # backend): emulate with equal contiguous blocks so multi-slice
        # code paths are testable on a virtual-device mesh
        if n % num_slices:
            raise ValueError(
                f"{n} devices do not split into num_slices={num_slices}")
    if num_slices > 1:
        # slice-major ordering only lines up with the mesh when the
        # data axis splits evenly into whole slices and every device
        # participates (a subset mesh could straddle a slice boundary)
        if need != n:
            raise ValueError(
                f"multi-slice mesh must cover all {n} devices "
                f"(shape {tuple(mesh_shape)} covers {need})")
        if axis_names[0] == "slice":
            # explicit slice axis (the hierarchical-exchange layout,
            # sharding.plan_mesh): the leading axis IS the slice
            # decomposition, so it must equal the slice count exactly
            # — slice-major device order then puts each mesh slice on
            # one hardware slice and every trailing axis (data/fsdp/
            # model) stays inside it by construction
            if mesh_shape[0] != num_slices:
                raise ValueError(
                    f"slice axis size {mesh_shape[0]} must equal the "
                    f"slice count ({num_slices}): the 'slice' mesh "
                    f"axis is the DCN decomposition itself and cannot "
                    f"split or merge hardware slices")
        elif mesh_shape[0] % num_slices:
            # this is also what keeps the trailing (fsdp/model) axes
            # INSIDE one slice: with slice-major device order, each
            # data index owns one contiguous block of trailing-axes
            # devices, and data % slices == 0 ⇔ that block never
            # straddles a slice boundary (no DCN hop inside an
            # fsdp/TP group)
            raise ValueError(
                f"data axis {mesh_shape[0]} does not split over "
                f"{num_slices} slices; the trailing axes "
                f"{tuple(axis_names[1:])} (sizes {mesh_shape[1:]}) "
                "must divide each slice's device count")
    if need > n and "model" in axis_names:
        # the model-axis analogue of the fsdp divisibility error
        # below: when an OVERSIZE mesh's model axis is the size that
        # cannot divide the per-slice device count, name that knob
        # and spell out the valid sizes instead of the generic
        # product message.  Gated on need > n deliberately — a
        # covering mesh's model size always divides the product, and
        # a SUBSET mesh (need < n, the single-chip smoke path) is
        # legal whatever its model width, so only the oversize path
        # ever implicates the model knob
        m = mesh_shape[axis_names.index("model")]
        per_slice = (n // num_slices
                     if num_slices > 1 and n % num_slices == 0 else n)
        if m > 1 and per_slice % m:
            raise ValueError(
                f"model axis size {m} does not divide the per-slice "
                f"device count ({per_slice}) — "
                f"TRAIN.SHARDING.MODEL_AXIS_SIZE must be one of "
                f"{divisors(per_slice)}")
    if need > n:
        raise ValueError(
            f"mesh shape {tuple(mesh_shape)} over axes {axis_names} "
            f"needs {need} devices, have {n} — the product of the "
            "axis sizes (TPU.MESH_SHAPE / "
            "TRAIN.SHARDING.FSDP_AXIS_SIZE) must not exceed the "
            "device count")
    if need < n and jax.process_count() > 1:
        # a subset mesh would leave some hosts' devices unrepresented —
        # their jit calls fail or hang at the first collective
        raise ValueError(
            f"mesh shape {tuple(mesh_shape)} covers {need} of {n} "
            f"devices; subset meshes are only valid single-process")
    # an explicit smaller mesh uses a device subset (single-chip smoke
    # runs on a multi-device host)
    dev_array = np.asarray(devices[:need]).reshape(mesh_shape)
    return Mesh(dev_array, tuple(axis_names))


def batch_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Sharding for per-step batches: leading dim split over ``data``."""
    return NamedSharding(mesh, P(axis))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for parameters/optimizer state: full replica per chip —
    the reference's layout (one Horovod model replica per GPU,
    SURVEY.md §2c 'full replica per GPU')."""
    return NamedSharding(mesh, P())
