"""Parallel-layer tests on the 8-device virtual CPU mesh."""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from eksml_tpu.parallel import (batch_sharding, build_mesh, cross_host_sum,
                                param_fingerprint, replicated_sharding,
                                validate_topology)
from eksml_tpu.parallel import collectives
from eksml_tpu.parallel.collectives import assert_replicas_in_sync
from eksml_tpu.parallel.mesh import TOPOLOGIES


def test_validate_topology_names():
    assert validate_topology("v5e-32") == (32, 8)
    with pytest.raises(ValueError):
        validate_topology("v5e-7")
    with pytest.raises(ValueError):
        validate_topology("v5e-32", num_chips=16)


def test_validate_topology_multislice():
    """Multislice semantics (chart values contract): topology names
    EACH slice, num_chips is the TOTAL — validate_topology must scale
    by num_slices and reject a contradicting total."""
    assert validate_topology("v5e-16", num_chips=32,
                             num_slices=2) == (32, 8)
    assert validate_topology("v5e-32", num_slices=4) == (128, 32)
    with pytest.raises(ValueError, match="contradicts 2xv5e-16"):
        validate_topology("v5e-16", num_chips=16, num_slices=2)
    with pytest.raises(ValueError, match="must be >= 1"):
        validate_topology("v5e-16", num_slices=0)


def test_validate_topology_chip_counts():
    # ≙ the MPIJob CRD schema: gpus ∈ {1,2,4,8k}
    assert validate_topology(num_chips=1) == (1, 1)
    assert validate_topology(num_chips=8) == (8, 2)
    with pytest.raises(ValueError):
        validate_topology(num_chips=6)


def test_build_mesh_default_dp():
    mesh = build_mesh()
    assert mesh.devices.shape == (8, 1)
    assert mesh.axis_names == ("data", "model")


def test_build_mesh_device_subset_and_overflow():
    # a smaller explicit mesh takes a device subset (single-chip smoke
    # on a multi-device host); more devices than exist still raises
    m = build_mesh(mesh_shape=(4, 1))
    assert m.devices.shape == (4, 1)
    with pytest.raises(ValueError):
        build_mesh(mesh_shape=(16, 1))


def test_sharded_batch_and_replicated_params():
    mesh = build_mesh()
    x = jnp.arange(16.0).reshape(8, 2)
    xs = jax.device_put(x, batch_sharding(mesh))
    assert len(xs.sharding.device_set) == 8
    p = jax.device_put(jnp.ones((3, 3)), replicated_sharding(mesh))
    # replicated: every device holds the full value
    assert p.sharding.is_fully_replicated


def test_jit_inserts_allreduce_for_mean_over_sharded_batch():
    """The core DP contract: batch sharded over 'data', params
    replicated → XLA inserts the gradient allreduce (the NCCL-ring
    replacement) without any explicit collective in user code."""
    mesh = build_mesh()
    w = jax.device_put(jnp.ones((4,)), replicated_sharding(mesh))
    x = jax.device_put(jnp.arange(32.0).reshape(8, 4),
                       batch_sharding(mesh))

    def loss(w, x):
        return jnp.mean((x @ w) ** 2)

    g = jax.jit(jax.grad(loss))(w, x)
    # grad of a mean over the full batch == average of per-shard grads
    expected = jax.grad(loss)(jnp.ones((4,)), np.arange(32.0).reshape(8, 4))
    np.testing.assert_allclose(np.asarray(g), np.asarray(expected),
                               rtol=1e-5)
    assert g.sharding.is_fully_replicated


def test_cross_host_sum_single_process_identity():
    # 8 virtual devices but ONE process: host-local metrics sum over
    # processes, so the value must come back unchanged
    tree = {"a": 2.0, "b": jnp.asarray([1.0, 3.0])}
    out = cross_host_sum(tree)
    np.testing.assert_allclose(float(out["a"]), 2.0)
    np.testing.assert_allclose(np.asarray(out["b"]), [1.0, 3.0])


def test_replica_sync_check():
    mesh = build_mesh()
    params = {"w": jax.device_put(jnp.ones((4, 4)),
                                  replicated_sharding(mesh))}
    assert assert_replicas_in_sync(params, mesh)
    fp = param_fingerprint(params)
    fp2 = param_fingerprint({"w": jnp.ones((4, 4)) * 2})
    assert float(fp[0]) != float(fp2[0])
    # rng inclusion appends exactly-representable 16-bit key halves
    fp3 = param_fingerprint(params, rng=jax.random.PRNGKey(3))
    assert fp3.shape[0] > 1 and float(fp3[0]) == float(fp[0])


def _divergent_replicated(mesh, base, perturbed, bad_device=3):
    """Build a jax.Array that CLAIMS full replication but whose buffer
    on one device differs — the exact silent corruption SPMD trusts
    away (multi-process restore divergence, donation bug, bitflip)."""
    import numpy as _np

    sharding = replicated_sharding(mesh)
    bufs = []
    for i, d in enumerate(mesh.devices.flatten()):
        src = perturbed if i == bad_device else base
        bufs.append(jax.device_put(_np.asarray(src), d))
    return jax.make_array_from_single_device_arrays(
        base.shape, sharding, bufs)


def test_replica_sync_check_catches_injected_divergence():
    # SURVEY.md §5.2 negative path: one device's replica is perturbed;
    # the guard must raise, not silently pass
    mesh = build_mesh()
    base = np.ones((4, 4), np.float32)
    bad = base.copy()
    bad[2, 1] += 1e-2
    params = {"w": _divergent_replicated(mesh, base, bad)}
    with pytest.raises(AssertionError, match="diverged"):
        assert_replicas_in_sync(params, mesh)


def test_replica_sync_check_catches_permutation_divergence():
    # a within-leaf permutation preserves mean AND sum of squares — a
    # moment-only fingerprint would pass it; the Weyl position weights
    # must not
    mesh = build_mesh()
    base = np.arange(16, dtype=np.float32).reshape(4, 4)
    perm = base.reshape(-1)[::-1].reshape(4, 4).copy()
    params = {"w": _divergent_replicated(mesh, base, perm)}
    with pytest.raises(AssertionError, match="diverged"):
        assert_replicas_in_sync(params, mesh)


def test_replica_sync_check_catches_rng_divergence():
    # identical params, diverged PRNG key stream (the failure mode that
    # corrupts augmentation/dropout long before params drift)
    mesh = build_mesh()
    params = {"w": jax.device_put(jnp.ones((4, 4)),
                                  replicated_sharding(mesh))}
    k0 = np.asarray(jax.random.key_data(jax.random.PRNGKey(0)))
    k1 = np.asarray(jax.random.key_data(jax.random.PRNGKey(7)))
    raw = _divergent_replicated(mesh, k0, k1)
    rng = jax.random.wrap_key_data(raw)
    assert assert_replicas_in_sync(params, mesh)  # params alone: fine
    with pytest.raises(AssertionError, match="diverged"):
        assert_replicas_in_sync(params, mesh, rng=rng)


def test_v5e_inventory_consistent():
    for name, (chips, hosts) in TOPOLOGIES.items():
        assert chips == int(name.split("-")[1])
        assert chips == hosts * 4 or chips < 4


def test_v6e_generation_supported_end_to_end():
    """v6e (Trillium) slices validate, label, and compose Multislice
    the same way v5e does — both generations use 4-chip hosts and the
    same 2D-torus grids (machine type is the only infra difference)."""
    from eksml_tpu.parallel.mesh import topology_label, validate_topology

    assert validate_topology("v6e-32") == (32, 8)
    assert topology_label("v6e-32") == "4x8"
    assert validate_topology("v6e-16", num_slices=2) == (32, 8)
    # both generations present and chip-for-chip symmetric
    v5e = {n for n in TOPOLOGIES if n.startswith("v5e-")}
    v6e = {n for n in TOPOLOGIES if n.startswith("v6e-")}
    assert {n.replace("v5e-", "") for n in v5e} == \
        {n.replace("v6e-", "") for n in v6e}


# ---- multi-slice (DCN) mesh --------------------------------------------


def test_multislice_emulated_mesh_slice_major_order():
    """num_slices=2 on 8 virtual devices: the data axis must decompose
    into contiguous whole-slice blocks (slice-major order), so model/TP
    axes can never straddle a DCN boundary."""
    from eksml_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(num_slices=2)
    assert mesh.devices.shape == (8, 1)
    devs = list(mesh.devices.ravel())
    assert devs == jax.devices()  # contiguous equal blocks, in order


def test_multislice_mesh_validation():
    from eksml_tpu.parallel.mesh import build_mesh

    with pytest.raises(ValueError, match="do not split"):
        build_mesh(num_slices=3)  # 8 % 3
    with pytest.raises(ValueError, match="cover all"):
        build_mesh(mesh_shape=(4, 1), num_slices=2)  # subset mesh
    with pytest.raises(ValueError, match="data axis"):
        build_mesh(mesh_shape=(2, 4), num_slices=4,
                   axis_names=("data", "model"))


def test_multislice_grad_matches_single_slice():
    """The DP contract is unchanged across slices: same gradient as the
    single-mesh layout, params stay replicated — XLA decides which hops
    ride ICI vs DCN; numerics must not change."""
    from eksml_tpu.parallel.mesh import build_mesh

    def loss(w, x):
        return jnp.mean((x @ w) ** 2)

    x_host = np.arange(32.0).reshape(8, 4).astype(np.float32)
    grads = []
    for n_slices in (1, 2, 4):
        mesh = build_mesh(num_slices=n_slices)
        w = jax.device_put(jnp.ones((4,)), replicated_sharding(mesh))
        x = jax.device_put(jnp.asarray(x_host), batch_sharding(mesh))
        g = jax.jit(jax.grad(loss))(w, x)
        assert g.sharding.is_fully_replicated
        grads.append(np.asarray(g))
    np.testing.assert_allclose(grads[0], grads[1], rtol=1e-6)
    np.testing.assert_allclose(grads[0], grads[2], rtol=1e-6)


def test_slice_groups_hardware_attr():
    """Devices exposing slice_index are grouped and ordered by it;
    platforms without the attribute return None (single slice)."""
    from eksml_tpu.parallel.mesh import slice_groups

    class Dev:
        def __init__(self, i, s):
            self.id, self.slice_index = i, s

        def __repr__(self):
            return f"Dev({self.id},s{self.slice_index})"

    devs = [Dev(0, 1), Dev(1, 0), Dev(2, 1), Dev(3, 0)]
    groups = slice_groups(devs)
    assert list(groups) == [0, 1]
    assert [d.id for d in groups[0]] == [1, 3]
    assert [d.id for d in groups[1]] == [0, 2]
    assert slice_groups(jax.devices()) is None  # CPU: no slice_index
    assert slice_groups([Dev(0, 0), Dev(1, 0)]) is None  # single slice


def test_dryrun_device_selection_is_slice_aware():
    """__graft_entry__.dryrun_multichip must never hand build_mesh a
    subset that straddles slices unevenly (4+2 of a 2×4 deployment has
    no valid mesh): single-slice subsets when n fits in one slice,
    whole slices when n divides into them, a clear error otherwise,
    and the synthetic 2-split only for sliceless (CPU) devices."""
    from __graft_entry__ import _select_dryrun_devices

    class Dev:
        def __init__(self, i, s):
            self.id, self.slice_index = i, s

    hw = [Dev(i, i // 4) for i in range(8)]  # 2 slices × 4 chips

    devs, ns = _select_dryrun_devices(hw, 3)       # fits slice 0
    assert [d.id for d in devs] == [0, 1, 2] and ns == 1
    devs, ns = _select_dryrun_devices(hw, 8)       # both whole slices
    assert [d.id for d in devs] == list(range(8)) and ns == 1
    with pytest.raises(ValueError, match="no valid multi-slice mesh"):
        _select_dryrun_devices(hw, 6)              # 4+2 straddle

    cpu = [object() for _ in range(8)]             # no slice_index
    devs, ns = _select_dryrun_devices(cpu, 8)
    assert len(devs) == 8 and ns == 2              # synthetic split
    devs, ns = _select_dryrun_devices(cpu, 5)
    assert len(devs) == 5 and ns == 1


def test_multislice_hardware_groups_validation():
    """Hardware-path guards (stub devices carrying slice_index): the
    validation runs before Mesh construction, so error paths are
    testable without real multi-slice hardware."""
    from eksml_tpu.parallel.mesh import build_mesh

    class Dev:
        def __init__(self, i, s):
            self.id, self.slice_index = i, s

    # uneven groups (partial subset of slice 1 passed): must refuse
    uneven = [Dev(0, 0), Dev(1, 0), Dev(2, 1)]
    with pytest.raises(ValueError, match="unequal device counts"):
        build_mesh(mesh_shape=(3, 1), devices=uneven)

    even = [Dev(0, 0), Dev(1, 0), Dev(2, 1), Dev(3, 1)]
    # subset mesh must fit inside one slice and stay single-slice
    with pytest.raises(ValueError, match="fit one slice"):
        build_mesh(mesh_shape=(3, 1), devices=even)
    with pytest.raises(ValueError, match="fit one slice"):
        build_mesh(mesh_shape=(2, 1), devices=even, num_slices=2)
    # num_slices contradicting the hardware count
    with pytest.raises(ValueError, match="contradicts hardware"):
        build_mesh(mesh_shape=(4, 1), devices=even, num_slices=3)


def test_warm_mesh_collectives_runs_mesh_allreduce(monkeypatch):
    """The init-time channel warm-up (Horovod-style first allreduce,
    added after the multihost e2e flaked on Gloo's 30s lazy-connect
    window) must execute a real all-reduce over the SAME mesh the
    trainer uses — a different communicator (process_allgather) does
    not establish the training clique.  Single-process it is a no-op;
    force the multi-process branch and check the sharded sum."""
    from eksml_tpu.parallel import build_mesh, collectives

    calls = []
    mesh = build_mesh((8, 1), ("data", "model"))

    # no-op when single-process: device_put must never run
    monkeypatch.setattr(collectives.jax, "device_put",
                        lambda *a, **k: calls.append(1))
    collectives.warm_mesh_collectives(mesh)
    assert calls == []
    monkeypatch.undo()

    # multi-process branch: the all-reduce runs on this mesh and the
    # result equals the device count (executed here on 8 local CPU
    # devices — same program, local transport)
    monkeypatch.setattr(collectives.jax, "process_count", lambda: 2)
    collectives.warm_mesh_collectives(mesh)  # raises on failure


def test_topology_manifest_round_trip_carries_slice_count():
    """The checkpoint topology manifest must carry num_slices through
    a JSON round-trip: a checkpoint saved at 2 slices restored at 1
    slice is a resharded restore, not a trusted-layout one — losing
    the field would alias the two."""
    import json

    from eksml_tpu.parallel.mesh import build_mesh
    from eksml_tpu.parallel.sharding import ShardingPlan
    from eksml_tpu.parallel.topology import (compatible,
                                             current_topology, diff,
                                             normalize)

    mesh = build_mesh((2, 1, 2, 2), ("slice", "data", "fsdp", "model"),
                      num_slices=2)
    plan = ShardingPlan("2d", mesh, exchange="hierarchical")
    topo = current_topology(mesh, plan, num_slices=2)
    assert topo["num_slices"] == 2
    assert topo["mesh_axes"] == ["slice", "data", "fsdp", "model"]
    # JSON round-trip (what the checkpoint manifest actually does)
    loaded = normalize(json.loads(json.dumps(topo)))
    assert compatible(topo, loaded) and compatible(loaded, topo)
    # a single-slice layout of the same shard widths is NOT the same
    # topology — num_slices (and the mesh axes) must break equality
    flat = build_mesh((2, 2, 2), ("data", "fsdp", "model"))
    topo1 = current_topology(flat, ShardingPlan("2d", flat),
                             num_slices=1)
    assert not compatible(loaded, topo1)
    assert "num_slices" in diff(loaded, topo1)


# ---- nothing edits LIBTPU_INIT_ARGS or starts a child that would
# ---- need the chip


def _repo_sources():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = [os.path.join(root, "__graft_entry__.py")]
    for base in ("eksml_tpu", "tools"):
        for d, _, files in os.walk(os.path.join(root, base)):
            paths += [os.path.join(d, f) for f in files
                      if f.endswith(".py")]
    return paths


def test_collective_flag_absent_from_libtpu_init_args():
    """libtpu 0.0.34 has no ``xla_tpu_all_reduce_combine_threshold_
    bytes`` and EXITS on the unknown flag, and it reads
    ``LIBTPU_INIT_ARGS`` once, at backend init.  So the flag is not set
    at all: no library or tool source writes that variable."""
    for path in _repo_sources():
        with open(path) as f:
            src = f.read()
        assert 'environ["LIBTPU_INIT_ARGS"] =' not in src, path
    assert not hasattr(collectives, "set_xla_collective_flags")


def test_collective_flag_layer_starts_no_child():
    """A process that has initialised JAX owns the chip; a child that
    needs it fails or hangs.  The collective layer (and the kernel
    gate) therefore never start one."""
    import inspect

    from eksml_tpu.ops.pallas import roi_align_kernel

    for mod in (collectives, roi_align_kernel):
        src = inspect.getsource(mod)
        assert "subprocess" not in src, mod.__name__
        assert "threading" not in src, mod.__name__


def test_collective_flag_operator_value_survives_trainer_init(
        monkeypatch, fresh_config, tmp_path):
    """An operator-set LIBTPU_INIT_ARGS (the charts' pod env — placed
    before the first backend call, where it can take effect) passes
    through ``Trainer.__init__`` untouched."""
    from eksml_tpu.config import SMOKE_OVERRIDES, finalize_configs
    from eksml_tpu.train import Trainer

    monkeypatch.setenv("LIBTPU_INIT_ARGS", "--xla_keep_me=1")
    fresh_config.update_args(list(SMOKE_OVERRIDES)
                             + ["TPU.MESH_SHAPE=(1,1)"])
    cfg = finalize_configs(is_training=True)
    trainer = Trainer(cfg, str(tmp_path), write_metrics=False)
    try:
        assert os.environ["LIBTPU_INIT_ARGS"] == "--xla_keep_me=1"
    finally:
        trainer.ckpt.close()
