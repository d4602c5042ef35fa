"""Native comm-shim tests: the C++ topology/collective-config layer
(parallel/native_src/topology.cc) must agree with the python inventory
(parallel/mesh.py) — the same dual-source risk the reference carried
between its CRD schema and the operator's --gpus-per-node arithmetic.
"""

import numpy as np
import pytest

from eksml_tpu.parallel.mesh import TOPOLOGIES, validate_topology
from eksml_tpu.parallel.native import get_lib, host_ring, topo_lookup


def test_native_lib_builds():
    assert get_lib() is not None, "C++ topology shim failed to build"


@pytest.mark.parametrize("name", sorted(TOPOLOGIES))
def test_lookup_agrees_with_python_inventory(name):
    from eksml_tpu.parallel.mesh import TOPOLOGY_GRIDS, topology_label

    info = topo_lookup(name)
    assert info is not None
    chips, hosts, mx, my = info
    assert (chips, hosts) == TOPOLOGIES[name]
    assert mx * my == chips  # physical grid covers the slice
    # grid (and thus the gke-tpu-topology label) agrees across the
    # C++ and python inventories
    assert (mx, my) == TOPOLOGY_GRIDS[name]
    assert topology_label(name) == f"{mx}x{my}"


def test_lookup_unknown():
    assert topo_lookup("v5e-7") is None


@pytest.mark.parametrize("name", sorted(TOPOLOGIES))
def test_host_ring_is_permutation(name):
    _, hosts = TOPOLOGIES[name]
    ring = host_ring(name)
    assert sorted(ring) == list(range(hosts))


def test_host_ring_snake_adjacency():
    # v5e-32: 8 hosts on a 2x4 grid; snake order keeps consecutive ring
    # members adjacent (|Δrow| + |Δcol| == 1), the minimum-hop property
    ring = host_ring("v5e-32")
    hx = 2
    coords = [(h // hx, h % hx) for h in ring]
    for a, b in zip(coords, coords[1:]):
        assert abs(a[0] - b[0]) + abs(a[1] - b[1]) == 1, (a, b)


def test_native_validate_matches_python():
    lib = get_lib()
    for chips in (1, 2, 4, 8, 32, 256):
        hosts = lib.topo_validate(chips, 4)
        assert hosts == validate_topology(num_chips=chips)[1]
    for chips in (0, 3, 6, -4):
        assert lib.topo_validate(chips, 4) == -1
        if chips > 0:
            with pytest.raises(ValueError):
                validate_topology(num_chips=chips)
