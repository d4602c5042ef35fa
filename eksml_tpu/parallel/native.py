"""ctypes bridge to the native comm-layer shim (topology.cc).

The reference's comm layer was native (NCCL ring construction, Horovod
fusion buffering — SURVEY.md §5.8); here the compiled surface owns
slice geometry and DCN ring ordering, with pure-python fallbacks so
nothing requires the build.
"""

from __future__ import annotations

import ctypes
import logging
import os
from typing import List, Optional, Tuple

from eksml_tpu._native import NativeLib

log = logging.getLogger(__name__)


def _declare(lib: ctypes.CDLL) -> None:
    i32p = ctypes.POINTER(ctypes.c_int32)
    lib.topo_lookup.argtypes = [ctypes.c_char_p, i32p, i32p, i32p, i32p]
    lib.topo_lookup.restype = ctypes.c_int32
    lib.topo_validate.argtypes = [ctypes.c_int32, ctypes.c_int32]
    lib.topo_validate.restype = ctypes.c_int32
    lib.topo_chip_coords.argtypes = [ctypes.c_char_p, ctypes.c_int32,
                                     i32p, i32p]
    lib.topo_chip_coords.restype = ctypes.c_int32
    lib.topo_host_ring.argtypes = [ctypes.c_char_p, i32p]
    lib.topo_host_ring.restype = ctypes.c_int32


_LIB = NativeLib(
    os.path.join(os.path.dirname(__file__), "_topology.so"),
    os.path.join(os.path.dirname(__file__), "native_src"),
    "topology.cc", _declare)


def get_lib() -> Optional[ctypes.CDLL]:
    return _LIB.get()


def topo_lookup(name: str) -> Optional[Tuple[int, int, int, int]]:
    """(chips, hosts, mesh_x, mesh_y) for a slice name, native path."""
    lib = get_lib()
    if lib is None:
        return None
    vals = [ctypes.c_int32() for _ in range(4)]
    rc = lib.topo_lookup(name.encode(), *[ctypes.byref(v) for v in vals])
    if rc != 0:
        return None
    return tuple(v.value for v in vals)


def host_ring(name: str) -> Optional[List[int]]:
    """Snake-order host ring for minimum-hop DCN collectives."""
    lib = get_lib()
    if lib is None:
        return _host_ring_py(name)
    info = topo_lookup(name)
    if info is None:
        return None
    hosts = info[1]
    buf = (ctypes.c_int32 * hosts)()
    n = lib.topo_host_ring(name.encode(), buf)
    if n <= 0:
        return None
    return list(buf[:n])


def _host_ring_py(name: str) -> Optional[List[int]]:
    from eksml_tpu.parallel.mesh import TOPOLOGIES, TOPOLOGY_GRIDS

    if name not in TOPOLOGIES:
        return None
    _, hosts = TOPOLOGIES[name]
    # host grid: hosts tile the chip grid 2 columns (of chips) wide
    hx = max(TOPOLOGY_GRIDS[name][0] // 2, 1)
    hy = max(hosts // hx, 1)
    order = []
    for row in range(hy):
        cols = range(hx) if row % 2 == 0 else range(hx - 1, -1, -1)
        order += [row * hx + c for c in cols]
    return order


