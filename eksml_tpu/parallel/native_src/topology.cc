// Native comm-layer shim: TPU slice topology introspection + collective
// configuration.  This owns the role the reference delegated to native
// code — NCCL's topology/ring discovery (tuned via NCCL_MIN_NRINGS /
// NCCL_SOCKET_IFNAME, reference charts/maskrcnn/values.yaml:26-28) and
// Horovod's C++ fusion buffer sizing (HOROVOD_FUSION_THRESHOLD,
// values.yaml:25) — re-expressed for ICI/DCN: slice geometry math,
// per-host chip coordinates and DCN ring ordering across hosts.
//
// C ABI + ctypes (eksml_tpu/parallel/native.py); build:
//   make -C eksml_tpu/parallel/native_src

#include <cstdint>
#include <cstring>

namespace {

struct V5eSlice {
  const char* name;
  int32_t chips;
  int32_t hosts;
  int32_t mesh_x;  // physical chip grid
  int32_t mesh_y;
};

// v5e + v6e (Trillium) slice inventories (chips = hosts × 4 above 4
// chips; v6e's ct6e-standard-4t hosts carry 4 chips like v5e's); the
// physical grid determines ICI neighbor distance.  Mirrors
// mesh.py TOPOLOGIES / TOPOLOGY_GRIDS (tests/test_native_topology.py
// asserts the two inventories agree name-for-name).
constexpr V5eSlice kSlices[] = {
    {"v5e-1", 1, 1, 1, 1},     {"v5e-4", 4, 1, 2, 2},
    {"v5e-8", 8, 2, 2, 4},     {"v5e-16", 16, 4, 4, 4},
    {"v5e-32", 32, 8, 4, 8},   {"v5e-64", 64, 16, 8, 8},
    {"v5e-128", 128, 32, 8, 16}, {"v5e-256", 256, 64, 16, 16},
    {"v6e-1", 1, 1, 1, 1},     {"v6e-4", 4, 1, 2, 2},
    {"v6e-8", 8, 2, 2, 4},     {"v6e-16", 16, 4, 4, 4},
    {"v6e-32", 32, 8, 4, 8},   {"v6e-64", 64, 16, 8, 8},
    {"v6e-128", 128, 32, 8, 16}, {"v6e-256", 256, 64, 16, 16},
};
constexpr int kNumSlices = sizeof(kSlices) / sizeof(kSlices[0]);

const V5eSlice* find(const char* name) {
  for (int i = 0; i < kNumSlices; ++i)
    if (std::strcmp(kSlices[i].name, name) == 0) return &kSlices[i];
  return nullptr;
}

}  // namespace

extern "C" {

// name → {chips, hosts, mesh_x, mesh_y}; returns 0 on success,
// -1 for unknown topology.
int32_t topo_lookup(const char* name, int32_t* chips, int32_t* hosts,
                    int32_t* mesh_x, int32_t* mesh_y) {
  const V5eSlice* s = find(name);
  if (!s) return -1;
  *chips = s->chips;
  *hosts = s->hosts;
  *mesh_x = s->mesh_x;
  *mesh_y = s->mesh_y;
  return 0;
}

// The CRD-schema check (reference charts/mpijob/templates/
// mpijob.yaml:21-49: gpus ∈ {1,2,4} ∪ 8ℤ) for v5e: 1, 2, or a
// multiple of chips_per_host.  Returns hosts, or -1 when invalid.
int32_t topo_validate(int32_t chips, int32_t chips_per_host) {
  if (chips <= 0) return -1;
  if (chips <= 2) return 1;
  if (chips_per_host <= 0 || chips % chips_per_host != 0) return -1;
  return chips / chips_per_host;
}

// Chip coordinate in the physical grid (row-major over mesh_x).
int32_t topo_chip_coords(const char* name, int32_t chip_id, int32_t* x,
                         int32_t* y) {
  const V5eSlice* s = find(name);
  if (!s || chip_id < 0 || chip_id >= s->chips) return -1;
  *x = chip_id % s->mesh_x;
  *y = chip_id / s->mesh_x;
  return 0;
}

// DCN ring order across hosts: snake order over the host grid so
// consecutive ring neighbors are physically adjacent (minimum-hop DCN
// ring — the role NCCL's ring builder played across nodes).
// out_order must hold `hosts` entries.
int32_t topo_host_ring(const char* name, int32_t* out_order) {
  const V5eSlice* s = find(name);
  if (!s) return -1;
  // hosts tile the chip grid in 2x2 blocks (4 chips/host) above 1 host
  int32_t hx = s->mesh_x >= 2 ? s->mesh_x / 2 : 1;
  int32_t hy = s->hosts / hx;
  if (hy <= 0) hy = 1;
  int32_t n = 0;
  for (int32_t row = 0; row < hy; ++row) {
    if (row % 2 == 0) {
      for (int32_t col = 0; col < hx; ++col) out_order[n++] = row * hx + col;
    } else {
      for (int32_t col = hx - 1; col >= 0; --col)
        out_order[n++] = row * hx + col;
    }
  }
  return n;
}

}  // extern "C"
