"""Pallas ROIAlign kernel vs the XLA reference formulation.

Runs in interpret mode (no TPU in the test environment, SURVEY.md §4);
the kernel's math — assigned-level tile DMA + separable two-tap
bilinear matmuls — must agree with ops.roi_align's gather formulation
everywhere the tile covers the ROI.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from eksml_tpu.ops.roi_align import batched_multilevel_roi_align
from eksml_tpu.ops.pallas.roi_align_kernel import (
    TILE, pallas_batched_multilevel_roi_align)

STRIDES = (4, 8, 16, 32)


def _feats(rng, b=1, img=128, c=32):
    return tuple(
        jnp.asarray(rng.randn(b, img // s, img // s, c).astype(np.float32))
        for s in STRIDES)


def _rois(rng, b, n, img=128):
    out = []
    for _ in range(b):
        ctr = rng.rand(n, 2) * img * 0.5 + img * 0.25
        size = np.exp(rng.rand(n) * np.log(20)) * 4
        ar = np.exp(rng.randn(n) * 0.3)
        w, h = size * ar, size / ar
        x1 = np.clip(ctr[:, 0] - w / 2, 1, img - 2)
        y1 = np.clip(ctr[:, 1] - h / 2, 1, img - 2)
        x2 = np.clip(x1 + w, None, img - 2)
        y2 = np.clip(y1 + h, None, img - 2)
        out.append(np.stack([x1, y1, x2, y2], 1))
    return jnp.asarray(np.stack(out).astype(np.float32))


def test_matches_xla_reference():
    rng = np.random.RandomState(0)
    feats = _feats(rng, b=2)
    rois = _rois(rng, 2, 12)
    ref = batched_multilevel_roi_align(feats, rois, STRIDES, 7)
    pal = pallas_batched_multilevel_roi_align(feats, rois, STRIDES, 7, 2,
                                              2, True)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               atol=1e-4)


def test_mask_head_resolution():
    rng = np.random.RandomState(1)
    feats = _feats(rng)
    rois = _rois(rng, 1, 6)
    ref = batched_multilevel_roi_align(feats, rois, STRIDES, 14)
    pal = pallas_batched_multilevel_roi_align(feats, rois, STRIDES, 14, 2,
                                              2, True)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               atol=1e-4)


def test_border_roi_zero_padding():
    # ROI hugging the image corner: zero-padding outside the image must
    # match the XLA formulation's out-of-range-taps-are-zero rule
    rng = np.random.RandomState(2)
    feats = _feats(rng)
    rois = jnp.asarray([[[0.0, 0.0, 12.0, 9.0]]], jnp.float32)
    ref = batched_multilevel_roi_align(feats, rois, STRIDES, 7)
    pal = pallas_batched_multilevel_roi_align(feats, rois, STRIDES, 7, 2,
                                              2, True)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               atol=1e-4)


def test_small_level_padding():
    # P5 of a 128px image is 4x4 < TILE: _pad_levels must zero-extend
    # and big ROIs (assigned to P5) must still match
    rng = np.random.RandomState(3)
    feats = _feats(rng)
    assert feats[-1].shape[1] < TILE
    rois = jnp.asarray([[[4.0, 8.0, 120.0, 116.0]]], jnp.float32)  # huge
    ref = batched_multilevel_roi_align(feats, rois, STRIDES, 7)
    pal = pallas_batched_multilevel_roi_align(feats, rois, STRIDES, 7, 2,
                                              2, True)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               atol=1e-4)


def test_gradient_matches_reference():
    rng = np.random.RandomState(4)
    feats = _feats(rng, c=8)
    rois = _rois(rng, 1, 5)

    gp = jax.grad(lambda fs: pallas_batched_multilevel_roi_align(
        fs, rois, STRIDES, 7, 2, 2, True).sum())(feats)
    gr = jax.grad(lambda fs: batched_multilevel_roi_align(
        fs, rois, STRIDES, 7).sum())(feats)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_extreme_aspect_ratio_fwd_bwd_consistent():
    """A ROI whose extent at the heuristic level overflows the tile is
    bumped to a coarser level (assign_fpn_levels_tile_fit); the Pallas
    forward and the XLA backward must use that SAME assignment, so the
    kernel output equals the XLA value at the bumped level and the
    gradient flows into the bumped level's feature map."""
    from eksml_tpu.ops.roi_align import (assign_fpn_levels,
                                         assign_fpn_levels_tile_fit)

    rng = np.random.RandomState(5)
    feats = _feats(rng, img=1024, c=8)
    # 900x12 px sliver: sqrt(area)~104 -> heuristic P3 (stride 8),
    # extent 900/8 = 112 > TILE-3 -> bumped to P4 (56 fits)
    rois = jnp.asarray([[[50.0, 100.0, 950.0, 112.0]]], jnp.float32)
    flat = rois.reshape(1, 4)
    heur = assign_fpn_levels(flat, 2, 5) - 2
    fit = assign_fpn_levels_tile_fit(flat, STRIDES, 4, TILE)
    assert int(fit[0]) > int(heur[0])  # the bump actually triggered

    ref = batched_multilevel_roi_align(
        feats, rois, STRIDES, 7, levels=fit.reshape(1, 1))
    pal = pallas_batched_multilevel_roi_align(feats, rois, STRIDES, 7, 2,
                                              2, True)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref), atol=1e-4)

    gp = jax.grad(lambda fs: pallas_batched_multilevel_roi_align(
        fs, rois, STRIDES, 7, 2, 2, True).sum())(feats)
    gr = jax.grad(lambda fs: batched_multilevel_roi_align(
        fs, rois, STRIDES, 7, levels=fit.reshape(1, 1)).sum())(feats)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_bwd_accumulation_is_linear_in_duplicate_rois():
    """N identical ROIs must deposit exactly N× one ROI's gradient —
    the sharp test of the backward kernel's sequential RMW
    accumulation into the shared tile region."""
    rng = np.random.RandomState(6)
    feats = _feats(rng, c=8)
    one = _rois(rng, 1, 1)
    four = jnp.tile(one, (1, 4, 1))

    g1 = jax.grad(lambda fs: pallas_batched_multilevel_roi_align(
        fs, one, STRIDES, 7, 2, 2, True).sum())(feats)
    g4 = jax.grad(lambda fs: pallas_batched_multilevel_roi_align(
        fs, four, STRIDES, 7, 2, 2, True).sum())(feats)
    for a, b in zip(g4, g1):
        np.testing.assert_allclose(np.asarray(a), 4 * np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_bwd_bf16_dtype_and_tolerance():
    """bf16 features: gradient comes back in bf16 (f32 accumulation
    inside) and tracks the f32 reference within bf16 resolution."""
    rng = np.random.RandomState(7)
    feats32 = _feats(rng, b=2, c=8)
    feats16 = tuple(f.astype(jnp.bfloat16) for f in feats32)
    rois = _rois(rng, 2, 6)

    gp = jax.grad(lambda fs: pallas_batched_multilevel_roi_align(
        fs, rois, STRIDES, 7, 2, 2, True).sum().astype(jnp.float32)
        )(feats16)
    gr = jax.grad(lambda fs: batched_multilevel_roi_align(
        fs, rois, STRIDES, 7).sum())(feats32)
    for a, b in zip(gp, gr):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, dtype=np.float32),
                                   np.asarray(b), atol=0.05, rtol=0.05)


def _dispatch_jaxpr(feats, rois, grad=False):
    from eksml_tpu.ops.roi_align import dispatch_roi_align

    def fwd(fs, r):
        return dispatch_roi_align(fs, r, STRIDES, 7)

    fn = jax.grad(lambda fs, r: fwd(fs, r).sum()) if grad else fwd
    return str(jax.make_jaxpr(fn)(feats, rois))


@pytest.mark.parametrize("grad", [False, True],
                         ids=["forward", "grad"])
@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_gate_follows_the_platform(monkeypatch, backend, grad):
    """The kernel-or-XLA choice is decidable BEFORE anything compiles,
    from the platform alone — no probe, no setting — and it is ONE
    choice: the backward ``pallas_call`` is in the gradient's jaxpr
    exactly when the forward's is (no Pallas forward over an XLA
    backward, nor the reverse)."""
    from eksml_tpu.ops.pallas import roi_align_kernel as rk

    monkeypatch.setattr(rk.jax, "default_backend", lambda: backend)
    rng = np.random.RandomState(9)
    jaxpr = _dispatch_jaxpr(_feats(rng), _rois(rng, 1, 4), grad=grad)
    kernel = backend == "tpu"
    assert ("pallas_call" in jaxpr) is kernel
    assert ("roi_align_fwd" in jaxpr) is kernel
    assert ("roi_align_bwd" in jaxpr) is (kernel and grad)


def test_gate_kernel_failure_on_tpu_raises_not_falls_back(monkeypatch):
    """On a TPU backend a kernel the compiler refuses is the run's
    failure, with the compiler's message — never a quiet switch to the
    XLA formulation.  (Here the 'TPU' is a CPU, which cannot compile a
    Mosaic kernel: exactly a refused compile.)"""
    from eksml_tpu.ops.pallas import roi_align_kernel as rk
    from eksml_tpu.ops.roi_align import dispatch_roi_align

    monkeypatch.setattr(rk.jax, "default_backend", lambda: "tpu")
    rng = np.random.RandomState(10)
    feats, rois = _feats(rng), _rois(rng, 1, 4)
    with pytest.raises(Exception) as err:
        jax.block_until_ready(jax.jit(
            lambda fs, r: dispatch_roi_align(fs, r, STRIDES, 7))(
                feats, rois))
    assert "interpret" in str(err.value).lower() \
        or "mosaic" in str(err.value).lower(), err.value


def test_gate_coverage_guard_selects_xla_before_compiling(monkeypatch):
    """Feature maps implying an image wider than the tile covers at the
    coarsest level take the XLA path even where the gate says kernel
    — a static shape decision, not a fallback."""
    from eksml_tpu.ops.pallas import roi_align_kernel as rk

    monkeypatch.setattr(rk.jax, "default_backend", lambda: "tpu")
    big = 2048  # > (TILE - margin) * 32
    feats = tuple(jnp.zeros((1, big // s, big // s, 8), jnp.float32)
                  for s in STRIDES)
    rois = jnp.asarray([[[8.0, 8.0, 200.0, 120.0]]], jnp.float32)
    assert "pallas_call" not in _dispatch_jaxpr(feats, rois)


def test_kernel_runs_once_per_batch_shard_on_a_mesh():
    """XLA's SPMD partitioner refuses a bare Mosaic kernel on a
    multi-device mesh ("cannot be automatically partitioned"; found by
    the described-v5e compile of the 4-device train step).  Under
    ``batch_partition`` the dispatch wraps the kernel in a shard_map
    over the batch axes; values and gradients must equal the unsharded
    reference."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from eksml_tpu.ops.roi_align import _per_shard, batch_partition

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4, 1),
                ("data", "model"))
    spec = P(("data", "model"))
    rng = np.random.RandomState(11)
    feats = _feats(rng, b=4, c=8)
    rois = _rois(rng, 4, 5)

    def kernel(fs, r):
        return pallas_batched_multilevel_roi_align(fs, r, STRIDES, 7, 2,
                                                   2, True)

    with batch_partition(mesh, spec):
        sharded = _per_shard(kernel, len(feats))
        assert sharded is not kernel
        sh = NamedSharding(mesh, spec)
        out = jax.jit(sharded, out_shardings=sh)(
            jax.device_put(feats, sh), jax.device_put(rois, sh))
        grads = jax.jit(jax.grad(
            lambda fs: sharded(fs, rois).sum()))(feats)
    assert len(out.sharding.device_set) == 4
    ref = batched_multilevel_roi_align(feats, rois, STRIDES, 7)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4)
    ref_grads = jax.grad(lambda fs: batched_multilevel_roi_align(
        fs, rois, STRIDES, 7).sum())(feats)
    for a, b in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4)
    # one device (or no declaration): the kernel is called directly
    assert _per_shard(kernel, len(feats)) is kernel


def test_vmem_chunk_math_covers_observed_hardware_oom():
    """The round-5 hardware compile failure: mask head, 128 ROIs x
    14x14 x 256ch bf16 — full output 12.85 MiB + 4 MiB scratch
    overflowed Mosaic's 16 MiB scoped-vmem stack by 160 KiB.  The
    static chunk bound must split exactly this case (and the box
    head's equivalent) under budget."""
    from eksml_tpu.ops.pallas.roi_align_kernel import (
        TILE, _VMEM_STACK_BUDGET, _roi_chunk)

    for n, out in ((128, 14), (512, 7)):  # mask head / box head
        c, esize = 256, 2  # bf16
        scratch = 2 * TILE * TILE * c * esize
        chunk = _roi_chunk(n, out, c, jnp.bfloat16, scratch)
        assert n % chunk == 0
        assert chunk < n  # the failing case MUST be split
        out_pad = out + (-out % 8)
        assert (chunk * out * out_pad * c * esize + scratch
                <= _VMEM_STACK_BUDGET)
    # small calls stay single-shot (no perf regression on probes)
    assert _roi_chunk(6, 7, 32, jnp.float32,
                      2 * TILE * TILE * 32 * 4) == 6


def test_forward_chunked_matches_unchunked(monkeypatch):
    """Force the chunked forward path (budget shrunk so n=12 splits)
    and assert bit-identical output vs the single-call path — each
    ROI's computation is independent, so chunking must be invisible."""
    from eksml_tpu.ops.pallas import roi_align_kernel as rk

    rng = np.random.RandomState(7)
    feats = _feats(rng, b=2)
    rois = _rois(rng, 2, 6)
    whole = rk._pallas_forward(feats, rois, STRIDES, 7, 2, 2, True)
    esize = 4
    scratch = 2 * rk.TILE * rk.TILE * 32 * esize
    monkeypatch.setattr(rk, "_VMEM_STACK_BUDGET",
                        scratch + 4 * 7 * 8 * 32 * esize)
    # per-ROI size uses the TILED layout (W 7→8)
    assert rk._roi_chunk(12, 7, 32, jnp.float32, scratch) == 4
    chunked = rk._pallas_forward(feats, rois, STRIDES, 7, 2, 2, True)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(chunked))


def test_backward_chunked_matches_unchunked(monkeypatch):
    """Same forcing for the backward: the chained aliased-accumulator
    chunks must reproduce the single-call feature gradients."""
    from eksml_tpu.ops.pallas import roi_align_kernel as rk

    rng = np.random.RandomState(8)
    feats = _feats(rng, b=1)
    rois = _rois(rng, 1, 6)
    g = jnp.asarray(rng.randn(1, 6, 7, 7, 32).astype(np.float32))
    whole = rk._pallas_backward(feats, rois, g, STRIDES, 7, 2, 2, True)
    esize = 4
    scratch = rk._bwd_scratch_bytes(7, 32)
    monkeypatch.setattr(rk, "_VMEM_STACK_BUDGET",
                        scratch + 2 * 7 * 8 * 32 * esize)
    # per-ROI size uses the TILED layout (W 7→8)
    assert rk._roi_chunk(6, 7, 32, jnp.float32, scratch) == 2
    chunked = rk._pallas_backward(feats, rois, g, STRIDES, 7, 2, 2, True)
    for w, ch in zip(whole, chunked):
        np.testing.assert_allclose(np.asarray(w), np.asarray(ch),
                                   atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("n,out", [(4 * 512, 7), (4 * 128, 14)],
                         ids=["box", "mask"])
def test_backward_chunk_fits_at_the_cells_shapes(n, out, dtype):
    """The backward's chunk of the incoming gradient at the benchmark
    cells' own shapes (batch 4, 512 box ROIs at 7x7 / 128 mask ROIs at
    14x14, C 256): it divides the grid, and a chunk of the gradient
    beside the kernel's own scratch stays under the stack budget, which
    stays under the limit every kernel declares."""
    from eksml_tpu.ops.pallas import roi_align_kernel as rk

    c = 256
    scratch = rk._bwd_scratch_bytes(out, c)
    chunk = rk._roi_chunk(n, out, c, dtype, scratch)
    assert n % chunk == 0
    out_pad = out + (-out % 8)
    held = chunk * out * out_pad * c * jnp.dtype(dtype).itemsize
    assert held + scratch <= rk._VMEM_STACK_BUDGET
    assert rk._VMEM_STACK_BUDGET < rk._SCOPED_VMEM_KIB * 1024


def test_ops_and_models_read_no_environment(tracked_files):
    """What the step's ops do is decided by what they can observe
    (the platform, static shapes), never by a variable read while
    tracing: the value would be baked into the jitted program, where
    the persistent compile cache ignores a later change."""
    import os
    import re

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sources = [p for p in tracked_files if p.endswith(".py")
               and p.startswith(("eksml_tpu/ops/", "eksml_tpu/models/"))]
    assert len(sources) >= 10, sources
    reads = []
    for path in sources:
        with open(os.path.join(root, path)) as f:
            reads += [f"{path}:{i}" for i, line in enumerate(f, 1)
                      if re.search(r"\benviron\b|\bgetenv\b", line)]
    assert not reads, reads


def _grads_vs_xla(feats, rois, strides, out_size, levels=None):
    """(kernel grads, XLA-formulation grads at the kernel's levels) of
    ``sum(roi_align · w)`` with a fixed random cotangent ``w``."""
    from eksml_tpu.ops.pallas import roi_align_kernel as rk
    from eksml_tpu.ops.roi_align import assign_fpn_levels_tile_fit

    b, n = rois.shape[:2]
    if levels is None:
        levels = assign_fpn_levels_tile_fit(
            rois.reshape(b * n, 4), strides, len(feats), TILE,
            align=rk.sublane_align(feats[0].dtype)).reshape(b, n)
    c = feats[0].shape[-1]
    w = jnp.asarray(np.random.RandomState(99).randn(
        b, n, out_size, out_size, c).astype(np.float32))
    feats32 = tuple(f.astype(jnp.float32) for f in feats)
    gp = jax.grad(lambda fs: (pallas_batched_multilevel_roi_align(
        fs, rois, strides, out_size, 2, 2, True).astype(jnp.float32)
        * w).sum())(feats)
    gr = jax.grad(lambda fs: (batched_multilevel_roi_align(
        fs, rois, strides, out_size, 2, 2, levels=levels) * w).sum())(
            feats32)
    return gp, gr


def _assert_grads_close(gp, gr, dtype):
    for a, b in zip(gp, gr):
        assert a.dtype == dtype
        if dtype == jnp.bfloat16:   # output rounding, 2^-8 relative
            np.testing.assert_allclose(np.asarray(a, dtype=np.float32),
                                       np.asarray(b), atol=0.05, rtol=0.02)
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4)


def _strip_counts(feats, rois, strides):
    from eksml_tpu.ops.pallas import roi_align_kernel as rk

    align = rk.sublane_align(feats[0].dtype)
    prep = rk._bwd_prep(rk._pad_levels(feats, align), rois, strides, 7, 2,
                        align)
    return set(zip(np.asarray(prep[4]).tolist(),
                   np.asarray(prep[5]).tolist()))


@pytest.mark.parametrize("out_size", [7, 14])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_bwd_every_strip_count_matches_xla_vjp(dtype, out_size):
    """A two-level pyramid whose coarsest level takes every large ROI:
    extents of 10, 20, 36 and 50 feature pixels down and across give
    every strip count from 1 × 1 to the tile-filling 4 × 4 (bf16: the
    tile fit stops an ROI at 46 pixels, so 3 strips down)."""
    from eksml_tpu.ops.pallas import roi_align_kernel as rk

    dtype = jnp.dtype(dtype)
    strides, img = (4, 8), 512
    rng = np.random.RandomState(12)
    feats = tuple(jnp.asarray(rng.randn(1, img // s, img // s, 8), dtype)
                  for s in strides)
    top = 50 if dtype == jnp.float32 else 44
    sizes = [10, 20, 36, top]
    boxes = [[20.3, 30.6, 20.3 + 40, 30.6 + 40]]      # P2, one strip
    for hf in sizes:
        for wf in sizes:
            # the first column on a multiple of 8 (the strips' origin),
            # or 7 past one where only the round-down makes 4 strips
            off = 7.6 if wf == 44 else 0.6
            x1 = 8 * (8 * rng.randint(0, (img // 8 - wf) // 8) + off)
            y1 = 8 * (rng.randint(0, img // 8 - hf - 1) + 0.6)
            boxes.append([x1, y1, x1 + 8 * wf, y1 + 8 * hf])
    rois = jnp.asarray([boxes], jnp.float32)
    counts = _strip_counts(feats, rois, strides)
    downs = (1, 2, 3, 4) if dtype == jnp.float32 else (1, 2, 3)
    assert counts >= {(ny, nx) for ny in downs for nx in (1, 2, 3, 4)}
    assert max(counts) <= (TILE // rk.STRIP_H, TILE // rk.STRIP_W)
    _assert_grads_close(*_grads_vs_xla(feats, rois, strides, out_size),
                        dtype)


@pytest.mark.parametrize("out_size", [7, 14])
def test_bwd_every_level_and_border_matches_xla_vjp(out_size):
    """ROIs on each of the four levels (P4 and P5 of a 512 px canvas
    are 32 and 16 wide: smaller than the tile, zero-extended), hugging
    each border and reaching past it (the strips are pulled inside the
    padded map; rows outside it get nothing, as zero padding wants)."""
    rng = np.random.RandomState(13)
    feats = _feats(rng, img=512, c=8)
    rois = jnp.asarray([[
        [100.2, 200.7, 140.9, 236.1],     # P2
        [60.5, 300.1, 220.3, 420.8],      # P3
        [101.0, 90.0, 400.0, 390.0],      # P4
        [10.0, 12.0, 500.0, 505.0],       # P5
        [0.0, 0.0, 30.0, 22.0],           # top-left corner
        [470.0, 0.0, 511.0, 41.0],        # top-right
        [0.0, 480.0, 40.0, 511.9],        # bottom-left
        [300.0, 330.0, 511.5, 511.5],     # bottom-right, P3
        [-14.0, -9.0, 31.0, 28.0],        # past the top-left
        [490.0, 495.0, 540.0, 530.0],     # past the bottom-right
        [0.0, 0.0, 0.0, 0.0],             # a padded (empty) proposal
    ]], jnp.float32)
    gp, gr = _grads_vs_xla(feats, rois, STRIDES, out_size)
    assert all(float(jnp.abs(g).max()) > 0 for g in gr)  # every level
    _assert_grads_close(gp, gr, jnp.float32)


@pytest.mark.parametrize("kind", ["identical", "half_overlapping"])
def test_bwd_overlapping_rois_accumulate(kind):
    """The hazard path: consecutive grid steps whose strips meet must
    add, not overwrite.  All-identical ROIs meet under ANY grid order
    (the de-clustering walk reorders the grid); a chain of
    half-overlapping ones meets its neighbours on both sides."""
    rng = np.random.RandomState(14)
    feats = _feats(rng, c=8)
    if kind == "identical":
        rois = jnp.tile(_rois(rng, 1, 1), (1, 8, 1))
    else:
        x1 = 10.0 + 12.0 * np.arange(8)
        rois = jnp.asarray([np.stack(
            [x1, 0.5 * x1 + 20, x1 + 24, 0.5 * x1 + 44], 1)], jnp.float32)
    _assert_grads_close(*_grads_vs_xla(feats, rois, STRIDES, 7),
                        jnp.float32)


def _share_in_numpy(feats, rois, strides):
    """bwd_tile_share recomputed from the ROIs: rows floor(y1 − 0.5) ..
    floor(y2 − 0.5) + 1 on the ROI's level, columns likewise from an
    origin rounded down to 8, in 16 × 16 strips, over the tile."""
    from eksml_tpu.ops.pallas import roi_align_kernel as rk
    from eksml_tpu.ops.roi_align import assign_fpn_levels_tile_fit

    flat = np.asarray(rois, np.float64).reshape(-1, 4)
    levels = np.asarray(assign_fpn_levels_tile_fit(
        jnp.asarray(flat, jnp.float32), strides, len(feats), TILE,
        align=rk.sublane_align(feats[0].dtype)))
    total = 0
    for (x1, y1, x2, y2), lv in zip(flat, levels):
        per_axis = []
        for lo, hi, size, align in (
                (y1, y2, feats[lv].shape[1], 1),
                (x1, x2, feats[lv].shape[2], 8)):
            size = max(size, TILE)
            first = int(np.clip(np.floor(lo / strides[lv] - 0.5), 0,
                                size - 1))
            last = int(np.clip(np.floor(hi / strides[lv] - 0.5) + 1,
                               first, size - 1))
            first = first // align * align
            per_axis.append(min((last - first) // 16 + 1, TILE // 16))
        total += per_axis[0] * per_axis[1]
    return total / len(flat) * 16 * 16 / (TILE * TILE)


@pytest.mark.parametrize("case", ["random", "tile_filling", "p2_32px"])
def test_bwd_tile_share(case):
    from eksml_tpu.ops.pallas import roi_align_kernel as rk

    assert (rk.STRIP_H, rk.STRIP_W) == (16, 16)   # _share_in_numpy's
    rng = np.random.RandomState(15)
    if case == "tile_filling":
        # two levels: a 400 px box is 50 px on the coarsest, 4 × 4 strips
        strides = (4, 8)
        feats = tuple(jnp.zeros((2, 512 // s, 512 // s, 8), jnp.float32)
                      for s in strides)
        xy = rng.uniform(2, 100, (2, 5, 2))
        rois = jnp.asarray(np.concatenate([xy, xy + 402.0], -1),
                           jnp.float32)
    else:
        strides = STRIDES
        feats = tuple(jnp.zeros((2, 1344 // s, 1344 // s, 8), jnp.bfloat16)
                      for s in strides)
        if case == "random":
            side = np.exp(rng.uniform(np.log(16), np.log(1200), (2, 64, 2)))
        else:
            side = np.full((2, 64, 2), 32.0)
        xy = rng.uniform(0, 1, (2, 64, 2)) * (1343 - side)
        rois = jnp.asarray(np.concatenate([xy, xy + side], -1),
                           jnp.float32)
    share = float(rk.bwd_tile_share(feats, rois, strides))
    assert share == pytest.approx(_share_in_numpy(feats, rois, strides))
    if case == "tile_filling":
        assert share == 1.0
    elif case == "p2_32px":
        # 8 px + taps: one strip down, one or two across (8-aligned)
        assert 1 / 16 <= share < 0.2
    else:
        assert 0.1 < share < 0.9


def _pallas_eqn_compiler_params(fn, *args):
    """Collect the compiler_params of every pallas_call equation in
    fn's jaxpr (recursing through closed subjaxprs)."""
    from jax._src import core as jc

    found = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn.params.get("compiler_params"))
            for v in eqn.params.values():
                vs = v if isinstance(v, (tuple, list)) else (v,)
                for w in vs:
                    if isinstance(w, jc.ClosedJaxpr):
                        walk(w.jaxpr)
                    elif isinstance(w, jc.Jaxpr):
                        walk(w)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def _assert_vmem_limit(params_list, kib):
    """Every emitted kernel declares exactly the limit."""
    assert params_list, "no pallas_call equation found"
    for cp in params_list:
        mosaic = cp["mosaic_tpu"] if "mosaic_tpu" in cp else cp
        assert mosaic.vmem_limit_bytes == kib * 1024, mosaic


def test_vmem_limit_rides_in_the_kernel():
    """Nothing in the repo edits LIBTPU_INIT_ARGS (libtpu reads it
    once, at backend init, so a flag appended later never reaches the
    compiler).  The scoped-vmem limit therefore travels IN the compiled
    module: assert every pallas_call the fwd, bwd, and HBM-laundering
    paths emit carries compiler_params.vmem_limit_bytes."""
    from eksml_tpu.ops.pallas import roi_align_kernel as rk

    rng = np.random.RandomState(3)
    feats = _feats(rng, b=1)
    rois = _rois(rng, 1, 4)
    g = jnp.asarray(rng.randn(1, 4, 7, 7, 32).astype(np.float32))

    fwd = _pallas_eqn_compiler_params(
        lambda f, r: rk._pallas_forward(f, r, STRIDES, 7, 2, 2, True),
        feats, rois)
    _assert_vmem_limit(fwd, rk._SCOPED_VMEM_KIB)

    # bwd path: the _to_hbm laundering kernels for the pinned
    # accumulators plus the chained RMW kernel, whose two staging
    # strips fit the base limit — no per-call grant
    bwd = _pallas_eqn_compiler_params(
        lambda f, r, gg: rk._pallas_backward(
            f, r, gg, STRIDES, 7, 2, 2, True),
        feats, rois, g)
    _assert_vmem_limit(bwd, rk._SCOPED_VMEM_KIB)
    assert rk._SCOPED_VMEM_KIB == 32768
