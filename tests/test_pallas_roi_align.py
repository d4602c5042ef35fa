"""Pallas ROIAlign kernels vs the XLA reference formulation.

Runs in interpret mode (no TPU in the test environment, SURVEY.md §4);
the kernels' math — strips of the assigned level's map over the ROI's
footprint, one product of pooled two-tap bilinear weights a strip —
must agree with ops.roi_align's gather formulation everywhere the
tile-fit level assignment puts the ROI.
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
    14x14 x 256ch bf16 — the full output (12.85 MiB) beside the
    kernel's scratch overflowed Mosaic's 16 MiB scoped-vmem stack by
    160 KiB.  The static chunk bound must split exactly this case (and
    the box head's equivalent) under budget."""
    from eksml_tpu.ops.pallas.roi_align_kernel import (
        _VMEM_STACK_BUDGET, _block_rows, _fwd_scratch_bytes, _roi_chunk)

    for n, out in ((128, 14), (512, 7)):  # mask head / box head
        c, esize = 256, 2  # bf16
        scratch = _fwd_scratch_bytes(out, c, jnp.bfloat16)
        chunk = _roi_chunk(n, out, c, jnp.bfloat16, scratch)
        assert n % chunk == 0
        assert chunk < n  # the failing case MUST be split
        assert (chunk * _block_rows(out) * c * esize + scratch
                <= _VMEM_STACK_BUDGET)
    # small calls stay single-shot (no perf regression on probes)
    assert _roi_chunk(6, 7, 32, jnp.float32,
                      _fwd_scratch_bytes(7, 32, jnp.float32)) == 6


def test_forward_chunked_matches_unchunked(monkeypatch):
    """Force the chunked forward path (budget shrunk so n=12 splits)
    and assert bit-identical output vs the single-call path — each
    ROI's computation is independent, so chunking must be invisible
    (the strip pipeline and the result slots start afresh in each
    call)."""
    from eksml_tpu.ops.pallas import roi_align_kernel as rk

    rng = np.random.RandomState(7)
    feats = _feats(rng, b=2)
    rois = _rois(rng, 2, 6)
    whole = rk._pallas_forward(feats, rois, STRIDES, 7, 2, 2, True)
    esize = 4
    scratch = rk._fwd_scratch_bytes(7, 32, jnp.float32)
    # per-ROI size uses the TILED layout ((i j) 49→64)
    assert rk._block_rows(7) == 64 and rk._block_rows(14) == 208
    monkeypatch.setattr(rk, "_VMEM_STACK_BUDGET",
                        scratch + 4 * 64 * 32 * esize)
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
                        scratch + 2 * rk._block_rows(7) * 32 * esize)
    assert rk._roi_chunk(6, 7, 32, jnp.float32, scratch) == 2
    chunked = rk._pallas_backward(feats, rois, g, STRIDES, 7, 2, 2, True)
    for w, ch in zip(whole, chunked):
        np.testing.assert_allclose(np.asarray(w), np.asarray(ch),
                                   atol=1e-5)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("n,out", [(4 * 512, 7), (4 * 128, 14)],
                         ids=["box", "mask"])
def test_chunk_fits_at_the_cells_shapes(n, out, dtype, direction):
    """The forward's chunk of its output and the backward's chunk of the
    incoming gradient at the benchmark cells' own shapes (batch 4, 512
    box ROIs at 7x7 / 128 mask ROIs at 14x14, C 256): it divides the
    grid, and a chunk beside the kernel's own scratch stays under the
    stack budget, which stays under the limit every kernel declares."""
    from eksml_tpu.ops.pallas import roi_align_kernel as rk

    c = 256
    scratch = (rk._fwd_scratch_bytes(out, c, dtype) if direction == "fwd"
               else rk._bwd_scratch_bytes(out, c))
    chunk = rk._roi_chunk(n, out, c, dtype, scratch)
    assert n % chunk == 0
    # eight box-head calls and eight mask-head calls a step in bf16, as
    # the cells' traces have had them since PR 25
    if dtype == jnp.bfloat16:
        assert chunk == {7: 256, 14: 64}[out]
    held = chunk * rk._block_rows(out) * c * jnp.dtype(dtype).itemsize
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


def _w_align(feats, direction):
    """W alignment of a direction's strips: the features' sublane tile
    for the forward's reads, 8 for the backward's f32 accumulators."""
    from eksml_tpu.ops.pallas import roi_align_kernel as rk

    return (rk.sublane_align(feats[0].dtype) if direction == "fwd"
            else rk._BWD_ALIGN)


def _strip_counts(feats, rois, strides, direction="bwd"):
    from eksml_tpu.ops.pallas import roi_align_kernel as rk

    align = rk.sublane_align(feats[0].dtype)
    prep = rk._strip_prep(rk._pad_levels(feats, align), rois, strides, 7,
                          2, align, _w_align(feats, direction))
    return set(zip(np.asarray(prep[4]).tolist(),
                   np.asarray(prep[5]).tolist()))


def _every_extent_rois(rng, img, sizes, origin_step, last_off):
    """One ROI per (rows, columns) pair of ``sizes`` (feature pixels at
    stride 8).  The first column sits just past a multiple of
    ``origin_step`` — or ``last_off`` past one for the widest extent,
    where only the origin's round-down makes the fourth strip."""
    boxes = [[20.3, 30.6, 20.3 + 40, 30.6 + 40]]      # P2, one strip
    for hf in sizes:
        for wf in sizes:
            off = last_off if wf == sizes[-1] else 0.6
            cells = (img // 8 - wf - int(off) - 1) // origin_step
            x1 = 8 * (origin_step * rng.randint(0, max(cells, 1)) + off)
            y1 = 8 * (rng.randint(0, img // 8 - hf - 1) + 0.6)
            boxes.append([x1, y1, x1 + 8 * wf, y1 + 8 * hf])
    return jnp.asarray([boxes], jnp.float32)


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
    # the first column on a multiple of 8 (the strips' origin), or 7
    # past one where only the round-down makes 4 strips
    if dtype == jnp.float32:
        rois = _every_extent_rois(rng, img, [10, 20, 36, 50], 8, 0.6)
    else:
        rois = _every_extent_rois(rng, img, [10, 20, 36, 44], 8, 7.6)
    counts = _strip_counts(feats, rois, strides)
    downs = (1, 2, 3, 4) if dtype == jnp.float32 else (1, 2, 3)
    assert counts >= {(ny, nx) for ny in downs for nx in (1, 2, 3, 4)}
    assert max(counts) <= (TILE // rk.STRIP_H, TILE // rk.STRIP_W)
    _assert_grads_close(*_grads_vs_xla(feats, rois, strides, out_size),
                        dtype)


_BORDER_ROIS = [
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
]


@pytest.mark.parametrize("out_size", [7, 14])
def test_bwd_every_level_and_border_matches_xla_vjp(out_size):
    """ROIs on each of the four levels (P4 and P5 of a 512 px canvas
    are 32 and 16 wide: smaller than the tile, zero-extended), hugging
    each border and reaching past it (the strips are pulled inside the
    padded map; rows outside it get nothing, as zero padding wants)."""
    rng = np.random.RandomState(13)
    feats = _feats(rng, img=512, c=8)
    rois = jnp.asarray([_BORDER_ROIS], jnp.float32)
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


def _share_in_numpy(feats, rois, strides, w_align=8):
    """A tile share recomputed from the ROIs: rows floor(y1 − 0.5) ..
    floor(y2 − 0.5) + 1 on the ROI's level, columns likewise from an
    origin rounded down to ``w_align``, in 16 × 16 strips, over the
    tile."""
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
                (x1, x2, feats[lv].shape[2], w_align)):
            size = max(size, TILE)
            first = int(np.clip(np.floor(lo / strides[lv] - 0.5), 0,
                                size - 1))
            last = int(np.clip(np.floor(hi / strides[lv] - 0.5) + 1,
                               first, size - 1))
            first = first // align * align
            per_axis.append(min((last - first) // 16 + 1, TILE // 16))
        total += per_axis[0] * per_axis[1]
    return total / len(flat) * 16 * 16 / (TILE * TILE)


@pytest.mark.parametrize("direction", ["bwd", "fwd"])
@pytest.mark.parametrize("case", ["random", "tile_filling", "p2_32px"])
def test_tile_share(case, direction):
    """``bwd_tile_share`` / ``fwd_tile_share`` (the step's
    ``roi_bwd_tile_share`` / ``roi_fwd_tile_share`` counters) against
    numpy.  The bf16 cases differ by the W origin: 16 for the forward's
    strips, 8 for the backward's."""
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
    fn = rk.fwd_tile_share if direction == "fwd" else rk.bwd_tile_share
    share = float(fn(feats, rois, strides))
    w_align = _w_align(feats, direction)
    assert w_align == (8 if direction == "bwd" or case == "tile_filling"
                       else 16)
    assert share == pytest.approx(
        _share_in_numpy(feats, rois, strides, w_align))
    if case == "tile_filling":
        assert share == 1.0
    elif case == "p2_32px":
        # 8 px + taps: one strip down, one or two across
        assert 1 / 16 <= share < 0.2
    else:
        assert 0.1 < share < 0.9
    if direction == "fwd" and case != "tile_filling":
        # a coarser origin never covers less
        assert share >= float(rk.bwd_tile_share(feats, rois, strides))


# ------------------------------------------------- the forward's strips


def _fwd_vs_xla(feats, rois, strides, out_size):
    """(kernel output, XLA formulation in float32 at the kernel's
    levels), both float32 arrays."""
    from eksml_tpu.ops.pallas import roi_align_kernel as rk
    from eksml_tpu.ops.roi_align import assign_fpn_levels_tile_fit

    b, n = rois.shape[:2]
    levels = assign_fpn_levels_tile_fit(
        rois.reshape(b * n, 4), strides, len(feats), TILE,
        align=rk.sublane_align(feats[0].dtype)).reshape(b, n)
    out = pallas_batched_multilevel_roi_align(feats, rois, strides,
                                              out_size, 2, 2, True)
    assert out.dtype == feats[0].dtype
    assert out.shape == (b, n, out_size, out_size, feats[0].shape[-1])
    ref = batched_multilevel_roi_align(
        tuple(f.astype(jnp.float32) for f in feats), rois, strides,
        out_size, 2, 2, levels=levels)
    return np.asarray(out, np.float32), np.asarray(ref)


def _assert_fwd_close(out, ref, dtype):
    if dtype == jnp.bfloat16:   # output rounding, 2^-9 relative
        np.testing.assert_allclose(out, ref, atol=0.02, rtol=0.01)
    else:
        np.testing.assert_allclose(out, ref, atol=1e-4)


@pytest.mark.parametrize("out_size", [7, 14])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fwd_every_strip_count_matches_xla(dtype, out_size):
    """The forward over every strip count from 1 × 1 to the
    tile-filling 4 × 4 (bf16: the tile fit stops an ROI at 46 pixels,
    so 3 strips down; across, the origin of 16 makes the fourth), on a
    two-level pyramid whose coarsest level takes every large ROI."""
    from eksml_tpu.ops.pallas import roi_align_kernel as rk

    dtype = jnp.dtype(dtype)
    strides, img = (4, 8), 512
    rng = np.random.RandomState(16)
    feats = tuple(jnp.asarray(rng.randn(1, img // s, img // s, 8), dtype)
                  for s in strides)
    if dtype == jnp.float32:
        rois = _every_extent_rois(rng, img, [10, 20, 36, 50], 8, 0.6)
        downs = (1, 2, 3, 4)
    else:
        rois = _every_extent_rois(rng, img, [10, 20, 36, 44], 16, 15.6)
        downs = (1, 2, 3)
    counts = _strip_counts(feats, rois, strides, "fwd")
    assert counts >= {(ny, nx) for ny in downs for nx in (1, 2, 3, 4)}
    assert max(counts) <= (TILE // rk.STRIP_H, TILE // rk.STRIP_W)
    _assert_fwd_close(*_fwd_vs_xla(feats, rois, strides, out_size), dtype)




@pytest.mark.parametrize("out_size", [7, 14])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fwd_every_level_and_border_matches_xla(dtype, out_size):
    """ROIs on each of the four levels (P4 and P5 of a 512 px canvas
    are 32 and 16 wide: a level smaller than the tile, P5 no wider than
    one strip; both zero-extended), hugging each border and reaching
    past it: the strips are pulled inside the padded map, and what lies
    outside the map reads as zeros."""
    dtype = jnp.dtype(dtype)
    rng = np.random.RandomState(17)
    feats = tuple(f.astype(dtype) for f in _feats(rng, img=512, c=8))
    assert feats[-1].shape[2] <= 16
    rois = jnp.asarray([_BORDER_ROIS], jnp.float32)
    out, ref = _fwd_vs_xla(feats, rois, STRIDES, out_size)
    assert all(np.abs(ref[0, k]).max() > 0 for k in range(10))
    _assert_fwd_close(out, ref, dtype)


def test_fwd_bf16_cover_is_aligned_and_never_needs_a_fifth_strip():
    """The bf16 forward's strips start on a multiple of 16 (Mosaic
    wants a provably aligned W origin for a packed dtype) and 4 × 16
    columns always hold the footprint: an ROI at the tile fit's bound
    (46 usable pixels on its level) whose first column sits 15 past a
    multiple of 16 spans 15 + 46 + 3 = 64.  Checked against the
    footprint recomputed in numpy, so the cover's own clip to four
    strips is shown never to cut."""
    from eksml_tpu.ops.pallas import roi_align_kernel as rk
    from eksml_tpu.ops.roi_align import assign_fpn_levels_tile_fit

    rng = np.random.RandomState(18)
    img = 1344
    feats = tuple(jnp.zeros((1, img // s, img // s, 8), jnp.bfloat16)
                  for s in STRIDES)
    align = rk.sublane_align(jnp.bfloat16)
    assert align == 16 and TILE - rk.tile_margin(jnp.bfloat16) == 46
    # random ROIs of every size, and for each level the widest and the
    # tallest ROI the fit leaves there, first column 15 past a multiple
    # of 16 at that level's stride
    side = np.exp(rng.uniform(np.log(8), np.log(1300), (400, 2)))
    xy = rng.uniform(0, 1, (400, 2)) * (img - 1 - side)
    boxes = np.concatenate([xy, xy + side], -1).tolist()
    for stride in STRIDES:
        long = 46 * stride * 0.999
        for k in range(1, 4):
            x1 = (16 * k + 15.5) * stride
            if x1 + long < img:
                boxes.append([x1, 40.0, x1 + long, 40.0 + long / 3])
                boxes.append([x1, 40.0, x1 + long / 3, 40.0 + long])
    rois = jnp.asarray([boxes], jnp.float32)
    padded = rk._pad_levels(feats, align)
    levels, _, ya, xa, ny, nx, ys, xs, bh, bw = (
        np.asarray(v) for v in rk._strip_prep(
            padded, rois, STRIDES, 7, 2, align, align))
    fit = np.asarray(assign_fpn_levels_tile_fit(
        rois[0], STRIDES, 4, TILE, align=align))
    np.testing.assert_array_equal(levels, fit)
    assert set(levels.tolist()) == {0, 1, 2, 3}
    assert nx.max() == 4 and ny.max() <= 4
    flat = np.asarray(boxes, np.float64)
    for k, lv in enumerate(levels):
        h, w = padded[lv].shape[1:3]
        assert w % 16 == 0
        x0 = xa[k] * 16                       # shipped as a block count
        for lo, hi, origin, count, size in (
                (flat[k, 1], flat[k, 3], ya[k], ny[k], h),
                (flat[k, 0], flat[k, 2], x0, nx[k], w)):
            first = int(np.clip(np.floor(lo / STRIDES[lv] - 0.5), 0,
                                size - 1))
            last = int(np.clip(np.floor(hi / STRIDES[lv] - 0.5) + 1,
                               first, size - 1))
            assert 0 <= origin <= first, (k, boxes[k])
            assert last < origin + 16 * count <= size, (k, boxes[k])
        # strip-local sample starts are relative to the strips' origin
        assert ys[k] == pytest.approx(
            flat[k, 1] / STRIDES[lv] - 0.5 - ya[k], abs=1e-3)
        assert xs[k] == pytest.approx(
            flat[k, 0] / STRIDES[lv] - 0.5 - x0, abs=1e-3)


@pytest.mark.parametrize("out_size", [7, 14])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fwd_and_bwd_are_transposes(dtype, out_size):
    """``⟨fwd(x), g⟩ = ⟨x, bwd(g)⟩`` on shared ROIs: the two kernels
    contract the same pooled weights over covers that differ only by
    the W origin.  Float32 features: to float32 rounding.  bf16
    features: the kernels' float32 blocks agree the same way, but each
    side then rounds its OUTPUT to bf16, so the bound is 2^-9 of the
    terms' mass."""
    from eksml_tpu.ops.pallas import roi_align_kernel as rk

    dtype = jnp.dtype(dtype)
    rng = np.random.RandomState(19)
    feats = tuple(f.astype(dtype) for f in _feats(rng, b=2, img=512, c=8))
    rois = jnp.concatenate(
        [_rois(rng, 2, 10, img=512),
         jnp.asarray([_BORDER_ROIS[:4]] * 2, jnp.float32)], 1)
    g = jnp.asarray(rng.randn(2, 14, out_size, out_size, 8), dtype)
    out = rk._pallas_forward(feats, rois, STRIDES, out_size, 2, 2, True)
    grads = rk._pallas_backward(feats, rois, g, STRIDES, out_size, 2, 2,
                                True)
    f64 = np.float64
    lhs = float((np.asarray(out, f64) * np.asarray(g, f64)).sum())
    rhs = sum(float((np.asarray(x, f64) * np.asarray(d, f64)).sum())
              for x, d in zip(feats, grads))
    mass = float(np.abs(np.asarray(out, f64) * np.asarray(g, f64)).sum())
    tol = 2.0 ** -9 if dtype == jnp.bfloat16 else 1e-5
    assert abs(lhs - rhs) <= tol * mass, (lhs, rhs, mass)


def test_strip_product_bf16_equals_highest_on_the_upcast_strip():
    """The three-term split of the weights against what it replaces:
    ``Precision.HIGHEST`` on the strip cast to float32 (here the CPU's
    float32 product, which is at least that).  Equal to float32
    rounding of the sum — far inside bf16's 2^-9, which a single bf16
    pass over the weights would show."""
    from eksml_tpu.ops.pallas import roi_align_kernel as rk

    rng = np.random.RandomState(20)
    w = jnp.asarray(rng.rand(64, 256), jnp.float32)
    strip = jnp.asarray(rng.randn(256, 32), jnp.bfloat16)
    got = np.asarray(rk._strip_product(w, strip), np.float64)
    want = (np.asarray(w, np.float64)
            @ np.asarray(strip.astype(jnp.float32), np.float64))
    scale = np.abs(np.asarray(w, np.float64)) @ np.abs(
        np.asarray(strip.astype(jnp.float32), np.float64))
    assert (np.abs(got - want) <= 2e-6 * scale).all()
    one_pass = np.asarray(jnp.dot(
        w.astype(jnp.bfloat16), strip,
        preferred_element_type=jnp.float32), np.float64)
    assert np.abs(one_pass - want).max() > 50 * np.abs(got - want).max()
    # float32 strips: HIGHEST itself
    got32 = np.asarray(rk._strip_product(w, strip.astype(jnp.float32)))
    np.testing.assert_allclose(got32, want, rtol=1e-5, atol=1e-5)


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
