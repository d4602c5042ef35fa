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


def test_bwd_env_override_forces_xla(monkeypatch):
    """EKSML_ROI_BWD=xla must route interpret-mode grads through the
    XLA formulation (and agree — both are the same linear map)."""
    rng = np.random.RandomState(8)
    feats = _feats(rng, c=8)
    rois = _rois(rng, 1, 3)

    monkeypatch.setenv("EKSML_ROI_BWD", "xla")
    g_xla = jax.grad(lambda fs: pallas_batched_multilevel_roi_align(
        fs, rois, STRIDES, 7, 2, 2, True).sum())(feats)
    monkeypatch.setenv("EKSML_ROI_BWD", "auto")
    g_pal = jax.grad(lambda fs: pallas_batched_multilevel_roi_align(
        fs, rois, STRIDES, 7, 2, 2, True).sum())(feats)
    for a, b in zip(g_xla, g_pal):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4)


def _dispatch_jaxpr(feats, rois):
    from eksml_tpu.ops.roi_align import dispatch_roi_align

    return str(jax.make_jaxpr(
        lambda fs, r: dispatch_roi_align(fs, r, STRIDES, 7))(feats, rois))


@pytest.mark.parametrize("backend,mode,kernel", [
    ("cpu", "auto", False),     # non-TPU platform -> XLA formulation
    ("tpu", "auto", True),      # TPU: auto MEANS the kernel
    ("tpu", "xla", False),      # the explicit setting wins
    ("cpu", "pallas", True),    # ... in both directions
])
def test_gate_is_decided_by_platform_or_setting(monkeypatch, backend,
                                                mode, kernel):
    """The kernel-or-XLA choice is decidable BEFORE anything compiles:
    platform, or the explicit EKSML_ROI_BACKEND setting.  No probe."""
    from eksml_tpu.ops.pallas import roi_align_kernel as rk

    monkeypatch.setattr(rk.jax, "default_backend", lambda: backend)
    monkeypatch.setenv("EKSML_ROI_BACKEND", mode)
    rng = np.random.RandomState(9)
    jaxpr = _dispatch_jaxpr(_feats(rng), _rois(rng, 1, 4))
    assert ("pallas_call" in jaxpr) is kernel


def test_gate_kernel_failure_on_tpu_raises_not_falls_back(monkeypatch):
    """On a TPU backend a kernel the compiler refuses is the run's
    failure, with the compiler's message — never a quiet switch to the
    XLA formulation.  (Here the 'TPU' is a CPU, which cannot compile a
    Mosaic kernel: exactly a refused compile.)"""
    from eksml_tpu.ops.pallas import roi_align_kernel as rk
    from eksml_tpu.ops.roi_align import dispatch_roi_align

    monkeypatch.setattr(rk.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("EKSML_ROI_BACKEND", raising=False)
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
    coarsest level take the XLA path even with the kernel forced — a
    static shape decision, not a fallback."""
    monkeypatch.setenv("EKSML_ROI_BACKEND", "pallas")
    big = 2048  # > (TILE - margin) * 32
    feats = tuple(jnp.zeros((1, big // s, big // s, 8), jnp.float32)
                  for s in STRIDES)
    rois = jnp.asarray([[[8.0, 8.0, 200.0, 120.0]]], jnp.float32)
    assert "pallas_call" not in _dispatch_jaxpr(feats, rois)


def test_gate_rejects_unknown_setting(monkeypatch):
    from eksml_tpu.ops.pallas import roi_align_kernel as rk

    monkeypatch.setenv("EKSML_ROI_BWD", "palas")
    with pytest.raises(ValueError, match="EKSML_ROI_BWD"):
        rk.pallas_roi_bwd_supported()


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
    scratch = rk.TILE * rk.TILE * 32 * esize
    monkeypatch.setattr(rk, "_VMEM_STACK_BUDGET",
                        scratch + 2 * 7 * 8 * 32 * esize)
    # per-ROI size uses the TILED layout (W 7→8)
    assert rk._roi_chunk(6, 7, 32, jnp.float32, scratch) == 2
    chunked = rk._pallas_backward(feats, rois, g, STRIDES, 7, 2, 2, True)
    for w, ch in zip(whole, chunked):
        np.testing.assert_allclose(np.asarray(w), np.asarray(ch),
                                   atol=1e-5)


def test_backward_overlap_matches_serial(monkeypatch):
    """The async write-back pipeline (EKSML_BWD_OVERLAP=1, default)
    must reproduce the serial RMW path bit-for-bit in interpret mode —
    including on DUPLICATED ROIs, where consecutive grid steps RMW the
    same accumulator tiles (the hazard the pipeline's drain logic
    exists for)."""
    from eksml_tpu.ops.pallas import roi_align_kernel as rk

    rng = np.random.RandomState(11)
    feats = _feats(rng, b=1)
    # ALL-same-box ROIs: every pair of grid steps hits the same tile
    # region, so the hazard path fires under ANY grid order — the
    # de-clustering stride permutation in _pallas_backward reorders
    # the grid, and merely-interleaved duplicates would be split apart
    # and never adjacent (code review r5)
    one = np.asarray(_rois(rng, 1, 1))
    rois = jnp.asarray(np.repeat(one, 8, axis=1))
    g = jnp.asarray(rng.randn(1, 8, 7, 7, 32).astype(np.float32))

    monkeypatch.setenv("EKSML_BWD_OVERLAP", "0")
    serial = rk._pallas_backward(feats, rois, g, STRIDES, 7, 2, 2, True)
    monkeypatch.setenv("EKSML_BWD_OVERLAP", "1")
    overlap = rk._pallas_backward(feats, rois, g, STRIDES, 7, 2, 2, True)
    for s, o in zip(serial, overlap):
        np.testing.assert_array_equal(np.asarray(s), np.asarray(o))


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


def _assert_vmem_limit(params_list, kib, extra_bytes=0):
    """Every emitted kernel must declare at least the base limit; the
    bwd RMW kernel may additionally carry its overlap-scratch grant
    (base .. base + extra_bytes)."""
    assert params_list, "no pallas_call equation found"
    for cp in params_list:
        mosaic = cp["mosaic_tpu"] if "mosaic_tpu" in cp else cp
        assert kib * 1024 <= mosaic.vmem_limit_bytes \
            <= kib * 1024 + extra_bytes, mosaic


def test_vmem_limit_rides_in_the_kernel(monkeypatch):
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

    # bwd path includes the _to_hbm laundering kernels for the pinned
    # accumulators plus the chained RMW kernel, which under the
    # overlap pipeline declares its doubled staging scratch in its OWN
    # limit (r5b hardware: 35.94 MiB needed vs the base 32 — the
    # extra must ride per-call, base + 2x the extra staging slot)
    # derive from the fixture exactly as the kernel does
    # (extra = TILE*TILE*c*esize, granted 2x)
    overlap_grant = (2 * rk.TILE * rk.TILE * feats[0].shape[-1]
                     * np.dtype(np.float32).itemsize)
    monkeypatch.setenv("EKSML_BWD_OVERLAP", "1")
    bwd = _pallas_eqn_compiler_params(
        lambda f, r, gg: rk._pallas_backward(
            f, r, gg, STRIDES, 7, 2, 2, True),
        feats, rois, g)
    _assert_vmem_limit(bwd, rk._SCOPED_VMEM_KIB, overlap_grant)
    assert any(
        (cp["mosaic_tpu"] if "mosaic_tpu" in cp else cp).vmem_limit_bytes
        == rk._SCOPED_VMEM_KIB * 1024 + overlap_grant for cp in bwd)

    # serial path: no grant, exact base everywhere
    monkeypatch.setenv("EKSML_BWD_OVERLAP", "0")
    bwd = _pallas_eqn_compiler_params(
        lambda f, r, gg: rk._pallas_backward(
            f, r, gg, STRIDES, 7, 2, 2, True),
        feats, rois, g)
    _assert_vmem_limit(bwd, rk._SCOPED_VMEM_KIB)

    # the env override must flow through to the emitted kernels
    monkeypatch.setenv("EKSML_SCOPED_VMEM_KIB", "65536")
    fwd = _pallas_eqn_compiler_params(
        lambda f, r: rk._pallas_forward(f, r, STRIDES, 7, 2, 2, True),
        feats, rois)
    _assert_vmem_limit(fwd, 65536)
