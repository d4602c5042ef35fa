"""ROIAlign tests: analytic cases + numpy bilinear reference."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from eksml_tpu.ops import multilevel_roi_align, roi_align
from eksml_tpu.ops.roi_align import assign_fpn_levels, resample_masks


def _np_roi_align(feat, roi, scale, out, sr=2):
    """Direct numpy transliteration of aligned=True ROIAlign for 1 ROI."""
    H, W, C = feat.shape
    x1, y1, x2, y2 = [v * scale for v in roi]
    bw = max(x2 - x1, 1e-4) / out
    bh = max(y2 - y1, 1e-4) / out
    res = np.zeros((out, out, C), np.float32)
    for by in range(out):
        for bx in range(out):
            acc = np.zeros(C, np.float32)
            for iy in range(sr):
                for ix in range(sr):
                    y = y1 - 0.5 + (by + (iy + 0.5) / sr) * bh
                    x = x1 - 0.5 + (bx + (ix + 0.5) / sr) * bw
                    y0, x0 = int(np.floor(y)), int(np.floor(x))
                    ly, lx = y - y0, x - x0
                    for (yy, xx, w) in [(y0, x0, (1 - ly) * (1 - lx)),
                                        (y0, x0 + 1, (1 - ly) * lx),
                                        (y0 + 1, x0, ly * (1 - lx)),
                                        (y0 + 1, x0 + 1, ly * lx)]:
                        if 0 <= yy < H and 0 <= xx < W:
                            acc += feat[yy, xx] * w
            res[by, bx] = acc / (sr * sr)
    return res


def test_roi_align_matches_numpy():
    feat = np.random.rand(16, 16, 3).astype(np.float32)
    rois = np.asarray([[4.0, 4.0, 28.0, 20.0],
                       [0.0, 0.0, 32.0, 32.0],
                       [10.0, 6.0, 14.0, 30.0]], np.float32)
    got = np.asarray(roi_align(jnp.asarray(feat), jnp.asarray(rois),
                               spatial_scale=0.5, out_size=4))
    for i, roi in enumerate(rois):
        ref = _np_roi_align(feat, roi, 0.5, 4)
        np.testing.assert_allclose(got[i], ref, atol=1e-4)


def test_roi_align_constant_feature():
    feat = jnp.full((8, 8, 1), 7.0)
    rois = jnp.asarray([[1.0, 1.0, 6.0, 6.0]])
    out = np.asarray(roi_align(feat, rois, 1.0, 2))
    np.testing.assert_allclose(out, 7.0, atol=1e-5)


def test_assign_fpn_levels():
    rois = jnp.asarray([
        [0, 0, 32, 32],      # small → P2
        [0, 0, 112, 112],    # → P3
        [0, 0, 224, 224],    # canonical → P4
        [0, 0, 448, 448],    # → P5
        [0, 0, 2000, 2000],  # huge → clipped at P5
    ], dtype=jnp.float32)
    lvls = np.asarray(assign_fpn_levels(rois))
    np.testing.assert_array_equal(lvls, [2, 3, 4, 5, 5])


def test_multilevel_matches_single_level():
    """A ROI assigned to level l must produce exactly the single-level
    result on that level's feature."""
    strides = [4, 8, 16, 32]
    H = 64
    feats = [np.random.rand(H // s, H // s, 2).astype(np.float32)
             for s in strides]
    roi = np.asarray([[8.0, 8.0, 40.0, 40.0]], np.float32)  # 32px → P2
    got = np.asarray(multilevel_roi_align(
        [jnp.asarray(f) for f in feats], jnp.asarray(roi), strides, 4))
    ref = np.asarray(roi_align(jnp.asarray(feats[0]), jnp.asarray(roi),
                               1.0 / strides[0], 4))
    np.testing.assert_allclose(got, ref, atol=1e-5)


def test_roi_chunking_identical_values_and_grads(monkeypatch):
    """The lax.map ROI chunking (added after the round-3 bench OOMed on
    the backward's 4×1.5 GB [N,out,s,out,s,C] temps) must be a pure
    memory optimization: outputs AND feature gradients bit-comparable
    to the unchunked formulation, including when N is not a multiple of
    the bound (largest-divisor fallback) and when N is prime (no
    chunking possible)."""
    import importlib

    import jax

    # the package __init__ re-exports the roi_align FUNCTION under the
    # same name, shadowing attribute-style module import
    ra = importlib.import_module("eksml_tpu.ops.roi_align")

    strides = [4, 8, 16, 32]
    H = 64
    rng = np.random.RandomState(0)
    feats = tuple(jnp.asarray(rng.rand(H // s, H // s, 2)
                              .astype(np.float32)) for s in strides)
    for n in (12, 10, 7):  # 12 → chunk 4, 10 → chunk 2(divisor), 7 → off
        rois = jnp.asarray(
            np.concatenate([rng.rand(n, 2) * 20,
                            20 + rng.rand(n, 2) * 40], axis=1)
            .astype(np.float32))

        def run():
            out, vjp = jax.vjp(
                lambda fs: ra.multilevel_roi_align(fs, rois, strides, 4),
                feats)
            (gf,) = vjp(jnp.ones_like(out))
            return np.asarray(out), [np.asarray(g) for g in gf]

        monkeypatch.setattr(ra, "_ROI_CHUNK", 0)   # chunking off
        ref_out, ref_g = run()
        monkeypatch.setattr(ra, "_ROI_CHUNK", 4)
        got_out, got_g = run()
        np.testing.assert_allclose(got_out, ref_out, atol=1e-6)
        for a, b in zip(got_g, ref_g):
            np.testing.assert_allclose(a, b, atol=1e-6)


def test_roi_chunking_prime_n_warns(monkeypatch, caplog):
    """ADVICE r3: when chunking is requested but N has no divisor in
    the bound (prime N from a config override), silently reinstating
    the full gather temps is the exact round-3 OOM path — it must leave
    a runtime warning."""
    import importlib
    import logging

    ra = importlib.import_module("eksml_tpu.ops.roi_align")
    monkeypatch.setattr(ra, "_ROI_CHUNK", 128)
    with caplog.at_level(logging.WARNING,
                         logger="eksml_tpu.ops.roi_align"):
        assert ra._chunk_size(509) is None  # prime > bound
    assert any("UNCHUNKED" in r.message for r in caplog.records)
    caplog.clear()
    with caplog.at_level(logging.WARNING,
                         logger="eksml_tpu.ops.roi_align"):
        assert ra._chunk_size(512) == 128   # clean divisor: silent
        assert ra._chunk_size(64) is None   # within bound: silent
    assert not caplog.records


def test_bf16_features_keep_float32_coordinates():
    """bf16 has 8 significant bits: a sample coordinate near 300 on a
    336-wide level-0 map would land a pixel or two off, and the XLA
    formulation — the reference every kernel check compares against,
    and what every non-TPU bf16 run computes — was off by more than
    max|out| at the 1344 px canvas (first chip run, PR 21).  The ROI
    coordinates and tap weights stay float32; only values are bf16."""
    import numpy as np

    from eksml_tpu.ops.roi_align import batched_multilevel_roi_align

    rng = np.random.RandomState(0)
    img, strides = 1344, (4, 8, 16, 32)
    feats = tuple(jnp.asarray(rng.randn(1, img // s, img // s, 8),
                              jnp.bfloat16) for s in strides)
    side = np.exp(rng.uniform(np.log(16), np.log(img * 0.9), (1, 32, 2)))
    xy = rng.uniform(0, 1, (1, 32, 2)) * (img - 1 - side)
    rois = jnp.asarray(np.concatenate([xy, xy + side], -1), jnp.float32)
    out = batched_multilevel_roi_align(feats, rois, strides, 7)
    ref = batched_multilevel_roi_align(
        tuple(f.astype(jnp.float32) for f in feats), rois, strides, 7)
    assert out.dtype == jnp.bfloat16
    err = float(jnp.abs(out.astype(jnp.float32) - ref).max()
                / jnp.abs(ref).max())
    assert err < 2e-2, err


# ---- resample_masks: the matmul formulation against the gather one ----

_OUT = 28


def _disc_masks(rng, n, size):
    """0/1 discs of random centre and radius on a ``size``² grid."""
    yy, xx = np.mgrid[:size, :size]
    cy, cx = rng.rand(2, n, 1, 1) * size
    r = rng.rand(n, 1, 1) * size * 0.6 + 2
    return ((yy - cy) ** 2 + (xx - cx) ** 2 <= r ** 2).astype(np.float32)


def _mask_rois(kind, size, rng, n=64):
    """ROIs ``[n, 4]`` in the pixel frame of a ``size``² mask."""
    s = float(size)
    if kind == "inside":
        lo = rng.rand(n, 2) * s * 0.6
        wh = rng.rand(n, 2) * (s - lo - 1) + 1
        return np.concatenate([lo, lo + wh], 1)
    if kind.startswith("overhang"):
        rois = np.tile([s * 0.2, s * 0.25, s * 0.7, s * 0.8], (n, 1))
        col, sign = {"overhang_left": (0, -1), "overhang_top": (1, -1),
                     "overhang_right": (2, 1),
                     "overhang_bottom": (3, 1)}[kind]
        rois[:, col] = (s if sign > 0 else 0.0) + sign * rng.rand(n) * s
        return rois
    if kind == "zero_area":     # the 1e-4 floor on width and height
        p = rng.rand(n, 2) * s
        return np.concatenate([p, p], 1)
    if kind == "whole_frame":
        return np.tile([0.0, 0.0, s, s], (n, 1))
    assert kind == "quadrant"
    h = s / 2
    corners = np.asarray([[0, 0], [h, 0], [0, h], [h, h]])[np.arange(n) % 4]
    return np.concatenate([corners, corners + h], 1)


_KINDS = ("inside", "overhang_left", "overhang_top", "overhang_right",
          "overhang_bottom", "zero_area", "whole_frame", "quadrant")


@pytest.mark.parametrize("size", [56, 28])
@pytest.mark.parametrize("kind", _KINDS)
def test_resample_masks_matches_gather_roi_align(kind, size):
    """``resample_masks`` (Ry · M · Cxᵀ) is the gather ``roi_align`` on
    one single-channel map per ROI: within float32 summation order
    before the mask targets' 0.5 threshold, equal after it wherever the
    gather value is not at the threshold itself, and bit-equal where
    the ROI is the GT box (which the sampler feeds every step) or a
    quadrant of it: the tap weights are then dyadic, every sum is
    exact in either order, and at 56² → 28² the bin means are
    multiples of 0.25, so ``== 0.5`` is common."""
    rng = np.random.RandomState(_KINDS.index(kind) * 100 + size)
    rois = jnp.asarray(_mask_rois(kind, size, rng), jnp.float32)
    masks = jnp.asarray(_disc_masks(rng, rois.shape[0], size))

    def gather_one(mask, roi):
        return roi_align(mask[:, :, None], roi[None], 1.0, _OUT)[0, :, :, 0]

    want = np.asarray(jax.jit(jax.vmap(gather_one))(masks, rois))
    got = jax.jit(lambda m, r: resample_masks(m, r, _OUT))(masks, rois)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    got = np.asarray(got)
    assert want.max() > 0.5, "the case samples no mask at all"
    if kind in ("whole_frame", "quadrant"):
        np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    decided = np.abs(want - 0.5) > 1e-6
    np.testing.assert_array_equal((got >= 0.5)[decided],
                                  (want >= 0.5)[decided])
