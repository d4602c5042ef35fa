"""ROIAlign for TPU via vectorized bilinear gathers.

The reference relies on TF's CUDA CropAndResize/ROIAlign inside
TensorPack (base image container/Dockerfile:1).  On TPU there is no
cuDNN equivalent (SURVEY.md §7 hard part #2); this implementation uses
the gather/interpolation formulation:

- every ROI produces ``out_size × out_size`` bins with
  ``sampling_ratio²`` bilinear sample points each,
- all sample coordinates are computed in closed form → one big gather
  from the feature map + weighted sum, fully vectorized (no per-ROI
  loop, static shapes throughout),
- multi-level assignment (FPN) is done with a one-hot level mask and a
  weighted sum over levels, keeping shapes static at the cost of
  aligning each ROI on every level; the Pallas kernel in
  ``ops/pallas/roi_align_kernel.py`` removes that overhead on real
  hardware.

Semantics match Detectron2's ``aligned=True`` ROIAlign (half-pixel
offset), which is what modern Mask-RCNN implementations use.

Three formulations of those semantics, and when each runs:

- ``roi_align`` (gathers; ``multilevel_roi_align`` over FPN levels):
  feature maps with a channel axis, wherever ``dispatch_roi_align``
  cannot take the Pallas kernel (no TPU, or a canvas beyond the tile's
  coverage); the oracle of the kernel's and ``resample_masks``' tests.
- the Pallas kernel (``dispatch_roi_align`` on a TPU): the same feature
  maps, strips of the ROI's footprint by DMA and one MXU product a
  strip.
- ``resample_masks`` (two batched float32 matmuls, ``Ry · M · Cxᵀ``):
  one single-channel map per ROI, i.e. ``MaskRCNN._mask_targets``'
  ground-truth masks, on every backend.  With one channel a gather
  moves one-lane rows, which no backend does well; the weights are the
  kernel's separable ones, built from ``roi_align``'s own coordinates.
"""

from __future__ import annotations

import contextlib
import contextvars
import logging
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# The gather formulation materializes [N, out, s, out, s, C]
# intermediates (and their transposes in the backward) — at the
# optimized operating point (batch 4, 1344², 512 ROIs) that is 4×1.5 GB
# of f32 HLO temps, which overflowed the v5e's 15.75 GB HBM on the
# round-3 bench.  Processing ROIs in chunks through ``lax.map`` bounds
# the temps to a chunk's share while XLA's scan-transpose accumulates
# the feature gradient across chunks; outputs are bit-identical (each
# ROI's computation is independent).  (Tests set 0, the unchunked form,
# to compare with.)
_ROI_CHUNK = 128


def _chunk_size(n: int) -> int | None:
    """Largest divisor of ``n`` that is ≤ the chunk bound (static shape
    arithmetic — runs at trace time), or None when chunking is off or
    pointless (n within bound, or n prime).  The prime-N case is loud
    (ADVICE r3): silently reinstating the full [N,out,s,out,s,C] temps
    is how the round-3 HBM OOM happened, and a config override landing
    on e.g. 509 ROIs must leave a runtime signal."""
    c = _ROI_CHUNK
    if c <= 0 or n <= c:
        return None
    best = max(d for d in range(1, c + 1) if n % d == 0)
    if best <= 1:
        logging.getLogger(__name__).warning(
            "ROIAlign chunks of at most %d ROIs requested but %d "
            "ROIs has no divisor in (1, %d] — running UNCHUNKED; the "
            "full gather temps may OOM HBM at large canvases. Pick an "
            "ROI count with a divisor <= the bound (powers of two are "
            "safe).", c, n, c)
        return None
    return best


def _bilinear_gather(feat: jnp.ndarray, y: jnp.ndarray, x: jnp.ndarray):
    """Sample ``feat [H, W, C]`` at float32 coords ``y, x [...]`` with
    bilinear interpolation; out-of-range samples contribute 0 (matching
    ROIAlign's zero padding).  Coordinates and tap weights are float32
    whatever the feature dtype; only the finished weight is cast."""
    H, W = feat.shape[0], feat.shape[1]
    y0 = jnp.floor(y)
    x0 = jnp.floor(x)
    ly = y - y0
    lx = x - x0
    hy = 1.0 - ly
    hx = 1.0 - lx

    def tap(yi, xi, w):
        inb = (yi >= 0) & (yi <= H - 1) & (xi >= 0) & (xi <= W - 1)
        yc = jnp.clip(yi, 0, H - 1).astype(jnp.int32)
        xc = jnp.clip(xi, 0, W - 1).astype(jnp.int32)
        vals = feat[yc, xc]  # gather → [..., C]
        return vals * (w * inb).astype(feat.dtype)[..., None]

    return (tap(y0, x0, hy * hx) + tap(y0, x0 + 1, hy * lx)
            + tap(y0 + 1, x0, ly * hx) + tap(y0 + 1, x0 + 1, ly * lx))


def _sample_coords(lo: jnp.ndarray, extent: jnp.ndarray, out_size: int,
                   sampling_ratio: int) -> jnp.ndarray:
    """Sample coordinates along one axis, float32 ``[N, out, s]``:
    ``lo − 0.5 + (bin + (i + 0.5)/s) · bin_size`` (``aligned=True``,
    ``1e-4`` floor on the extent).  The one definition both
    formulations below sample at."""
    bin_size = jnp.maximum(extent, 1e-4) / out_size
    s = sampling_ratio
    # sample offsets within a bin: (i + 0.5)/s for i in [0, s)
    frac = (jnp.arange(s, dtype=jnp.float32) + 0.5) / s
    bins = jnp.arange(out_size, dtype=jnp.float32)
    return (lo[:, None, None] - 0.5
            + (bins[None, :, None] + frac[None, None, :])
            * bin_size[:, None, None])


def roi_align(feat: jnp.ndarray, rois: jnp.ndarray, spatial_scale: float,
              out_size: int, sampling_ratio: int = 2) -> jnp.ndarray:
    """ROIAlign on one level: feat ``[H, W, C]``, rois ``[N, 4]``
    (x1,y1,x2,y2 in image coords) → ``[N, out_size, out_size, C]``."""
    # float32 coordinates even for bf16 features: bf16's 8 significant
    # bits cannot place a sample on a 336-wide map (first chip run: the
    # bf16 formulation was off by more than max|out| at 1344 px)
    rois = rois.astype(jnp.float32) * spatial_scale
    x1, y1, x2, y2 = rois[:, 0], rois[:, 1], rois[:, 2], rois[:, 3]
    # y coords: [N, out, s] ; x coords: [N, out, s]
    ys = _sample_coords(y1, y2 - y1, out_size, sampling_ratio)
    xs = _sample_coords(x1, x2 - x1, out_size, sampling_ratio)
    # full sample grid [N, out, s, out, s]
    yy = ys[:, :, :, None, None]
    xx = xs[:, None, None, :, :]
    yy, xx = jnp.broadcast_arrays(yy, xx)
    vals = _bilinear_gather(feat, yy, xx)  # [N, out, s, out, s, C]
    return vals.mean(axis=(2, 4))  # average sample points → [N,out,out,C]


def _axis_weights(coords: jnp.ndarray, size: int) -> jnp.ndarray:
    """``[N, out, s]`` sample coordinates → ``[N, out, size]`` weights
    of a ``size``-long axis: each sample's two bilinear taps (``1 − l``
    at ``floor(c)``, ``l`` at ``floor(c) + 1``) are the hat
    ``max(0, 1 − |c − t|)`` over ``t = 0..size−1``, averaged over the
    bin's samples.  A tap outside the axis matches no column and so
    weighs 0: ``_bilinear_gather``'s in-bounds test, per axis."""
    taps = jnp.arange(size, dtype=jnp.float32)
    hat = jnp.maximum(0.0, 1.0 - jnp.abs(coords[..., None] - taps))
    return hat.mean(axis=2)


def resample_masks(masks: jnp.ndarray, rois: jnp.ndarray, out_size: int,
                   sampling_ratio: int = 2) -> jnp.ndarray:
    """ROIAlign of one single-channel map per ROI, on the MXU: masks
    ``[N, H, W]``, rois ``[N, 4]`` (x1,y1,x2,y2 in the mask's pixel
    coords) → float32 ``[N, out_size, out_size]``.

    Same samples and zero padding as ``roi_align(masks[n, :, :, None],
    rois[n:n+1], 1.0, out_size)``, but the bilinear weight of a sample
    factors into a row part and a column part, so the bin averages are
    ``Ry · M · Cxᵀ``: two batched matmuls instead of four gathers of
    one-lane rows (20.7 ms each at 4×128 ROIs on a v5e, PERF.md §6
    PR 26).  float32 throughout, ``Precision.HIGHEST``: at the TPU's
    default the weights would round to bfloat16 and pixels near the
    mask targets' 0.5 threshold would flip."""
    masks = masks.astype(jnp.float32)
    rois = rois.astype(jnp.float32)
    x1, y1, x2, y2 = rois[:, 0], rois[:, 1], rois[:, 2], rois[:, 3]
    _, h, w = masks.shape
    ry = _axis_weights(
        _sample_coords(y1, y2 - y1, out_size, sampling_ratio), h)
    cx = _axis_weights(
        _sample_coords(x1, x2 - x1, out_size, sampling_ratio), w)
    highest = jax.lax.Precision.HIGHEST
    rows = jnp.einsum("nih,nhw->niw", ry, masks, precision=highest)
    return jnp.einsum("niw,njw->nij", rows, cx, precision=highest)


def assign_fpn_levels(rois: jnp.ndarray, min_level: int = 2,
                      max_level: int = 5, canonical_size: float = 224.0,
                      canonical_level: int = 4) -> jnp.ndarray:
    """FPN heuristic level per ROI (int32 ``[N]``), k = k0 + log2(√area/224)."""
    w = jnp.maximum(rois[:, 2] - rois[:, 0], 0.0)
    h = jnp.maximum(rois[:, 3] - rois[:, 1], 0.0)
    scale = jnp.sqrt(jnp.maximum(w * h, 1e-8))
    lvl = jnp.floor(canonical_level + jnp.log2(scale / canonical_size + 1e-8))
    return jnp.clip(lvl, min_level, max_level).astype(jnp.int32)


def assign_fpn_levels_tile_fit(rois: jnp.ndarray, strides: Sequence[int],
                               num_levels: int, tile: int,
                               min_level: int = 2,
                               align: int = 8) -> jnp.ndarray:
    """Level *indices* (``[N]`` in ``[0, num_levels)``) for the Pallas
    tile kernel: the FPN heuristic, bumped to a coarser level whenever
    the ROI's extent at the assigned level would not fit in a
    ``tile × tile`` feature window (extreme aspect ratios).  Forward
    kernel and XLA backward both use this assignment so their values
    agree exactly.  Assumes FPN's ``strides[l] = strides[0] · 2^l``.

    ``align``: the kernel's sublane alignment for the feature dtype
    (8 for f32, 16 for bf16) — the tile x-origin is rounded down by up
    to align-1 px, shrinking the usable extent."""
    levels = assign_fpn_levels(
        rois, min_level=min_level,
        max_level=min_level + num_levels - 1) - min_level
    w = jnp.maximum(rois[:, 2] - rois[:, 0], 0.0)
    h = jnp.maximum(rois[:, 3] - rois[:, 1], 0.0)
    extent = jnp.maximum(jnp.maximum(w, h), 1e-4)
    # need extent/strides[l] ≤ tile - (2 bilinear taps + origin slack
    # + up to align-1 px of sublane round-down)
    usable = float(tile - 3 - (align - 1))
    need = jnp.ceil(jnp.log2(extent / (usable * strides[0])))
    levels = jnp.maximum(levels, need.astype(jnp.int32))
    return jnp.clip(levels, 0, num_levels - 1)


def multilevel_roi_align(feats: Sequence[jnp.ndarray], rois: jnp.ndarray,
                         strides: Sequence[int], out_size: int,
                         sampling_ratio: int = 2,
                         min_level: int = 2,
                         levels: jnp.ndarray | None = None) -> jnp.ndarray:
    """FPN ROIAlign: feats ``[(Hl, Wl, C), ...]`` for levels
    P_min..P_max, rois ``[N, 4]`` → ``[N, out, out, C]``.

    Static-shape strategy: align every ROI on every level, then select
    by one-hot level mask.  XLA fuses the weighted sum; the redundant
    levels are the price of shape stability (Pallas kernel removes it).

    ``levels``: optional explicit per-ROI level indices in
    ``[0, len(feats))`` — used by the Pallas backward so both passes
    share one assignment.
    """
    if levels is None:
        levels = assign_fpn_levels(
            rois, min_level=min_level,
            max_level=min_level + len(feats) - 1) - min_level
    n = rois.shape[0]
    c = _chunk_size(n)
    if c is not None:
        feats = tuple(feats)
        out = jax.lax.map(
            lambda rl: _multilevel_impl(feats, rl[0], strides, out_size,
                                        sampling_ratio, rl[1]),
            (rois.reshape(n // c, c, 4), levels.reshape(n // c, c)))
        return out.reshape(n, out_size, out_size, feats[0].shape[-1])
    return _multilevel_impl(feats, rois, strides, out_size,
                            sampling_ratio, levels)


def _multilevel_impl(feats, rois, strides, out_size, sampling_ratio,
                     levels):
    out = None
    for i, (feat, stride) in enumerate(zip(feats, strides)):
        mask = (levels == i).astype(feat.dtype)
        aligned = roi_align(feat, rois, 1.0 / stride, out_size, sampling_ratio)
        contrib = aligned * mask[:, None, None, None]
        out = contrib if out is None else out + contrib
    return out


def batched_multilevel_roi_align(feats, rois, strides, out_size,
                                 sampling_ratio: int = 2, min_level: int = 2,
                                 levels=None):
    """vmap over batch: feats ``[(B, Hl, Wl, C), ...]``, rois ``[B, N, 4]``."""
    if levels is None:
        fn = jax.vmap(
            lambda fs, r: multilevel_roi_align(fs, r, strides, out_size,
                                               sampling_ratio, min_level),
            in_axes=(0, 0))
        return fn(tuple(feats), rois)
    fn = jax.vmap(
        lambda fs, r, lv: multilevel_roi_align(fs, r, strides, out_size,
                                               sampling_ratio, min_level,
                                               levels=lv),
        in_axes=(0, 0, 0))
    return fn(tuple(feats), rois, levels)


# (mesh, batch axes) of the program being traced, set by
# ``ShardingPlan.jit`` — see ``batch_partition`` / ``_per_shard``
_BATCH_PARTITION = contextvars.ContextVar("eksml_batch_partition",
                                          default=None)


@contextlib.contextmanager
def batch_partition(mesh, batch_spec):
    """Declare, for the duration of a trace, the mesh and the axes the
    BATCH dimension is split over.  XLA's SPMD partitioner cannot split
    a Mosaic kernel ("Mosaic kernels cannot be automatically
    partitioned"), so on a multi-device mesh the kernel dispatch below
    needs to know how to run once per batch shard."""
    token = _BATCH_PARTITION.set((mesh, batch_spec[0]))
    try:
        yield
    finally:
        _BATCH_PARTITION.reset(token)


def _per_shard(kernel, num_levels: int):
    """``kernel(feats, rois)`` as one call per batch shard
    (``jax.shard_map`` over the declared batch axes; ROIAlign is
    independent per image, so this is exact).  Identity when no
    multi-device partition is declared."""
    declared = _BATCH_PARTITION.get()
    if declared is None or declared[0].size == 1:
        return kernel
    mesh, axes = declared
    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=((P(axes),) * num_levels, P(axes)),
        out_specs=P(axes), check_vma=False)


# "roi_align" scope → roi-fwd / roi-bwd (transpose context) in the
# profiling attribution (eksml_tpu/profiling SCOPE_RULES)
@jax.named_scope("roi_align")
def dispatch_roi_align(feats, rois, strides, out_size,
                       sampling_ratio: int = 2, min_level: int = 2):
    """Backend dispatch: the Pallas kernel on real TPU (the ROI's
    footprint on its assigned level read in 16×16 strips, one MXU
    product of pooled bilinear weights a strip, forward and backward:
    ops/pallas/roi_align_kernel.py), the XLA gather formulation
    elsewhere.

    Correctness guard: the strips cover at most a ``TILE``-wide window,
    which the tile-fit level assignment keeps every ROI inside by
    moving it to a coarser level; an ROI wider than that window's
    coverage at the COARSEST level — ``(TILE - margin) × strides[-1]``
    px, ~1696 (f32) / ~1440 (bf16) with TILE=64 — has no level left and
    would be silently truncated.  ROI extent is bounded by the (padded)
    image extent, so when the feature maps imply images beyond that
    bound, dispatch takes the XLA path."""
    from eksml_tpu.ops.pallas import (TILE,
                                      pallas_batched_multilevel_roi_align,
                                      pallas_roi_align_supported,
                                      sublane_align, tile_margin)

    dtype = feats[0].dtype
    img_extent = max(feats[0].shape[1], feats[0].shape[2]) * strides[0]
    coverage = (TILE - tile_margin(dtype)) * strides[-1]
    if img_extent <= coverage and pallas_roi_align_supported():
        def kernel(fs, r):
            return pallas_batched_multilevel_roi_align(
                fs, r, tuple(strides), out_size, sampling_ratio,
                min_level)

        return _per_shard(kernel, len(feats))(tuple(feats), rois)
    return batched_multilevel_roi_align(feats, rois, strides, out_size,
                                        sampling_ratio, min_level)
