"""Pallas multilevel ROIAlign: per-ROI tile DMA + separable matmuls.

Why a kernel (SURVEY.md §7 hard part #2): the XLA formulation in
ops/roi_align.py must align every ROI on every FPN level (one-hot
select keeps shapes static) and sample via gathers — 4× redundant work
on a gather path the TPU executes poorly.  This kernel:

- reads the per-ROI *assigned* level only (the 4× back);
- replaces gathers with two MXU matmuls per ROI: bilinear
  interpolation is separable, so sampling is
  ``Ry @ tile @ Cx`` with ``Ry[s,t] = relu(1 - |y_s - t|)``
  (row weights) and ``Cx`` likewise for columns — exactly the 2-tap
  bilinear weights, built with iota arithmetic on the VPU;
- DMAs one fixed ``T×T×C`` feature tile per ROI from HBM (grid is
  sequential per core, so no write races), scalar-prefetching ALL
  per-ROI metadata — level/batch/origin indices and the float
  start/bin-size values — through SMEM.  (Putting the float info in a
  VMEM block would need a (1, 8) block shape, which Mosaic rejects:
  the second-to-last block dim must be a multiple of 8.)  Tile fetch
  is DOUBLE-BUFFERED: ROI r+1's tile streams into the other slot while
  ROI r's matmuls run, so the 2-4 MB/ROI DMA overlaps compute.

Semantics notes:
- matches ``aligned=True`` ROIAlign with zero padding outside the
  image, PROVIDED each level's feature map is spatially padded to at
  least ``T`` (the caller pads; padding is zeros, which is exactly the
  zero-padding ROIAlign wants);
- level assignment is the shared tile-fit variant
  (``assign_fpn_levels_tile_fit``): ROIs whose extent would overflow
  the tile at the heuristic level are bumped to a coarser level, so
  the forward and backward kernels (both read ``_prep``'s levels)
  compute the same linear map — no silent fwd/bwd divergence for
  extreme aspect ratios.

The backward wrt features is the TRANSPOSE of the same separable
linear map, accumulated into per-level f32 HBM buffers by sequential
read-modify-write DMA (the grid is sequential per core — no write
races; buffers start zeroed through ``input_output_aliases``).  It
moves and multiplies the ROI's FOOTPRINT, not the tile: the rows and
columns that carry a non-zero weight are covered by strips of one
fixed shape (``STRIP_H × STRIP_W``, ``_bwd_prep``; an ROI that fills
the tile still gets all of it), and a strip's update is one MXU
product with channels in lanes, ``d[(y x), c] = Σ_(i j) RyP[i,y] ·
CxP[j,x] · g[(i j), c]`` with the *pooled* weights
(``RyP[i,t] = mean_a Ry[i·s+a, t]``; pooling is linear so it folds
into the weights) — no scatter, no transpose.  Write-back is
asynchronous over two staging strips (``_bwd_kernel``).
``bwd_tile_share`` is the share of the tile the strips cover, the
step's ``roi_bwd_tile_share`` counter.  Whoever runs the forward
kernel runs this one (``_bwd``): there is no mixed path.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

TILE = 64  # T: per-ROI feature tile (covers √area/stride ≲ 56 + taps)
# The backward moves its f32 accumulators in strips of this one shape
# (rows × columns; DMA shapes are static); 4 × 4 of them cover a tile.
# Chosen on the chip (PERF.md §6, PR 29): the kernel's time is 0.9 µs a
# ROI + 0.5 µs a strip + 3.1 ns a strip pixel (the MXU), and 16 × 16
# gave the least on both ROI sets among 8×32, 16×16, 16×32, 32×32, 8×64.
STRIP_H, STRIP_W = 16, 16
# W origin of a strip: the f32 accumulators' sublane tile, whatever the
# features' dtype (the forward's origin follows ``sublane_align``)
_BWD_ALIGN = 8

# Mosaic's default per-kernel scoped-vmem stack is 16 MiB, and the
# production mask-head call (double-buffered 64×64×256 tile scratch +
# vmem-resident output) needs ~16.16 MiB — 160 KiB over, a hard compile
# reject.  v5e/v6e have 128 MiB of vmem per core; a 32 MiB stack is
# comfortably safe.  The limit rides inside every Mosaic custom call
# (``_compiler_params``), so no process-wide libtpu flag is needed.
_SCOPED_VMEM_KIB = 32768


def _compiler_params():
    """Per-kernel Mosaic params carrying the scoped-vmem stack limit
    IN the compiled module.  The ONE construction site for the limit."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        vmem_limit_bytes=_SCOPED_VMEM_KIB * 1024)


def sublane_align(dtype) -> int:
    """Mosaic's second-to-last-dim tiling for HBM memrefs: 8 sublanes
    × (32 / itemsize) packing — f32 tiles (8, 128), bf16 (16, 128).
    Dynamic W-origin slices must be provably aligned to this."""
    return 8 * (4 // np.dtype(dtype).itemsize)


def tile_margin(dtype) -> int:
    """Tile pixels unusable for ROI extent: 2 bilinear taps + origin
    slack (3) plus up to align-1 of origin round-down."""
    return 3 + sublane_align(dtype) - 1


def pallas_roi_align_supported() -> bool:
    """The kernel gate, decidable BEFORE anything compiles: the kernels
    (forward and backward together) exactly when the default backend is
    a TPU.  A kernel the compiler or the runtime refuses raises from
    the caller's own compile with the compiler's message — nothing
    substitutes the XLA formulation after a failure."""
    return jax.default_backend() == "tpu"


def _tap_weights(start, binsz, s_idx, t_idx, sampling: int):
    """Two-tap bilinear weight of feature position ``t_idx`` for sample
    ``s_idx`` (float arrays of one shape), sample coords
    ``start + (bin + (j+0.5)/sampling) * binsz`` — the ONE definition
    of the sampling semantics; forward contracts it directly, backward
    uses its bin-pooled mean.  Any change here keeps fwd/bwd transposed
    by construction."""
    bins = jnp.floor(s_idx / sampling)
    off = (s_idx - bins * sampling + 0.5) / sampling
    coord = start + (bins + off) * binsz
    return jnp.maximum(0.0, 1.0 - jnp.abs(coord - t_idx))


def _bilinear_weights(start, binsz, out_size: int, sampling: int):
    """[S, T] weight matrix of the forward: samples down, tile
    positions across."""
    s_total = out_size * sampling
    f32 = jnp.float32
    # Mosaic's iota is integer-only; build int32 and convert
    s_idx = jax.lax.broadcasted_iota(
        jnp.int32, (s_total, TILE), 0).astype(f32)
    t_idx = jax.lax.broadcasted_iota(
        jnp.int32, (s_total, TILE), 1).astype(f32)
    return _tap_weights(start, binsz, s_idx, t_idx, sampling)


def _kernel(out_size: int, sampling: int, num_levels: int, align: int,
            # scalar prefetch (SMEM), one entry per ROI:
            lvl_ref, b_ref, y0_ref, x0_ref,   # int32 level/batch/origin
            ys_ref, xs_ref, bh_ref, bw_ref,   # f32 tile-local start/bin
            *refs):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    feat_refs = refs[:num_levels]          # HBM [B, Hp, Wp, C] each
    out_ref = refs[num_levels]             # HBM [N, out, out_pad, C]
    tiles_ref = refs[num_levels + 1]       # VMEM scratch [2, T, T, C]
    sems = refs[num_levels + 2]            # DMA semaphores (2,)
    res_ref = refs[num_levels + 3]         # VMEM scratch [1, out, pad, C]
    out_sem = refs[num_levels + 4]         # DMA semaphore

    r = pl.program_id(0)
    n = pl.num_programs(0)

    # Double-buffered tile fetch: while ROI r's matmuls run, ROI r+1's
    # tile streams into the other slot — the per-ROI DMA (4 MB f32 /
    # 2 MB bf16) stops serializing with compute.  Slot parity keeps the
    # in-flight DMA and the live compute on different buffers; the grid
    # is sequential per core, so step r's body starts only after step
    # r-1's compute retired.
    def _dma(slot, idx, op):
        lv = lvl_ref[idx]
        bb = b_ref[idx]
        yy = y0_ref[idx]
        # x0 arrives as a sublane-block count; multiplying by the
        # dtype's sublane alignment (8 for f32 tiles (8,128), 16 for
        # bf16 (16,128)) here lets Mosaic PROVE the W-dim slice origin
        # is aligned (its HBM-slice tiling requirement — an SMEM value
        # alone is unprovable)
        xx = x0_ref[idx] * align
        for i in range(num_levels):
            @pl.when(lv == i)
            def _(i=i):
                op(pltpu.make_async_copy(
                    feat_refs[i].at[bb, pl.ds(yy, TILE),
                                    pl.ds(xx, TILE), :],
                    tiles_ref.at[slot], sems.at[slot]))

    @pl.when(r == 0)
    def _():
        _dma(0, 0, lambda d: d.start())

    @pl.when(r + 1 < n)
    def _():
        _dma((r + 1) % 2, r + 1, lambda d: d.start())

    _dma(r % 2, r, lambda d: d.wait())
    tile_ref = tiles_ref.at[r % 2]

    y_start = ys_ref[r]
    x_start = xs_ref[r]
    bin_h = bh_ref[r]
    bin_w = bw_ref[r]

    ry = _bilinear_weights(y_start, bin_h, out_size, sampling)  # [S, T]
    cx = _bilinear_weights(x_start, bin_w, out_size, sampling)  # [S, T]
    f32 = jnp.float32
    s_total = out_size * sampling

    tile = tile_ref[:].astype(f32)                  # [T, T, C]
    c = tile.shape[-1]
    # rows: [S, T] @ [T, T*C] → [S, T, C].  HIGHEST precision: the MXU
    # multiplies in bf16 passes; one-pass (default) loses ~2^-8 relative
    # accuracy vs the XLA gather formulation.
    rows = jnp.dot(ry, tile.reshape(TILE, TILE * c),
                   preferred_element_type=f32,
                   precision=jax.lax.Precision.HIGHEST
                   ).reshape(s_total, TILE, c)
    # cols: contract T with cx → [S, S, C]
    sampled = jax.lax.dot_general(
        rows, cx.T,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=f32,
        precision=jax.lax.Precision.HIGHEST)        # [S, C, S]
    sampled = sampled.transpose(0, 2, 1)            # [S, S, C]
    pooled = sampled.reshape(out_size, sampling, out_size, sampling,
                             c).mean(axis=(1, 3))
    # The output buffer is pinned to HBM and written by explicit DMA
    # (~100 KB/ROI, negligible next to the matmuls).  A windowed VMEM
    # out_spec let XLA choose the buffer's home — and on hardware it
    # greedily packed pallas outputs into scoped vmem until the
    # kernel's own stack allocation failed, at ANY limit (16 MiB
    # default and the raised 32 MiB both died with the same ~156 KiB
    # overshoot, round 5).  Explicit HBM removes the choice.
    # The DMA must move full tile-aligned extents: the buffer's W dim
    # is padded to the sublane tile (7→8, 14→16) and the pad columns
    # ride along (sliced off at the XLA level after the call).
    pad_w = res_ref.shape[2] - out_size
    if pad_w:
        pooled = jnp.pad(pooled, ((0, 0), (0, pad_w), (0, 0)))
    res_ref[0] = pooled.astype(res_ref.dtype)
    copy = pltpu.make_async_copy(res_ref, out_ref.at[pl.ds(r, 1)],
                                 out_sem)
    copy.start()
    copy.wait()


def _bwd_kernel(out_size: int, sampling: int, num_levels: int,
                # scalar prefetch (SMEM), one entry per ROI:
                lvl_ref, b_ref, ya_ref, xa_ref,   # level/batch/strip origin
                ny_ref, nx_ref,                   # strips down / across
                ys_ref, xs_ref, bh_ref, bw_ref,   # f32 start/bin size
                *refs):
    """Transpose of ``_kernel``, over the ROI's footprint only: the
    rows and columns of the level's map that carry a non-zero weight
    are covered by ``ny × nx`` strips of ONE shape
    ``[STRIP_H, STRIP_W, C]`` (``_bwd_prep``), and each strip of the
    f32 accumulator is read, updated and written back by DMA.

    A strip's update is one MXU product with channels in lanes on both
    sides: ``d[(y x), c] = Σ_(i j) RyP[i, y]·CxP[j, x] · g[(i j), c]``.
    The ``[STRIP_H·STRIP_W, out²]`` weight matrix is the outer product
    of the two pooled ``_tap_weights`` vectors, built on the VPU; the
    product streams one LHS row per strip pixel, so the MXU's time
    follows the footprint too.  (The separable order — columns, then
    rows — streams ``STRIP_W·C`` rows of a 7-long contraction per
    strip; that, not the DMA, was the 64×64 kernel's 26 of 31 µs.)

    The write-back is asynchronous over two staging slots: strip s's
    write stays in flight while strip s+1 is read and formed.
    Bookkeeping in SMEM (``state``: one pending flag per slot, and the
    count of strips issued, which picks the slot):

    - a slot is drained (its write of two strips ago waited) before it
      is refilled, so the only write in flight during a read is the
      previous strip's;
    - strips of one ROI are disjoint; the previous strip can overlap
      only across an ROI boundary, so at an ROI's start both slots are
      drained when its strips' bounding box meets the previous ROI's
      (same level and image) — RAW and WAW safe;
    - the final grid step drains everything.

    Every DMA moves one strip's byte count, so waits are issued
    against a fixed level-0 descriptor — a DMA wait is semaphore +
    byte-count accounting, not an address match."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g_ref = refs[0]                         # VMEM [1, out*out, C]
    # refs[1 : 1+L] are the zero-initialized ANY inputs aliased to the
    # outputs — unused directly; the RMW goes through the out refs
    acc_refs = refs[1 + num_levels: 1 + 2 * num_levels]  # ANY outputs
    strips, in_sem, out_sem, state = refs[1 + 2 * num_levels:]

    f32 = jnp.float32
    oo = out_size * out_size
    r = pl.program_id(0)
    lvl = lvl_ref[r]
    b = b_ref[r]
    ya = ya_ref[r]
    xa = xa_ref[r] * _BWD_ALIGN             # see _kernel: provable align
    ny = ny_ref[r]
    nx = nx_ref[r]

    @pl.when(r == 0)
    def _():
        state[0] = 0
        state[1] = 0
        state[2] = 0

    def on_level(op):
        for i in range(num_levels):
            pl.when(lvl == i)(functools.partial(op, acc_refs[i]))

    def window(acc, bb, y, x):
        return acc.at[bb, pl.ds(y, STRIP_H), pl.ds(x, STRIP_W), :]

    fixed = window(acc_refs[0], 0, 0, 0)

    def drain(s, cond):
        # All SMEM flag accesses use STATIC indices (slot-parity
        # branches); a dynamically-indexed SMEM store is an unproven
        # Mosaic construct.
        @pl.when(cond & (state[s] == 1))
        def _():
            pltpu.make_async_copy(strips.at[s], fixed,
                                  out_sem.at[s]).wait()
            state[s] = 0

    rp = jnp.maximum(r - 1, 0)
    yp = ya_ref[rp]
    xp = xa_ref[rp] * _BWD_ALIGN
    clash = ((r >= 1) & (lvl_ref[rp] == lvl) & (b_ref[rp] == b)
             & (ya < yp + ny_ref[rp] * STRIP_H)
             & (yp < ya + ny * STRIP_H)
             & (xa < xp + nx_ref[rp] * STRIP_W)
             & (xp < xa + nx * STRIP_W))
    drain(0, clash)
    drain(1, clash)

    def pooled(start, binsz, t0, t_axis, size):
        """Bin-pooled tap weights of strip positions ``t0 .. t0+size``
        (down ``t_axis`` of a ``[STRIP_H|1, STRIP_W|1, out²]`` array)
        for the output bins of the other axis's flattened ``(i j)``
        index: ``i = q // out`` for rows, ``j = q % out`` for columns."""
        shape = [1, 1, oo]
        shape[t_axis] = size
        t_idx = (jax.lax.broadcasted_iota(jnp.int32, shape, t_axis)
                 + t0).astype(f32)
        q = jax.lax.broadcasted_iota(jnp.int32, shape, 2).astype(f32)
        i_idx = jnp.floor((q + 0.5) / out_size)
        bin_idx = i_idx if t_axis == 0 else q - i_idx * out_size
        return sum(
            _tap_weights(start, binsz, bin_idx * sampling + a, t_idx,
                         sampling)
            for a in range(sampling)) / sampling

    g = g_ref[0].astype(f32)                                # [oo, C]
    c = g.shape[-1]
    y_start = ys_ref[r]
    x_start = xs_ref[r]
    bin_h = bh_ref[r]
    bin_w = bw_ref[r]

    def column(cx, issued):
        x = xa + cx * STRIP_W
        cxp = pooled(x_start, bin_w, cx * STRIP_W, 1, STRIP_W)

        def update_strip(ry, issued):
            slot = issued % 2
            y = ya + ry * STRIP_H
            # slot reuse: drain the write issued two strips ago
            drain(0, slot == 0)
            drain(1, slot == 1)
            on_level(lambda acc: pltpu.make_async_copy(
                window(acc, b, y, x), strips.at[slot], in_sem).start())
            ryp = pooled(y_start, bin_h, ry * STRIP_H, 0, STRIP_H)
            # HIGHEST precision: the MXU multiplies in bf16 passes
            d = jnp.dot((ryp * cxp).reshape(STRIP_H * STRIP_W, oo), g,
                        preferred_element_type=f32,
                        precision=jax.lax.Precision.HIGHEST)
            pltpu.make_async_copy(fixed, strips.at[slot], in_sem).wait()
            strips[slot] = strips[slot] + d.reshape(STRIP_H, STRIP_W, c)
            on_level(lambda acc: pltpu.make_async_copy(
                strips.at[slot], window(acc, b, y, x),
                out_sem.at[slot]).start())
            for s in range(2):
                @pl.when(slot == s)
                def _(s=s):
                    state[s] = 1
            return issued + 1

        return jax.lax.fori_loop(0, ny, update_strip, issued)

    state[2] = jax.lax.fori_loop(0, nx, column, state[2])

    last = r == pl.num_programs(0) - 1
    drain(0, last)
    drain(1, last)


def _prep(feats, rois, strides, out_size, min_level, align):
    """Host-side (traced) index/weight prep: tile-fit level assignment,
    clamped tile origins, tile-local sample-start coordinates."""
    from eksml_tpu.ops.roi_align import assign_fpn_levels_tile_fit

    b, n = rois.shape[0], rois.shape[1]
    flat = rois.reshape(b * n, 4)
    levels = assign_fpn_levels_tile_fit(
        flat, strides, len(feats), TILE, min_level=min_level,
        align=align)  # [BN] in [0,L)
    batch_idx = jnp.repeat(jnp.arange(b, dtype=jnp.int32), n)

    inv_strides = jnp.asarray([1.0 / s for s in strides], jnp.float32)
    scale = inv_strides[levels]                              # [BN]
    x1 = flat[:, 0] * scale
    y1 = flat[:, 1] * scale
    x2 = flat[:, 2] * scale
    y2 = flat[:, 3] * scale
    bin_h = jnp.maximum(y2 - y1, 1e-4) / out_size
    bin_w = jnp.maximum(x2 - x1, 1e-4) / out_size

    h_pad = jnp.asarray([f.shape[1] for f in feats], jnp.int32)[levels]
    w_pad = jnp.asarray([f.shape[2] for f in feats], jnp.int32)[levels]
    # aligned=True: samples start at y1 - 0.5; tile origin 1 tap early.
    # The x origin is additionally rounded DOWN to the dtype's sublane
    # alignment and shipped as a block count (Mosaic requires a provably
    # aligned W-dim HBM slice; _pad_levels makes w_pad ≡ 0 mod align so
    # the clamp bound is itself aligned and right-edge coverage
    # survives).
    y0 = jnp.clip(jnp.floor(y1 - 1.5).astype(jnp.int32), 0,
                  jnp.maximum(h_pad - TILE, 0))
    x0 = jnp.clip(jnp.floor(x1 - 1.5).astype(jnp.int32), 0,
                  jnp.maximum(w_pad - TILE, 0)) // align * align

    ys = y1 - 0.5 - y0.astype(jnp.float32)
    xs = x1 - 0.5 - x0.astype(jnp.float32)
    return (levels.astype(jnp.int32), batch_idx, y0, x0 // align,
            ys, xs, bin_h, bin_w)


def _bwd_prep(feats, rois, strides, out_size, min_level, align):
    """The backward's twin of ``_prep``: same levels, sample starts and
    bin sizes, but instead of one tile origin the cover of the ROI's
    FOOTPRINT by ``ny × nx`` strips of ``[STRIP_H, STRIP_W]``.  A row
    carries a non-zero weight from ``floor(y1 − 0.5)`` (the first
    sample's upper tap) to ``floor(y2 − 0.5) + 1`` (the last sample's
    lower tap), columns likewise; the column origin is rounded down to
    ``_BWD_ALIGN`` and shipped as a block count, and both origins are
    pulled in so the last strip ends inside the padded map.  The
    tile-fit level assignment bounds the footprint by the tile, so at
    most ``TILE/STRIP_H × TILE/STRIP_W`` strips are ever needed."""
    levels, batch_idx, y0, x0, ys, xs, bin_h, bin_w = _prep(
        feats, rois, strides, out_size, min_level, align)
    shapes = jnp.asarray([f.shape[1:3] for f in feats], jnp.int32)[levels]

    def cover(origin, start, binsz, size, strip, align_to):
        lo = origin.astype(jnp.float32) + start      # y1 − 0.5 on the map
        first = jnp.clip(jnp.floor(lo).astype(jnp.int32), 0, size - 1)
        last = jnp.clip(
            jnp.floor(lo + out_size * binsz).astype(jnp.int32) + 1,
            first, size - 1)
        first = first // align_to * align_to
        count = jnp.clip((last - first) // strip + 1, 1, TILE // strip)
        first = jnp.minimum(first, size - count * strip)
        return first, count, lo - first.astype(jnp.float32)

    ya, ny, ys = cover(y0, ys, bin_h, shapes[:, 0], STRIP_H, 1)
    xa, nx, xs = cover(x0 * align, xs, bin_w, shapes[:, 1], STRIP_W,
                       _BWD_ALIGN)
    return (levels, batch_idx, ya, xa // _BWD_ALIGN, ny, nx,
            ys, xs, bin_h, bin_w)


def bwd_tile_share(feats, rois, strides, out_size: int = 7,
                   min_level: int = 2):
    """Mean over ROIs of the accumulator bytes the backward kernel
    moves (its strips) over the bytes of a ``TILE × TILE`` tile, from
    ``_bwd_prep``'s own strip counts.  A pure function of the ROIs and
    the levels' shapes: 1.0 when every ROI fills its tile."""
    align = sublane_align(feats[0].dtype)
    padded = jax.eval_shape(lambda fs: _pad_levels(fs, align), list(feats))
    prep = _bwd_prep(padded, rois, strides, out_size, min_level, align)
    strips = (prep[4] * prep[5]).astype(jnp.float32)
    return strips.mean() * (STRIP_H * STRIP_W / (TILE * TILE))


def _pad_levels(feats, align):
    """Zero-pad each level's spatial dims to ≥ TILE, and W additionally
    to a multiple of ``align`` so the clamped tile x-origin stays
    sublane-aligned (zero padding IS ROIAlign's out-of-image semantics,
    so this is free correctness)."""
    out = []
    for f in feats:
        _, h, w, _ = f.shape
        ph = max(TILE - h, 0)
        pw = max(TILE - w, 0) or (-w % align)
        if ph or pw:
            f = jnp.pad(f, ((0, 0), (0, ph), (0, pw), (0, 0)))
        out.append(f)
    return out


# Mosaic's per-kernel scoped-vmem stack is 16 MiB: when XLA elects to
# keep a pallas output (or operand) resident in vmem, the WHOLE buffer
# counts against the kernel's stack, not just the windowed block.  The
# round-5 hardware compile proved it: the mask head's full
# bf16[128,14,14,256] output (12.85 MiB) + the double-buffered tile
# scratch overflowed the limit by 160 KiB and Mosaic rejected the
# kernel.  The fix is static shape arithmetic, not a probe: chunk the
# ROI grid so worst-case (full output vmem-resident + scratch +
# headroom) provably fits.
_VMEM_STACK_BUDGET = 13 * 2 ** 20   # leave ~3 MiB for spills/semaphores


def _roi_chunk(n_total: int, out_size: int, c: int, dtype,
               scratch_bytes: int) -> int:
    """Largest divisor of ``n_total`` whose per-call stack estimate
    (chunk's output + kernel scratch) fits the scoped-vmem budget
    (module-level ``_VMEM_STACK_BUDGET``, read at call time so tests
    can monkeypatch it).
    The per-ROI size uses the TILED output layout (W padded to the
    sublane tile, 7→8 / 14→16) — the buffer XLA would actually pack."""
    esize = jnp.dtype(dtype).itemsize
    out_pad = out_size + (-out_size % 8)
    per_roi = out_size * out_pad * c * esize
    room = max(_VMEM_STACK_BUDGET - scratch_bytes, per_roi)
    bound = max(room // per_roi, 1)
    if n_total <= bound:
        return n_total
    return max(d for d in range(1, int(bound) + 1) if n_total % d == 0)


def _pallas_forward(feats, rois, strides, out_size, sampling, min_level,
                    interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    align = sublane_align(feats[0].dtype)
    feats = _pad_levels(feats, align)
    b, n = rois.shape[0], rois.shape[1]
    c = feats[0].shape[-1]
    scalars = _prep(feats, rois, strides, out_size, min_level, align)
    num_levels = len(feats)
    kern = functools.partial(_kernel, out_size, sampling, num_levels,
                             align)

    esize = jnp.dtype(feats[0].dtype).itemsize
    out_pad = out_size + (-out_size % 8)
    # tile double-buffer + the per-ROI result staging block
    scratch_bytes = (2 * TILE * TILE + out_size * out_pad) * c * esize
    chunk = _roi_chunk(b * n, out_size, c, feats[0].dtype, scratch_bytes)

    def call(chunk_scalars, n_rois):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=8,
            grid=(n_rois,),
            # unwindowed HBM refs: Mosaic DMAs explicitly, and the
            # buffers stay off the kernel's scoped-vmem stack UNLESS
            # XLA elects to place them there — chunking bounds each
            # call's output so that even a packed chunk fits the
            # raised 32 MiB limit alongside the tile scratch
            in_specs=[pl.BlockSpec(memory_space=pltpu.HBM)] * num_levels,
            out_specs=pl.BlockSpec(memory_space=pltpu.HBM),
            scratch_shapes=[
                pltpu.VMEM((2, TILE, TILE, c), feats[0].dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((1, out_size, out_pad, c), feats[0].dtype),
                pltpu.SemaphoreType.DMA(()),
            ],
        )
        # no output coloring here: with ROI chunking bounding the
        # output and the 32 MiB scoped limit, worst-case packing
        # (chunk output + feats + scratch) stays well under the limit,
        # and leaving XLA free to keep small outputs vmem-resident is
        # measurably faster (18.8 vs 16.4 img/s at 512px/b4)
        return pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(
                (n_rois, out_size, out_pad, c), feats[0].dtype),
            compiler_params=_compiler_params(),
            interpret=interpret,
            name="roi_align_fwd",
        )(*chunk_scalars, *feats)

    if chunk == b * n:
        out = call(scalars, b * n)
    else:
        out = jnp.concatenate([
            call(tuple(s[i:i + chunk] for s in scalars), chunk)
            for i in range(0, b * n, chunk)], axis=0)
    return out[:, :, :out_size, :].reshape(b, n, out_size, out_size, c)


def _hbm_out(shape, dtype):
    """out_shape entry that pins the output buffer to HBM.  A MemoryRef
    out_shape flows an annotated aval into the pallas_call params (the
    lowering reads them into the custom call's output_memory_colors)
    while the primitive's abstract eval strips the annotation from the
    OUTWARD aval — so placement is constrained without annotated avals
    leaking into downstream jax ops (which reject them).  This is the
    output-side twin of with_memory_space_constraint, and together
    they close the round-5 hardware failure: XLA packing pallas
    outputs/aliased seeds into scoped vmem until the Mosaic kernel
    stack overflowed (at the 16 MiB default and 32 MiB alike)."""
    from jax._src import core as jax_core
    from jax._src.pallas.core import MemoryRef
    from jax._src.pallas.mosaic.core import MemorySpace

    return MemoryRef(jax_core.ShapedArray(shape, dtype),
                     MemorySpace.HBM)


def _to_hbm(x):
    """Materialize ``x`` in an HBM-pinned buffer via a whole-buffer DMA
    copy kernel.  Output coloring is the one placement constraint this
    XLA revision demonstrably honors (S(1) vanished from colored
    outputs on hardware); INPUT colors on must-alias operands are
    ignored when the operand is a vmem-placed fusion (a jnp.zeros
    broadcast), which is exactly how the backward's aliased gradient
    accumulators ended up on the Mosaic stack.  Copying through this
    kernel launders the buffer into HBM so everything downstream that
    aliases it inherits the placement.  Stack-safe: the kernel has no
    vmem scratch, so even a vmem-placed INPUT (≤ the scoped limit by
    definition) still compiles."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def k(in_ref, out_ref, sem):
        copy = pltpu.make_async_copy(in_ref, out_ref, sem)
        copy.start()
        copy.wait()

    return pl.pallas_call(
        k,
        in_specs=[pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.HBM),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        out_shape=_hbm_out(x.shape, x.dtype),
        # a >16 MiB input XLA elects to keep vmem-resident must not
        # bust THIS kernel's stack check either
        compiler_params=_compiler_params(),
        name="roi_align_seed_copy",
    )(x)


def _bwd_scratch_bytes(out_size: int, c: int) -> int:
    """The backward kernel's own vmem: two staging strips, and the two
    values a strip's update holds at once (its ``[strip, out²]`` weight
    matrix, lanes padded to 128, and the ``[strip, C]`` product)."""
    strip = STRIP_H * STRIP_W * 4
    lanes = -(-out_size * out_size // 128) * 128
    return strip * (3 * c + lanes)


def _pallas_backward(feats, rois, g, strides, out_size, sampling,
                     min_level, interpret):
    """Per-level feature gradients via the transpose kernel.  Returns
    gradients in the feats' dtype (accumulation runs in f32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    align = sublane_align(feats[0].dtype)
    padded = _pad_levels(feats, align)
    b, n = rois.shape[0], rois.shape[1]
    c = padded[0].shape[-1]
    scalars = _bwd_prep(padded, rois, strides, out_size, min_level, align)
    num_levels = len(padded)
    kern = functools.partial(_bwd_kernel, out_size, sampling,
                             num_levels)

    # (i j) flattened on the XLA side: the kernel contracts it whole
    g_flat = g.reshape(b * n, out_size * out_size, c)

    # De-cluster the grid order: accumulation is order-independent, so
    # walk ROIs by a fixed coprime stride (golden-ratio spacing).
    # Consecutive proposals/fg-ROIs are spatially CLUSTERED (score
    # order; objects), which is exactly when the async write-back's
    # RAW-hazard drain must serialize — a stride walk makes adjacent
    # grid steps land on unrelated strips so the pipeline actually
    # overlaps.
    bn = b * n
    if bn > 2:
        from math import gcd

        stride = max(2, round(bn * 0.618))
        while gcd(stride, bn) != 1:
            stride += 1
        # host-side int64: i*stride overflows int32 past bn ≈ 58k ROIs
        # and the "bijection" would silently drop/double-count
        # gradients; numpy folds this to a constant
        perm = jnp.asarray(
            (np.arange(bn, dtype=np.int64) * stride) % bn, jnp.int32)
        scalars = tuple(x[perm] for x in scalars)
        g_flat = g_flat[perm]

    # Same scoped-vmem stack bound as the forward, from the other side:
    # the incoming gradient is this kernel's big windowed buffer, and
    # XLA electing to keep it vmem-resident would put all b·n ROIs of
    # it on the Mosaic stack.  Chunk the ROI grid and CHAIN the calls
    # through the aliased accumulators — each call RMWs the previous
    # call's partial feature gradients, so memory stays bounded and no
    # extra adds are emitted.
    chunk = _roi_chunk(b * n, out_size, c, g_flat.dtype,
                       _bwd_scratch_bytes(out_size, c))

    def call(chunk_scalars, g_chunk, accs, n_rois):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=10,
            grid=(n_rois,),
            in_specs=[pl.BlockSpec((1, out_size * out_size, c),
                                   lambda r, *_: (r, 0, 0),
                                   memory_space=pltpu.VMEM)]
            + [pl.BlockSpec(memory_space=pltpu.HBM)] * num_levels,
            # the f32 feature-grad accumulators are the BIG buffers
            # ([B,128,128,256] = 16.8 MiB at 512px/b4): on hardware
            # XLA packed them into scoped vmem as S(1) tuple elements
            # and broke the compile at any limit (round-5 convergence
            # run).  BlockSpec memory_space alone does NOT constrain
            # XLA's buffer placement — the with_memory_space_constraint
            # on the aliased inputs below is what pins them to HBM.
            out_specs=[pl.BlockSpec(memory_space=pltpu.HBM)] * num_levels,
            scratch_shapes=[
                pltpu.VMEM((2, STRIP_H, STRIP_W, c), jnp.float32),
                pltpu.SemaphoreType.DMA(()),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((3,), jnp.int32)],
        )
        out_shape = tuple(
            _hbm_out(f.shape, jnp.float32) if pinned[i]
            else jax.ShapeDtypeStruct(f.shape, jnp.float32)
            for i, f in enumerate(padded))
        return pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=out_shape,
            # accumulator i (flat arg index 10 scalars + 1 g + i) owns
            # output buffer i: the kernel RMWs it through the out refs
            input_output_aliases={11 + i: i for i in range(num_levels)},
            compiler_params=_compiler_params(),
            interpret=interpret,
            name="roi_align_bwd",
        )(*chunk_scalars, g_chunk, *accs)

    # Pin the LARGEST accumulator levels to HBM (colored out avals +
    # laundered zero seeds) and leave the rest eligible for XLA's
    # vmem packing.  Both directions matter, measured on v5e:
    # vmem-resident accumulators make the kernel's per-ROI RMW tiles
    # vmem-local (pinning everything costs ~12% step time at
    # 512px/b4), while unpinned-large is the round-5 compile failure
    # (XLA vmem-placed the zeros broadcasts and the aliased chain
    # dragged 29 MiB onto the Mosaic stack).  The budgets below keep
    # the unpinned sum small enough that unpinned + g-chunk + tile
    # scratch (two strips, ~1 MiB) fits the 32 MiB limit the RMW
    # kernel declares, even if XLA packs every unpinned buffer.
    sizes = [int(np.prod(f.shape)) * 4 for f in padded]
    pinned = [False] * num_levels
    if not interpret:
        limit = _SCOPED_VMEM_KIB * 1024
        if jnp.dtype(feats[0].dtype) == jnp.float32:
            # f32 graphs carry double-size temps everywhere and the
            # packer runs much hotter (the round-5 f32 convergence
            # compile failed at every looser setting tried on
            # hardware): pin largest-first until the unpinned sum is
            # small — compile safety over RMW locality
            order = sorted(range(num_levels), key=lambda i: -sizes[i])
            remaining = sum(sizes)
            for i in order:
                if remaining <= 12 * 2 ** 20:
                    break
                pinned[i] = True
                remaining -= sizes[i]
        else:
            # bf16 production path: walk fine→coarse keeping levels
            # vmem-eligible — level 0 carries most ROIs (FPN sends
            # small objects to the finest level) and its residency
            # buys the most RMW locality (17.9 vs 16.3 img/s at
            # 512px/b4 on v5e); a level that cannot fit the scoped
            # limit at all is left unpinned for free
            kept = 0
            budget = min(18 * 2 ** 20, limit - 14 * 2 ** 20)
            for i in range(num_levels):
                if sizes[i] >= limit:
                    continue
                if kept + sizes[i] <= budget:
                    kept += sizes[i]
                else:
                    pinned[i] = True

    outs = tuple(jnp.zeros(f.shape, jnp.float32) for f in padded)
    outs = tuple(_to_hbm(o) if pinned[i] else o
                 for i, o in enumerate(outs))
    for i in range(0, b * n, chunk):
        outs = call(tuple(s[i:i + chunk] for s in scalars),
                    g_flat[i:i + chunk], outs, chunk)
    return tuple(
        o[:, :f.shape[1], :f.shape[2], :].astype(f.dtype)
        for o, f in zip(outs, feats))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def pallas_batched_multilevel_roi_align(
        feats, rois, strides: Sequence[int], out_size: int,
        sampling_ratio: int = 2, min_level: int = 2,
        interpret: bool = False):
    """Drop-in for ops.roi_align.batched_multilevel_roi_align:
    feats ``[(B, Hl, Wl, C), ...]``, rois ``[B, N, 4]`` →
    ``[B, N, out, out, C]``.  Pallas forward; the backward is the
    transpose Pallas kernel (``_bwd``)."""
    return _pallas_forward(tuple(feats), rois, strides, out_size,
                           sampling_ratio, min_level, interpret)


def _fwd(feats, rois, strides, out_size, sampling_ratio, min_level,
         interpret):
    out = _pallas_forward(tuple(feats), rois, strides, out_size,
                          sampling_ratio, min_level, interpret)
    return out, (tuple(feats), rois)


def _bwd(strides, out_size, sampling_ratio, min_level, interpret, res, g):
    """Backward: the transpose Pallas kernel (one MXU product a strip +
    sequential RMW accumulation, no scatter), with the SAME tile-fit
    level assignment as the forward kernel, so fwd/bwd never diverge."""
    feats, rois = res
    g_feats = _pallas_backward(feats, rois, g, strides, out_size,
                               sampling_ratio, min_level, interpret)
    return g_feats, jnp.zeros_like(rois)


pallas_batched_multilevel_roi_align.defvjp(_fwd, _bwd)
