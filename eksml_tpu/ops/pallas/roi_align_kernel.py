"""Pallas multilevel ROIAlign: strips of the ROI's footprint, one MXU
product a strip, channels in lanes — forward and backward.

Why a kernel (SURVEY.md §7 hard part #2): the XLA formulation in
ops/roi_align.py must align every ROI on every FPN level (one-hot
select keeps shapes static) and sample via gathers — 4× redundant work
on a gather path the TPU executes poorly.  These kernels:

- read (forward) or update (backward) the per-ROI *assigned* level
  only (the 4× back);
- move and multiply the ROI's FOOTPRINT, not a fixed tile: the rows
  ``floor(y1 − 0.5) .. floor(y2 − 0.5) + 1`` and the columns likewise,
  the ones that carry a non-zero bilinear weight, are covered by
  ``ny × nx ≤ 4 × 4`` strips of one fixed shape
  (``STRIP_H × STRIP_W × C``; DMA shapes are static), ``_strip_prep``'s
  cover.  One cover serves both directions; only the W alignment of the
  origin differs (``sublane_align(dtype)`` for the forward's strips,
  read in the features' own dtype; 8 for the backward's float32
  accumulators);
- replace gathers with ONE MXU product a strip.  Bilinear sampling and
  bin pooling are linear and separable, so a strip's contribution is
  ``out[(i j), c] += Σ_(y x) RyP[i, y] · CxP[j, x] · strip[(y x), c]``
  with the *pooled* two-tap weights
  (``RyP[i, t] = mean_a relu(1 − |y_(i,a) − t|)``, ``_pooled_weights``);
  the ``[out², STRIP_H·STRIP_W]`` weight matrix is the outer product of
  the two pooled vectors, built with iota arithmetic on the VPU.
  Channels stay in lanes on both sides: no transpose, no
  ``[S, T, C]`` intermediate;
- scalar-prefetch ALL per-ROI metadata — level/batch/strip origin and
  counts, and the float start/bin-size values — through SMEM.  (Putting
  the float info in a VMEM block would need a (1, 8) block shape, which
  Mosaic rejects: the second-to-last block dim must be a multiple
  of 8.)

The forward (``_fwd_kernel``) accumulates a ROI's strips in a float32
VMEM block and writes it to HBM once.  Its strips are read-only, so
there are no hazards: the next strip — the next ROI's first one too —
streams into the other slot while this one is multiplied, and a ROI's
result leaves by DMA while the next ROI is formed.  bf16 features are
exact in one bf16 pass, so only the float32 weights are split into
their three bf16 terms, each multiplied by the strip once
(``_strip_product``; what ``Precision.HIGHEST`` computes on the
up-cast strip, less the passes over its zero low parts); float32
features take ``Precision.HIGHEST`` itself.

Semantics notes:
- matches ``aligned=True`` ROIAlign with zero padding outside the
  image, PROVIDED each level's feature map is spatially padded to at
  least ``TILE`` (the caller pads; padding is zeros, which is exactly
  the zero-padding ROIAlign wants);
- level assignment is the shared tile-fit variant
  (``assign_fpn_levels_tile_fit`` at ``TILE``): ROIs whose extent would
  overflow a ``TILE``-wide window at the heuristic level are bumped to
  a coarser level.  ``TILE`` is thereby the bound of the cover (4 × 4
  strips always suffice) and part of the mathematics the benchmark's
  reference repeats (``roi_tile_usable``); both kernels read
  ``_prep``'s levels, so they compute the same linear map.

The backward wrt features is the TRANSPOSE of the same linear map,
accumulated into per-level f32 HBM buffers by sequential
read-modify-write DMA (the grid is sequential per core — no write
races; buffers start zeroed through ``input_output_aliases``):
``d[(y x), c] = Σ_(i j) RyP[i,y] · CxP[j,x] · g[(i j), c]`` a strip,
write-back asynchronous over two staging strips (``_bwd_kernel``).
``fwd_tile_share`` / ``bwd_tile_share`` are the shares of a
``TILE × TILE`` tile the strips of each direction cover, the step's
``roi_fwd_tile_share`` / ``roi_bwd_tile_share`` counters.  Whoever runs
the forward kernel runs the backward one (``_bwd``): there is no mixed
path.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

# T: the window the tile-fit level assignment fits every ROI into
# (√area/stride ≲ 56 + taps), hence the bound of the strips' cover
TILE = 64
# Both kernels move the level's map in strips of this one shape
# (rows × columns; DMA shapes are static); 4 × 4 of them cover a tile.
# Chosen on the chip (PERF.md §6, PR 29): the kernel's time is 0.9 µs a
# ROI + 0.5 µs a strip + 3.1 ns a strip pixel (the MXU), and 16 × 16
# gave the least on both ROI sets among 8×32, 16×16, 16×32, 32×32, 8×64.
STRIP_H, STRIP_W = 16, 16
# W origin of a backward strip: the f32 accumulators' sublane tile,
# whatever the features' dtype (a forward strip is read in the
# features' dtype, so its origin follows ``sublane_align``)
_BWD_ALIGN = 8

# Mosaic's default per-kernel scoped-vmem stack is 16 MiB, and a
# production call whose chunk of the output (forward) or of the
# incoming gradient (backward) XLA keeps vmem-resident beside the
# kernel's own scratch has overflowed it (round 5: 160 KiB over, a hard
# compile reject).  v5e/v6e have 128 MiB of vmem per core; a 32 MiB
# stack is comfortably safe.  The limit rides inside every Mosaic custom call
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
    of the sampling semantics; both kernels contract its bin-pooled
    mean (``_pooled_weights``), so any change here keeps fwd/bwd
    transposed by construction."""
    bins = jnp.floor(s_idx / sampling)
    off = (s_idx - bins * sampling + 0.5) / sampling
    coord = start + (bins + off) * binsz
    return jnp.maximum(0.0, 1.0 - jnp.abs(coord - t_idx))


def _pooled_weights(start, binsz, bin_idx, t_idx, sampling: int):
    """Weight of feature position ``t_idx`` in output bin ``bin_idx``:
    the mean of the bin's ``sampling`` tap weights (pooling is linear,
    so it folds into the weights)."""
    return sum(
        _tap_weights(start, binsz, bin_idx * sampling + a, t_idx, sampling)
        for a in range(sampling)) / sampling


def _strip_product(weights, strip):
    """``weights [M, K] (float32) · strip [K, C]`` accumulated in
    float32, to float32 rounding.  A bf16 strip is exact in one bf16
    pass, so only the weights are split into their three bf16 terms
    (3 × 8 significand bits = float32's 24), stacked down ``M`` so the
    strip is latched in the MXU once: ``Precision.HIGHEST`` on the
    up-cast strip computes the same three products plus three more with
    the strip's zero low parts.  Other dtypes take ``HIGHEST``."""
    f32 = jnp.float32
    if strip.dtype != jnp.bfloat16:
        return jnp.dot(weights, strip.astype(f32),
                       preferred_element_type=f32,
                       precision=jax.lax.Precision.HIGHEST)
    terms, rest = [], weights
    for _ in range(3):
        terms.append(rest.astype(jnp.bfloat16))
        rest = rest - terms[-1].astype(f32)
    m = weights.shape[0]
    prod = jnp.dot(jnp.concatenate(terms, axis=0), strip,
                   preferred_element_type=f32)
    # smallest first: the low terms' sum is rounded once into the high
    return (prod[2 * m:] + prod[m:2 * m]) + prod[:m]


def _block_rows(out_size: int) -> int:
    """Rows of a ROI's ``[(i j), C]`` block: ``out²`` padded to the
    bf16 sublane tile, so the forward's result, its three stacked
    weight terms and its HBM image are tile-aligned in either dtype."""
    return -(-out_size * out_size // 16) * 16


def _fwd_kernel(out_size: int, sampling: int, num_levels: int, align: int,
                # scalar prefetch (SMEM), one entry per ROI:
                lvl_ref, b_ref, ya_ref, xa_ref,   # level/batch/strip origin
                ny_ref, nx_ref,                   # strips down / across
                ys_ref, xs_ref, bh_ref, bw_ref,   # f32 start/bin size
                *refs):
    """ROI r's ``ny × nx`` strips (``_strip_prep``, W origin aligned to
    ``align``) are read from its level by DMA in the features' dtype
    and each adds one MXU product to the ROI's float32 block:
    ``acc[(i j), c] += Σ_(y x) RyP[i, y]·CxP[j, x] · strip[(y x), c]``
    — the backward's product the other way round, with the weight
    matrix built ``[(i j), (y x)]`` so that the contraction runs over
    its lanes and channels stay in lanes on the other side.

    Nothing is written to the maps, so reads never wait for anything:
    while strip s is multiplied, strip s+1 — the next ROI's first strip
    after this ROI's last — streams into the other slot (``state[0]``
    counts strips issued and picks the slot).  A ROI's block is cast to
    the features' dtype into the result block and leaves by DMA while
    the next ROI's strips are multiplied; that write is waited before
    the block is refilled, a whole ROI later, and the last grid step
    waits for its own.  Every DMA of a kind moves one byte count, so
    waits are issued against any descriptor of the kind (a wait is
    semaphore + byte-count accounting, not an address match)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    feat_refs = refs[:num_levels]          # HBM [B, Hp, Wp, C] each
    out_ref = refs[num_levels]             # HBM [N, rows, C]
    strips, in_sems, acc_ref, res_ref, out_sem, state = \
        refs[num_levels + 1:]

    f32 = jnp.float32
    rows, c = acc_ref.shape
    px = STRIP_H * STRIP_W
    r = pl.program_id(0)
    n = pl.num_programs(0)

    def window(feat, bb, y, x):
        return feat.at[bb, pl.ds(y, STRIP_H), pl.ds(x, STRIP_W), :]

    def start_read(idx, ry, cx, slot):
        lv = lvl_ref[idx]
        bb = b_ref[idx]
        y = ya_ref[idx] + ry * STRIP_H
        # the origin arrives as a block count: a product with the
        # dtype's sublane alignment lets Mosaic PROVE the W-dim slice
        # is aligned (an SMEM value alone is unprovable)
        x = (xa_ref[idx] + cx * (STRIP_W // align)) * align
        for i in range(num_levels):
            @pl.when(lv == i)
            def _(i=i):
                pltpu.make_async_copy(window(feat_refs[i], bb, y, x),
                                      strips.at[slot],
                                      in_sems.at[slot]).start()

    def wait_read(slot):
        pltpu.make_async_copy(window(feat_refs[0], 0, 0, 0),
                              strips.at[slot], in_sems.at[slot]).wait()

    @pl.when(r == 0)
    def _():
        state[0] = 0
        start_read(0, 0, 0, 0)

    ny = ny_ref[r]
    nx = nx_ref[r]
    nxt = jnp.minimum(r + 1, n - 1)

    # [(i j), (y x)] index grids (Mosaic's iota is integer-only)
    q = jax.lax.broadcasted_iota(jnp.int32, (rows, px), 0).astype(f32)
    p = jax.lax.broadcasted_iota(jnp.int32, (rows, px), 1).astype(f32)
    i_idx = jnp.floor((q + 0.5) / out_size)
    j_idx = q - i_idx * out_size
    y_idx = jnp.floor((p + 0.5) / STRIP_W)
    x_idx = p - y_idx * STRIP_W
    y_start = ys_ref[r]
    x_start = xs_ref[r]
    bin_h = bh_ref[r]
    bin_w = bw_ref[r]

    acc_ref[...] = jnp.zeros((rows, c), f32)

    def column(cx, issued):
        cxp = _pooled_weights(x_start, bin_w, j_idx,
                              x_idx + (cx * STRIP_W).astype(f32), sampling)

        def add_strip(ry, issued):
            slot = issued % 2
            more_down = ry + 1 < ny
            more_across = cx + 1 < nx
            mine = more_down | more_across

            @pl.when(mine | (r + 1 < n))
            def _():
                start_read(jnp.where(mine, r, nxt),
                           jnp.where(more_down, ry + 1, 0),
                           jnp.where(more_down, cx,
                                     jnp.where(more_across, cx + 1, 0)),
                           1 - slot)

            ryp = _pooled_weights(y_start, bin_h, i_idx,
                                  y_idx + (ry * STRIP_H).astype(f32),
                                  sampling)
            wait_read(slot)
            acc_ref[...] += _strip_product(
                ryp * cxp, strips[slot].reshape(px, c))
            return issued + 1

        return jax.lax.fori_loop(0, ny, add_strip, issued)

    state[0] = jax.lax.fori_loop(0, nx, column, state[0])

    # The output buffer is pinned to HBM and written by explicit DMA.
    # A windowed VMEM out_spec let XLA choose the buffer's home — and on
    # hardware it greedily packed pallas outputs into scoped vmem until
    # the kernel's own stack allocation failed, at ANY limit (round 5).
    # Explicit HBM removes the choice.
    def write(op):
        op(pltpu.make_async_copy(res_ref, out_ref.at[r], out_sem))

    @pl.when(r >= 1)
    def _():
        write(lambda d: d.wait())    # the previous ROI's, a ROI ago

    res_ref[...] = acc_ref[...].astype(res_ref.dtype)
    write(lambda d: d.start())

    @pl.when(r == n - 1)
    def _():
        write(lambda d: d.wait())


def _bwd_kernel(out_size: int, sampling: int, num_levels: int,
                # scalar prefetch (SMEM), one entry per ROI:
                lvl_ref, b_ref, ya_ref, xa_ref,   # level/batch/strip origin
                ny_ref, nx_ref,                   # strips down / across
                ys_ref, xs_ref, bh_ref, bw_ref,   # f32 start/bin size
                *refs):
    """Transpose of ``_fwd_kernel``, over the same footprint: the
    rows and columns of the level's map that carry a non-zero weight
    are covered by ``ny × nx`` strips of ONE shape
    ``[STRIP_H, STRIP_W, C]`` (``_strip_prep``), and each strip of the
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
    xa = xa_ref[r] * _BWD_ALIGN             # see _fwd_kernel: provable
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
        return _pooled_weights(start, binsz, bin_idx, t_idx, sampling)

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
    the clamped origin of the ``TILE``-wide window that bounds the ROI,
    window-local sample-start coordinates — the source of
    ``_strip_prep``'s cover."""
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


def _strip_prep(feats, rois, strides, out_size, min_level, align,
                w_align):
    """``_prep``'s levels, sample starts and bin sizes, and the cover of
    the ROI's FOOTPRINT by ``ny × nx`` strips of ``[STRIP_H, STRIP_W]``
    — the one cover both kernels walk.  A row carries a non-zero weight
    from ``floor(y1 − 0.5)`` (the first sample's upper tap) to
    ``floor(y2 − 0.5) + 1`` (the last sample's lower tap), columns
    likewise; the column origin is rounded down to ``w_align`` (the
    sublane tile of what the strips hold: ``sublane_align(dtype)`` for
    the forward's features, ``_BWD_ALIGN`` for the backward's float32
    accumulators) and shipped as a block count, and both origins are
    pulled in so the last strip ends inside the padded map.  The
    tile-fit level assignment bounds the footprint by the tile less
    ``tile_margin`` (46 usable pixels + 3 of taps + up to 15 of
    round-down in bf16), so at most ``TILE/STRIP_H × TILE/STRIP_W``
    strips are ever needed."""
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
                       w_align)
    return (levels, batch_idx, ya, xa // w_align, ny, nx,
            ys, xs, bin_h, bin_w)


def _tile_share(feats, rois, strides, out_size, min_level, w_align):
    """Mean over ROIs of the pixels a kernel moves (its strips, W origin
    aligned to ``w_align``) over those of a ``TILE × TILE`` tile, from
    ``_strip_prep``'s own strip counts.  A pure function of the ROIs
    and the levels' shapes: 1.0 when every ROI fills its tile."""
    align = sublane_align(feats[0].dtype)
    padded = jax.eval_shape(lambda fs: _pad_levels(fs, align), list(feats))
    prep = _strip_prep(padded, rois, strides, out_size, min_level, align,
                       w_align)
    strips = (prep[4] * prep[5]).astype(jnp.float32)
    return strips.mean() * (STRIP_H * STRIP_W / (TILE * TILE))


def fwd_tile_share(feats, rois, strides, out_size: int = 7,
                   min_level: int = 2):
    """``_tile_share`` of the forward kernel's strips, read in the
    features' dtype."""
    return _tile_share(feats, rois, strides, out_size, min_level,
                       sublane_align(feats[0].dtype))


def bwd_tile_share(feats, rois, strides, out_size: int = 7,
                   min_level: int = 2):
    """``_tile_share`` of the backward kernel's strips of its float32
    accumulators."""
    return _tile_share(feats, rois, strides, out_size, min_level,
                       _BWD_ALIGN)


def _pad_levels(feats, align):
    """Zero-pad each level's spatial dims to ≥ TILE, and W additionally
    to a multiple of ``align`` so a strip origin pulled in from the
    right edge stays sublane-aligned (zero padding IS ROIAlign's
    out-of-image semantics, so this is free correctness)."""
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
# bf16[128,14,14,256] output (12.85 MiB) + the kernel's scratch of the
# time overflowed the limit by 160 KiB and Mosaic rejected the
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
    The per-ROI size is that of a ``[(i j), C]`` block in its TILED
    layout (``_block_rows``: 49→64 / 196→208) — the forward's output and
    the backward's incoming gradient as XLA would actually pack them."""
    per_roi = _block_rows(out_size) * c * jnp.dtype(dtype).itemsize
    room = max(_VMEM_STACK_BUDGET - scratch_bytes, per_roi)
    bound = max(room // per_roi, 1)
    if n_total <= bound:
        return n_total
    return max(d for d in range(1, int(bound) + 1) if n_total % d == 0)


def _fwd_scratch_bytes(out_size: int, c: int, dtype) -> int:
    """The forward kernel's own vmem: two strips and the result block
    in the features' dtype, the float32 block, and what one strip's
    product holds at once (its weight terms, ``STRIP_H·STRIP_W`` lanes
    wide, and the ``[3·rows, C]`` product of the stacked terms)."""
    esize = jnp.dtype(dtype).itemsize
    rows, px = _block_rows(out_size), STRIP_H * STRIP_W
    return (2 * px * c * esize + rows * c * esize + rows * c * 4
            + 3 * rows * (px + c) * 4)


def _pallas_forward(feats, rois, strides, out_size, sampling, min_level,
                    interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dtype = feats[0].dtype
    align = sublane_align(dtype)
    feats = _pad_levels(feats, align)
    b, n = rois.shape[0], rois.shape[1]
    c = feats[0].shape[-1]
    scalars = _strip_prep(feats, rois, strides, out_size, min_level, align,
                          align)
    num_levels = len(feats)
    kern = functools.partial(_fwd_kernel, out_size, sampling, num_levels,
                             align)
    rows = _block_rows(out_size)
    chunk = _roi_chunk(b * n, out_size, c, dtype,
                       _fwd_scratch_bytes(out_size, c, dtype))

    def call(chunk_scalars, n_rois):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=10,
            grid=(n_rois,),
            # unwindowed HBM refs: Mosaic DMAs explicitly, and the
            # buffers stay off the kernel's scoped-vmem stack UNLESS
            # XLA elects to place them there — chunking bounds each
            # call's output so that even a packed chunk fits the
            # raised 32 MiB limit alongside the kernel's scratch
            in_specs=[pl.BlockSpec(memory_space=pltpu.HBM)] * num_levels,
            out_specs=pl.BlockSpec(memory_space=pltpu.HBM),
            scratch_shapes=[
                pltpu.VMEM((2, STRIP_H, STRIP_W, c), dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((rows, c), jnp.float32),
                pltpu.VMEM((rows, c), dtype),
                pltpu.SemaphoreType.DMA(()),
                pltpu.SMEM((1,), jnp.int32),
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
            out_shape=jax.ShapeDtypeStruct((n_rois, rows, c), dtype),
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
    # the pad rows of the (i j) axis ride along to HBM (the DMA moves
    # full tile-aligned extents) and are sliced off here
    return out[:, :out_size * out_size, :].reshape(
        b, n, out_size, out_size, c)


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
    scalars = _strip_prep(padded, rois, strides, out_size, min_level,
                          align, _BWD_ALIGN)
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
