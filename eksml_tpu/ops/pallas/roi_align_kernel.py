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
  the forward kernel and the XLA backward (which receives the SAME
  levels) compute identical values — no silent fwd/bwd divergence for
  extreme aspect ratios.

The backward wrt features is the TRANSPOSE of the same separable
linear map, so it is also two MXU matmuls per ROI — no scatter at all:
``d_tile = RyPᵀ @ g @ CxP`` with the *pooled* weight matrices
(``RyP[i,t] = mean_a Ry[i·s+a, t]``; pooling is linear so it folds into
the weights), accumulated into per-level HBM buffers via sequential
read-modify-write DMA (the grid is sequential per core — no write
races; buffers start zeroed through ``input_output_aliases``).
``EKSML_ROI_BWD={auto,pallas,xla}`` selects it (auto = the kernel on
a TPU backend, the XLA gather-transpose formulation via
``jax.custom_vjp`` elsewhere).
"""

from __future__ import annotations

import functools
import os
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

TILE = 64  # T: per-ROI feature tile (covers √area/stride ≲ 56 + taps)

# Mosaic's default per-kernel scoped-vmem stack is 16 MiB, and the
# production mask-head call (double-buffered 64×64×256 tile scratch +
# vmem-resident output) needs ~16.16 MiB — 160 KiB over, a hard compile
# reject.  v5e/v6e have 128 MiB of vmem per core; a 32 MiB stack is
# comfortably safe.  The limit rides inside every Mosaic custom call
# (``_compiler_params``), so no process-wide libtpu flag is needed.
_SCOPED_VMEM_KIB = 32768


def _scoped_vmem_kib() -> int:
    """The ONE read point for the EKSML_SCOPED_VMEM_KIB override, read
    at trace time: the value is baked into the jitted program and keyed
    into the persistent compile cache, so set it before the first
    compile."""
    return int(os.environ.get("EKSML_SCOPED_VMEM_KIB",
                              str(_SCOPED_VMEM_KIB)))


def _compiler_params(extra_bytes: int = 0):
    """Per-kernel Mosaic params carrying the scoped-vmem stack limit
    IN the compiled module.  The ONE construction site for the limit:
    callers whose kernel carries extra scratch (the bwd overlap
    pipeline) declare it here."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        vmem_limit_bytes=_scoped_vmem_kib() * 1024 + extra_bytes)


def sublane_align(dtype) -> int:
    """Mosaic's second-to-last-dim tiling for HBM memrefs: 8 sublanes
    × (32 / itemsize) packing — f32 tiles (8, 128), bf16 (16, 128).
    Dynamic W-origin slices must be provably aligned to this."""
    return 8 * (4 // np.dtype(dtype).itemsize)


def tile_margin(dtype) -> int:
    """Tile pixels unusable for ROI extent: 2 bilinear taps + origin
    slack (3) plus up to align-1 of origin round-down."""
    return 3 + sublane_align(dtype) - 1


def _use_kernel(env_var: str) -> bool:
    """Kernel gate, decidable BEFORE anything compiles: the explicit
    ``xla`` / ``pallas`` setting wins; ``auto`` means the kernel exactly
    when the default backend is a TPU.  A kernel the compiler or the
    runtime refuses raises from the caller's own compile with the
    compiler's message — nothing substitutes the XLA formulation after
    a failure."""
    mode = os.environ.get(env_var, "auto").lower()
    if mode not in ("auto", "pallas", "xla"):
        raise ValueError(f"{env_var}={mode!r}: expected auto, pallas "
                         "or xla")
    if mode == "auto":
        return jax.default_backend() == "tpu"
    return mode == "pallas"


def pallas_roi_align_supported() -> bool:
    """True when the forward kernel path should be used
    (``EKSML_ROI_BACKEND={auto,pallas,xla}`` — the A/B switch bench.py
    exposes as ``--roi-backend``)."""
    return _use_kernel("EKSML_ROI_BACKEND")


def _bilinear_weights(start, binsz, out_size: int, sampling: int):
    """[S, T] two-tap bilinear weight matrix for sample coords
    ``start + (bin + (j+0.5)/sampling) * binsz`` — the ONE definition
    of the sampling semantics; forward contracts it directly, backward
    uses its bin-pooled mean.  Any change here keeps fwd/bwd transposed
    by construction."""
    s_total = out_size * sampling
    f32 = jnp.float32
    # Mosaic's iota is integer-only; build int32 and convert
    s_idx = jax.lax.broadcasted_iota(
        jnp.int32, (s_total, TILE), 0).astype(f32)
    t_idx = jax.lax.broadcasted_iota(
        jnp.int32, (s_total, TILE), 1).astype(f32)
    bins = jnp.floor(s_idx / sampling)
    off = (s_idx - bins * sampling + 0.5) / sampling
    coord = start + (bins + off) * binsz
    return jnp.maximum(0.0, 1.0 - jnp.abs(coord - t_idx))


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
                align: int, overlap: bool,
                # scalar prefetch (SMEM), one entry per ROI:
                lvl_ref, b_ref, y0_ref, x0_ref,
                ys_ref, xs_ref, bh_ref, bw_ref,
                *refs):
    """Transpose of ``_kernel``: d_tile = RyPᵀ @ g @ CxP, accumulated
    into the per-level gradient buffer by RMW DMA.

    With ``overlap=True`` the write-back is ASYNC: ROI r's out-DMA
    stays in flight while ROI r+1's tile read and matmuls run (the RMW
    moves 2×4 MiB per ROI at TILE=64/C=256/f32 — fully serialized
    read→compute→write was the measured bwd bottleneck at 1344 px).
    Correctness bookkeeping, all in SMEM scalar flags:

    - two staging slots (``acc_tile[2]``), so the in-flight write's
      buffer is never the one being refilled;
    - a RAW-hazard drain: if ROI r's tile REGION (level, batch, y/x
      origin within TILE) can overlap ROI r-1's, the previous write is
      waited before r's read — overlapping writes are thereby also
      ordered (WAW safe);
    - slot reuse drains the write issued two steps ago, and the final
      grid step drains everything.

    Every out-DMA moves the same [T,T,C] f32 byte count, so waits are
    issued against a fixed level-0 region descriptor — a DMA wait is
    semaphore + byte-count accounting, not an address match."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g_ref = refs[0]                         # VMEM [1, out, out, C]
    # refs[1 : 1+L] are the zero-initialized ANY inputs aliased to the
    # outputs — unused directly; the RMW goes through the out refs
    acc_refs = refs[1 + num_levels: 1 + 2 * num_levels]  # ANY outputs
    if overlap:
        acc_tile = refs[1 + 2 * num_levels]   # VMEM [2, T, T, C] f32
        in_sem = refs[1 + 2 * num_levels + 1]
        out_sem = refs[1 + 2 * num_levels + 2]   # DMA sems (2,)
        pending = refs[1 + 2 * num_levels + 3]   # SMEM (2,) int32
    else:
        acc_tile = refs[1 + 2 * num_levels]   # VMEM scratch [T, T, C]
        sem = refs[1 + 2 * num_levels + 1]    # DMA semaphore

    r = pl.program_id(0)
    lvl = lvl_ref[r]
    b = b_ref[r]
    y0 = y0_ref[r]
    x0 = x0_ref[r] * align                  # see _kernel: provable align

    if overlap:
        n = pl.num_programs(0)
        slot = r % 2

        @pl.when(r == 0)
        def _():
            pending[0] = 0
            pending[1] = 0

        def wait_out(s):
            # fixed-region descriptor: same byte count as every
            # out-DMA (see docstring)
            pltpu.make_async_copy(
                acc_tile.at[s],
                acc_refs[0].at[0, pl.ds(0, TILE), pl.ds(0, TILE), :],
                out_sem.at[s]).wait()

        # All SMEM flag accesses use STATIC indices (slot-parity
        # branches): the forward kernel proves dynamic VMEM slot
        # indexing on hardware, but a dynamically-indexed SMEM STORE
        # is an unproven Mosaic construct — don't bet the kernel on it.
        def drain(s, extra_cond):
            @pl.when(extra_cond & (pending[s] == 1))
            def _():
                wait_out(s)
                pending[s] = 0

        # slot reuse: drain the write issued two grid steps ago
        drain(0, slot == 0)
        drain(1, slot == 1)

        # RAW hazard vs the previous ROI's in-flight write (lives on
        # the OTHER slot): conservative region-overlap test on
        # (level, batch, tile origins)
        rp = jnp.maximum(r - 1, 0)
        xp = x0_ref[rp] * align
        same = ((r >= 1) & (lvl_ref[rp] == lvl) & (b_ref[rp] == b)
                & (jnp.abs(y0_ref[rp] - y0) < TILE)
                & (jnp.abs(xp - x0) < TILE))
        drain(0, same & (slot == 1))
        drain(1, same & (slot == 0))

        # read the current accumulation tile (blocking)
        for i in range(num_levels):
            @pl.when(lvl == i)
            def _(i=i):
                dma = pltpu.make_async_copy(
                    acc_refs[i].at[b, pl.ds(y0, TILE),
                                   pl.ds(x0, TILE), :],
                    acc_tile.at[slot], in_sem)
                dma.start()
                dma.wait()
    else:
        # read the current accumulation tile
        for i in range(num_levels):
            @pl.when(lvl == i)
            def _(i=i):
                dma = pltpu.make_async_copy(
                    acc_refs[i].at[b, pl.ds(y0, TILE),
                                   pl.ds(x0, TILE), :],
                    acc_tile, sem)
                dma.start()
                dma.wait()

    y_start = ys_ref[r]
    x_start = xs_ref[r]
    bin_h = bh_ref[r]
    bin_w = bw_ref[r]

    f32 = jnp.float32

    def pooled_weights(start, binsz):
        """[out, T]: the fwd's weight matrix averaged over each bin's
        ``sampling`` sample points (pooling is linear, so the sample
        axis folds into the weights)."""
        w = _bilinear_weights(start, binsz, out_size, sampling)  # [S, T]
        return w.reshape(out_size, sampling, TILE).mean(axis=1)

    ryp = pooled_weights(y_start, bin_h)                       # [out, T]
    cxp = pooled_weights(x_start, bin_w)                       # [out, T]

    g_tile = g_ref[0].astype(f32)                              # [o, o, C]
    c = g_tile.shape[-1]
    # rows: [T, out] @ [out, out*C] → [T, out, C]
    rows = jnp.dot(ryp.T, g_tile.reshape(out_size, out_size * c),
                   preferred_element_type=f32,
                   precision=jax.lax.Precision.HIGHEST
                   ).reshape(TILE, out_size, c)
    # cols: contract out with cxp → [T, C, T] → [T, T, C]
    d_tile = jax.lax.dot_general(
        rows, cxp,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=f32,
        precision=jax.lax.Precision.HIGHEST).transpose(0, 2, 1)

    if overlap:
        acc_tile[slot] = acc_tile[slot] + d_tile

        # async write-back: overlaps the next ROI's read + matmuls
        for i in range(num_levels):
            @pl.when(lvl == i)
            def _(i=i):
                pltpu.make_async_copy(
                    acc_tile.at[slot],
                    acc_refs[i].at[b, pl.ds(y0, TILE),
                                   pl.ds(x0, TILE), :],
                    out_sem.at[slot]).start()

        @pl.when(slot == 0)
        def _():
            pending[0] = 1

        @pl.when(slot == 1)
        def _():
            pending[1] = 1

        # final grid step: nothing after this to drain us — wait both
        # (static slot-parity branches; own slot's pending was just
        # set, the other's may have been hazard-drained already)
        last = r == n - 1

        def final_drain(s, my_slot):
            @pl.when(last & (slot == my_slot) & (pending[s] == 1))
            def _():
                wait_out(s)
                pending[s] = 0

        final_drain(1, 0)   # other slot first (the older write)
        final_drain(0, 1)
        final_drain(0, 0)   # then the write this very step issued
        final_drain(1, 1)
    else:
        acc_tile[:] = acc_tile[:] + d_tile

        # write the updated tile back (sequential grid — no races)
        for i in range(num_levels):
            @pl.when(lvl == i)
            def _(i=i):
                dma = pltpu.make_async_copy(
                    acc_tile,
                    acc_refs[i].at[b, pl.ds(y0, TILE),
                                   pl.ds(x0, TILE), :],
                    sem)
                dma.start()
                dma.wait()


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
               scratch_bytes: int, extra_budget: int = 0) -> int:
    """Largest divisor of ``n_total`` whose per-call stack estimate
    (chunk's output + kernel scratch) fits the scoped-vmem budget
    (module-level ``_VMEM_STACK_BUDGET``, read at call time so tests
    can monkeypatch it, plus the caller's ``extra_budget``).
    The per-ROI size uses the TILED output layout (W padded to the
    sublane tile, 7→8 / 14→16) — the buffer XLA would actually pack."""
    esize = jnp.dtype(dtype).itemsize
    out_pad = out_size + (-out_size % 8)
    per_roi = out_size * out_pad * c * esize
    room = max(_VMEM_STACK_BUDGET + extra_budget - scratch_bytes, per_roi)
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
    scalars = _prep(padded, rois, strides, out_size, min_level, align)
    num_levels = len(padded)
    # async write-back pipeline (see _bwd_kernel docstring); A/B knob
    overlap = os.environ.get("EKSML_BWD_OVERLAP", "1") != "0"
    kern = functools.partial(_bwd_kernel, out_size, sampling,
                             num_levels, align, overlap)

    g_flat = g.reshape(b * n, out_size, out_size, c)

    # De-cluster the grid order: accumulation is order-independent, so
    # walk ROIs by a fixed coprime stride (golden-ratio spacing).
    # Consecutive proposals/fg-ROIs are spatially CLUSTERED (score
    # order; objects), which is exactly when the async write-back's
    # RAW-hazard drain must serialize — a stride walk makes adjacent
    # grid steps land on unrelated tiles so the overlap pipeline
    # actually overlaps.  Applied regardless of the overlap flag so
    # serial/overlap A/B (and the bitwise equality test) see the same
    # accumulation order.
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
    esize = jnp.dtype(jnp.float32).itemsize
    scratch_bytes = (2 if overlap else 1) * TILE * TILE * c * esize
    # Overlap doubles the tile scratch (2×4 MiB at TILE=64/C=256).
    # Keep the chunk count unchanged by granting the bwd call a larger
    # stack budget — and, now that the per-kernel compiler params
    # demonstrably reach the compiler (see _compiler_params), declare
    # the extra scratch in THIS call's vmem limit instead of trying to
    # squeeze the accumulator pin budget: on r5b hardware the 1344/b4
    # bf16 overlap compile needed 35.94 MiB (= the measured serial-path
    # stack + one extra staging slot) against the base 32 MiB, and
    # shrinking the pin budget did NOT keep the pinned accumulator off
    # the stack.  base + 2×extra gives the observed need ~4 MiB of
    # headroom while staying far under v5e's 128 MiB of vmem.
    extra = TILE * TILE * c * esize if overlap else 0
    chunk = _roi_chunk(b * n, out_size, c, g_flat.dtype, scratch_bytes,
                       extra_budget=extra)

    def call(chunk_scalars, g_chunk, accs, n_rois):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=8,
            grid=(n_rois,),
            in_specs=[pl.BlockSpec((1, out_size, out_size, c),
                                   lambda r, *_: (r, 0, 0, 0),
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
            scratch_shapes=(
                [pltpu.VMEM((2, TILE, TILE, c), jnp.float32),
                 pltpu.SemaphoreType.DMA(()),
                 pltpu.SemaphoreType.DMA((2,)),
                 pltpu.SMEM((2,), jnp.int32)]
                if overlap else
                [pltpu.VMEM((TILE, TILE, c), jnp.float32),
                 pltpu.SemaphoreType.DMA(()),
                 ]),
        )
        out_shape = tuple(
            _hbm_out(f.shape, jnp.float32) if pinned[i]
            else jax.ShapeDtypeStruct(f.shape, jnp.float32)
            for i, f in enumerate(padded))
        return pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=out_shape,
            # accumulator i (flat arg index 8 scalars + 1 g + i) owns
            # output buffer i: the kernel RMWs it through the out refs
            input_output_aliases={9 + i: i for i in range(num_levels)},
            compiler_params=_compiler_params(extra_bytes=2 * extra),
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
    # scratch fits the limit the RMW kernel itself declares — base
    # 32 MiB plus, on the overlap path, 2x the extra staging slot
    # (r5b hardware: 35.94 MiB observed need at 1344/b4 bf16, ~4 MiB
    # headroom under the 40 MiB grant) — even if XLA packs every
    # unpinned buffer.
    sizes = [int(np.prod(f.shape)) * 4 for f in padded]
    pinned = [False] * num_levels
    if not interpret and os.environ.get("EKSML_BWD_PIN", "1") != "0":
        limit = _scoped_vmem_kib() * 1024
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
            # the overlap path's extra scratch is paid for by the
            # per-call extra_bytes grant in _compiler_params, NOT by
            # shrinking this budget — r5b hardware showed evicting a
            # pinned aliased accumulator doesn't reliably keep it off
            # the stack anyway
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


def pallas_roi_bwd_supported() -> bool:
    """Backward-kernel gate: ``EKSML_ROI_BWD={auto,pallas,xla}`` —
    auto is the kernel on a TPU backend, xla forces the gather-
    transpose formulation, pallas forces the kernel."""
    return _use_kernel("EKSML_ROI_BWD")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def pallas_batched_multilevel_roi_align(
        feats, rois, strides: Sequence[int], out_size: int,
        sampling_ratio: int = 2, min_level: int = 2,
        interpret: bool = False):
    """Drop-in for ops.roi_align.batched_multilevel_roi_align:
    feats ``[(B, Hl, Wl, C), ...]``, rois ``[B, N, 4]`` →
    ``[B, N, out, out, C]``.  Pallas forward; backward is the
    transpose Pallas kernel when enabled (``EKSML_ROI_BWD``, see
    ``_bwd``) and the XLA formulation's VJP otherwise."""
    return _pallas_forward(tuple(feats), rois, strides, out_size,
                           sampling_ratio, min_level, interpret)


def _fwd(feats, rois, strides, out_size, sampling_ratio, min_level,
         interpret):
    out = _pallas_forward(tuple(feats), rois, strides, out_size,
                          sampling_ratio, min_level, interpret)
    return out, (tuple(feats), rois)


def _bwd(strides, out_size, sampling_ratio, min_level, interpret, res, g):
    """Backward: the transpose Pallas kernel when enabled (two MXU
    matmuls + sequential RMW accumulation, no scatter), else the XLA
    formulation's VJP — both with the SAME tile-fit level assignment as
    the forward kernel, so fwd/bwd never diverge."""
    from eksml_tpu.ops.roi_align import (assign_fpn_levels_tile_fit,
                                         batched_multilevel_roi_align)

    feats, rois = res
    mode = os.environ.get("EKSML_ROI_BWD", "auto").lower()
    if mode != "xla" and (interpret or pallas_roi_bwd_supported()):
        g_feats = _pallas_backward(feats, rois, g, strides, out_size,
                                   sampling_ratio, min_level, interpret)
        return g_feats, jnp.zeros_like(rois)
    b, n = rois.shape[0], rois.shape[1]
    levels = assign_fpn_levels_tile_fit(
        rois.reshape(b * n, 4), strides, len(feats), TILE,
        min_level=min_level,
        align=sublane_align(feats[0].dtype)).reshape(b, n)
    _, vjp = jax.vjp(
        lambda fs: batched_multilevel_roi_align(
            fs, rois, strides, out_size, sampling_ratio, min_level,
            levels=levels),
        feats)
    (g_feats,) = vjp(g)
    return g_feats, jnp.zeros_like(rois)


pallas_batched_multilevel_roi_align.defvjp(_fwd, _bwd)
