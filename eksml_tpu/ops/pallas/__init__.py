"""Pallas TPU kernels for the detection hot ops.

The reference's equivalents are cuDNN/CUDA kernels inside TF 1.15
(reference container/Dockerfile:1).  These kernels exist where the pure
XLA formulation leaves real performance on the table (SURVEY.md §7 hard
part #2); every kernel has an XLA fallback and the dispatchers pick per
backend.
"""

from eksml_tpu.ops.pallas.roi_align_kernel import (  # noqa: F401
    TILE, bwd_tile_share, fwd_tile_share,
    pallas_batched_multilevel_roi_align,
    pallas_roi_align_supported, sublane_align, tile_margin)
