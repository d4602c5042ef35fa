"""Fixed-shape greedy NMS for TPU.

The reference gets NMS from TF's CUDA kernel inside TensorPack/
mask-rcnn-tensorflow (base image container/Dockerfile:1).  A CUDA-style
dynamic-output NMS cannot run under XLA's static-shape regime, so this
is a re-design, not a port:

- inputs are a *fixed* K boxes (score-padded; padding boxes carry
  score -inf and zero area),
- output is a keep *mask* plus top-``max_outputs`` indices — shapes are
  compile-time constants,
- the greedy recurrence runs as a `lax.fori_loop` over boxes in score
  order with O(K) vector work per step (VPU-friendly), using a
  precomputed K×K IoU matrix (MXU/VPU-friendly).

`batched_nms` vmaps the per-image kernel across the batch.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from eksml_tpu.ops.boxes import pairwise_iou


# 256 balances outer-step count against the [tile, tile] fixed-point
# block staying VMEM-cheap
NMS_TILE = 256


# "nms" scope → the rpn-nms attribution component (eksml_tpu/profiling
# SCOPE_RULES); keeps NMS fusions nameable in profiles
@jax.named_scope("nms")
def nms_mask(boxes: jnp.ndarray, scores: jnp.ndarray,
             iou_threshold: float, tile: int = NMS_TILE) -> jnp.ndarray:
    """Greedy NMS keep-mask for boxes ``[K, 4]`` (any order).

    Returns a bool ``[K]`` mask in the *input* order.  Padding entries
    should have ``scores = -inf``; they never suppress anything and are
    excluded from the keep mask.

    TPU formulation: instead of K sequential greedy steps (the CUDA
    shape of the reference's TF kernel), walk score-sorted *tiles* of
    ``tile`` boxes.  Tiles are visited in rank order, so by the time a
    tile is processed every earlier keep decision is final — cross-tile
    suppression is ONE ``[tile, K]`` masked reduction, no iteration.
    Within the tile, iterate the synchronous fixed point

        keep_i ← alive_i ∧ ¬∃j:  rank_j < rank_i ∧ IoU(j,i) > t ∧ keep_j

    until unchanged; it runs for the longest suppression *chain inside
    the tile* (≤ tile, typically ≪).  The global formulation (one
    fixed point over all K) was profiled at 20.6 ms per FPN level at
    1344 px — RPN-decoded boxes from dense anchor grids build
    suppression chains hundreds deep, and each global sweep re-reads a
    [K,K] matrix from HBM.  Tiling bounds the sequential depth by
    K/tile outer steps plus per-tile chain depth on a [tile,tile]
    block that lives in VMEM.  The result is exact greedy NMS
    (tests/test_nms.py cross-checks the sequential recurrence).
    """
    if tile <= 0:
        raise ValueError(f"NMS tile size must be positive, got {tile}")
    k = boxes.shape[0]
    order = jnp.argsort(-scores)
    sboxes = boxes[order]
    sscores = scores[order]
    pad = (-k) % tile
    if pad:
        # zero-area padding boxes with -inf scores: IoU 0 against
        # everything, isfinite=False — they neither keep nor suppress
        sboxes = jnp.concatenate(
            [sboxes, jnp.zeros((pad, 4), sboxes.dtype)])
        sscores = jnp.concatenate(
            [sscores, jnp.full((pad,), -jnp.inf, sscores.dtype)])
    kp = k + pad
    svalid = jnp.isfinite(sscores)
    rank_t = jnp.arange(tile)
    rank_all = jnp.arange(kp)

    def outer(t, keep):
        t0 = t * tile
        rows = jax.lax.dynamic_slice(sboxes, (t0, 0), (tile, 4))
        iou_tk = pairwise_iou(rows, sboxes)            # [tile, kp]
        alive = jax.lax.dynamic_slice(svalid, (t0,), (tile,))
        # suppression by FINAL keeps from earlier tiles (rank < t0)
        prev = keep & (rank_all < t0)
        alive &= ~jnp.any((iou_tk > iou_threshold) & prev[None, :],
                          axis=1)
        # within-tile fixed point on the [tile, tile] diagonal block
        iou_tt = jax.lax.dynamic_slice(iou_tk, (0, t0), (tile, tile))
        # sup[j, i]: j would suppress i if j is kept
        sup = (iou_tt > iou_threshold) & (rank_t[:, None] < rank_t[None, :])

        def cond(state):
            cur, prv, it = state
            return (it < tile) & jnp.any(cur != prv)

        def body(state):
            cur, _, it = state
            new = alive & ~jnp.any(sup & cur[:, None], axis=0)
            return new, cur, it + 1

        fixed, _, _ = jax.lax.while_loop(
            cond, body,
            (alive, jnp.zeros_like(alive), jnp.zeros((), jnp.int32)))
        return jax.lax.dynamic_update_slice(keep, fixed, (t0,))

    keep_sorted = jax.lax.fori_loop(
        0, kp // tile, outer, jnp.zeros((kp,), dtype=bool))
    # scatter back to input order
    return jnp.zeros((k,), dtype=bool).at[order].set(keep_sorted[:k])


def nms_mask_sequential(boxes: jnp.ndarray, scores: jnp.ndarray,
                        iou_threshold: float) -> jnp.ndarray:
    """Reference O(K)-step greedy recurrence (the textbook algorithm);
    kept for cross-checking the fixed-point formulation above."""
    k = boxes.shape[0]
    order = jnp.argsort(-scores)
    sboxes = boxes[order]
    svalid = jnp.isfinite(scores[order])
    iou = pairwise_iou(sboxes, sboxes)

    def body(i, keep):
        kept_i = keep[i]
        suppress = (iou[i] > iou_threshold) & (jnp.arange(k) > i) & kept_i
        return keep & ~suppress

    keep_sorted = jax.lax.fori_loop(0, k, body, svalid)
    return jnp.zeros((k,), dtype=bool).at[order].set(keep_sorted)


@partial(jax.jit, static_argnames=("max_outputs", "iou_threshold"))
def _topk_nms(boxes, scores, iou_threshold: float, max_outputs: int):
    keep = nms_mask(boxes, scores, iou_threshold)
    masked_scores = jnp.where(keep, scores, -jnp.inf)
    top_scores, idx = jax.lax.top_k(masked_scores, max_outputs)
    valid = jnp.isfinite(top_scores)
    return idx, top_scores, valid


def batched_nms(boxes: jnp.ndarray, scores: jnp.ndarray,
                iou_threshold: float, max_outputs: int):
    """NMS over a batch: boxes ``[B, K, 4]``, scores ``[B, K]``.

    Returns ``(indices [B, max_outputs], scores [B, max_outputs],
    valid [B, max_outputs])``; invalid slots have score ``-inf``.
    """
    fn = jax.vmap(lambda b, s: _topk_nms(b, s, iou_threshold, max_outputs))
    return fn(boxes, scores)


@jax.named_scope("nms")
def class_aware_nms(boxes, scores, iou_threshold: float, max_outputs: int,
                    class_ids=None, class_offset_scale: float = None):
    """Per-class NMS via the coordinate-offset trick: shift each class's
    boxes to a disjoint region so one class never suppresses another,
    then run a single fixed-shape NMS.  Standard static-shape
    formulation of torchvision/TF ``batched_nms`` semantics used by the
    second-stage head (TEST.FRCNN_NMS_THRESH).

    The offset stride defaults to ``max_coordinate + 1`` (torchvision's
    rule): a fixed huge stride would push coordinates into float32
    ranges where per-coordinate quantization (~0.5px at 8e6) corrupts
    IoU for small boxes of high-numbered classes.
    """
    if class_ids is not None:
        if class_offset_scale is None:
            class_offset_scale = jax.lax.stop_gradient(boxes).max() + 1.0
        offsets = class_ids.astype(boxes.dtype)[..., None] * class_offset_scale
        boxes = boxes + offsets
    return _topk_nms(boxes, scores, iou_threshold, max_outputs)
