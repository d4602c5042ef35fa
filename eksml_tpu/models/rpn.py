"""Region Proposal Network: head, anchor matching, proposal generation.

Parity target: TensorPack ``modeling/model_rpn.py`` + the proposal
logic in ``generalized_rcnn.py`` (external, container/Dockerfile:16-19).
TPU-first divergences (SURVEY.md §7 hard part #1):

- anchor labels are computed *inside* the jitted step on padded GT
  (no host-side ragged preprocessing),
- proposals are fixed-count: per-level top-k → NMS → global top-k with
  validity masks, never dynamic,
- the RPN loss samples a fixed BATCH_PER_IM of anchors via top-k on
  randomized priorities — an XLA-friendly replacement for
  `np.random.choice` subsampling.
"""

from __future__ import annotations

from typing import Any, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from eksml_tpu.ops.boxes import EPS, area, clip_boxes, decode_boxes
from eksml_tpu.ops.nms import nms_mask


class RPNHead(nn.Module):
    """Shared 3x3 conv + 1x1 objectness / box-delta convs, applied to
    every FPN level with shared parameters.  Convs run in ``dtype``
    (bf16 under the optimized chart); outputs return f32 so proposal
    decoding/NMS and losses keep full coordinate precision."""
    num_anchors: int = 3
    channels: int = 256
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, feats: Sequence[jnp.ndarray]):
        conv = nn.Conv(self.channels, (3, 3), name="conv0",
                       dtype=self.dtype)
        cls = nn.Conv(self.num_anchors, (1, 1), name="class",
                      dtype=self.dtype)
        box = nn.Conv(self.num_anchors * 4, (1, 1), name="box",
                      dtype=self.dtype)
        logits, deltas = [], []
        for f in feats:
            h = nn.relu(conv(f.astype(self.dtype)))
            b, fh, fw, _ = h.shape
            logits.append(cls(h).reshape(b, -1).astype(jnp.float32))
            deltas.append(
                box(h).reshape(b, -1, 4).astype(jnp.float32))
        return logits, deltas


def match_reductions(anchors: jnp.ndarray, gt_boxes: jnp.ndarray,
                     target_ok: jnp.ndarray, crowd_ok: jnp.ndarray):
    """The reductions ``match_anchors`` labels from, one ground-truth
    row at a time: ``(best_iou [A], matched_gt [A], crowd_iou [A],
    gt_best_iou [G], best_anchor [G])``, equal to the max / argmax over
    the rows (per anchor) and over the anchors (per row) of the
    ``[G, A]`` IoU matrix whose rows outside ``target_ok`` read 0, and
    the per-anchor max over the ``crowd_ok`` rows.

    A is ~450k at 1344 px and G (``DATA.MAX_GT_BOXES``) 100, of which
    an image holds a handful: the dense matrix is 180 MB an image,
    written once and read three times, padding rows and all (PERF.md
    §6).  Here a loop visits the image's target rows in increasing row
    order, then its crowd rows, wherever they sit among the G; each
    computes its IoU against the anchors (``pairwise_iou``'s formula,
    anchors along the lanes) and folds it into running maxima carried
    through the loop.  An anchor's best row is replaced only where a
    later row is strictly greater, so the lowest tied row keeps it and
    an anchor no row overlaps keeps row 0 at IoU 0, as ``argmax`` does;
    rows the loop skips keep 0 and anchor 0, as their zero rows of the
    dense matrix give.  The work follows the image's rows: all G rows
    valid cost about what the dense form does."""
    ax1, ay1, ax2, ay2 = (anchors[:, k] for k in range(4))
    anchor_area = area(anchors)
    gt_area = area(gt_boxes)
    # the rows to visit first, targets before crowds, each in row order
    kind = jnp.where(target_ok, 0, jnp.where(crowd_ok, 1, 2))
    order = jnp.argsort(kind, stable=True).astype(jnp.int32)
    n_target = jnp.sum(target_ok, dtype=jnp.int32)
    n_crowd = jnp.sum(crowd_ok, dtype=jnp.int32)

    def iou(g):
        x1, y1, x2, y2 = (gt_boxes[g, k] for k in range(4))
        w = jnp.maximum(jnp.minimum(x2, ax2) - jnp.maximum(x1, ax1), 0.0)
        h = jnp.maximum(jnp.minimum(y2, ay2) - jnp.maximum(y1, ay1), 0.0)
        inter = w * h
        return inter / jnp.maximum(gt_area[g] + anchor_area - inter, EPS)

    def target(e, carry):
        best, matched, row_best, row_anchor = carry
        g = order[e]
        v = iou(g)
        better = v > best
        return (jnp.where(better, v, best), jnp.where(better, g, matched),
                row_best.at[g].set(v.max()),
                row_anchor.at[g].set(jnp.argmax(v).astype(jnp.int32)))

    a, rows = anchors.shape[0], gt_boxes.shape[0]
    zeros = lambda n, dtype=jnp.float32: jnp.zeros(n, dtype)
    best, matched, row_best, row_anchor = jax.lax.fori_loop(
        0, n_target, target,
        (zeros(a), zeros(a, jnp.int32), zeros(rows), zeros(rows, jnp.int32)))
    crowd_iou = jax.lax.fori_loop(
        n_target, n_target + n_crowd,
        lambda e, c: jnp.maximum(c, iou(order[e])), zeros(a))
    return best, matched, crowd_iou, row_best, row_anchor


@jax.named_scope("matching")
def match_anchors(anchors: jnp.ndarray, gt_boxes: jnp.ndarray,
                  gt_valid: jnp.ndarray, pos_thresh: float,
                  neg_thresh: float,
                  gt_crowd: jnp.ndarray = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Label anchors against padded GT.

    Returns ``labels`` [A] (1 fg, 0 bg, -1 ignore) and ``matched_gt``
    [A] (index of best GT).  Padded GT rows (gt_valid=0) take no part
    (``match_reductions`` visits the valid rows alone), so static GT
    padding never creates positives.  Crowd GT rows (``gt_crowd=1``)
    never become positives, and anchors overlapping a crowd region
    above ``neg_thresh`` are *ignored* rather than trained as
    background.
    """
    crowd = jnp.zeros_like(gt_valid) if gt_crowd is None else gt_crowd
    target_ok = (gt_valid > 0) & (crowd == 0)
    crowd_ok = (gt_valid > 0) & (crowd > 0)
    best_iou, matched_gt, crowd_iou, gt_best_iou, best_anchor_per_gt = (
        match_reductions(anchors, gt_boxes, target_ok, crowd_ok))
    labels = jnp.full(anchors.shape[0], -1, jnp.int32)
    labels = jnp.where(best_iou < neg_thresh, 0, labels)
    labels = jnp.where(best_iou >= pos_thresh, 1, labels)
    # crowd overlap → ignore (only demotes background, never positives)
    labels = jnp.where((labels == 0) & (crowd_iou >= neg_thresh), -1, labels)
    # force-match: every valid non-crowd GT gets its best anchor positive
    force = target_ok & (gt_best_iou > 1e-3)
    labels = labels.at[best_anchor_per_gt].set(
        jnp.where(force, 1, labels[best_anchor_per_gt]))
    has_gt = (target_ok.sum() > 0)
    labels = jnp.where(has_gt, labels,
                       jnp.where(labels == 1, 0, labels))  # no GT → all bg
    return labels, matched_gt


@jax.named_scope("sampling")
def sample_anchors(labels: jnp.ndarray, rng: jax.Array, batch_per_im: int,
                   fg_ratio: float
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                              jnp.ndarray]:
    """Fixed-size fg/bg anchor subsample for the loss; see
    ops.sampling for the choice-without-replacement construction.
    Returns (fg_mask, bg_mask, fg_idx, fg_take): the two masks over
    [A] with at most batch_per_im total bits, and the foreground draw
    itself, ``k = int(batch_per_im * fg_ratio)`` anchor indices and
    which of the k slots are real picks (``fg_idx[fg_take]`` are the
    set bits of ``fg_mask``; the other slots point at arbitrary
    anchors).  The box term reads those k rows, not [A]."""
    from eksml_tpu.ops.sampling import (picks_to_mask, sample_by_priority,
                                        sample_mask_by_priority)

    rng_fg, rng_bg = jax.random.split(rng)
    max_fg = int(batch_per_im * fg_ratio)
    fg_idx, fg_take = sample_by_priority(labels == 1, rng_fg, max_fg)
    fg_mask = picks_to_mask(fg_idx, fg_take, labels.shape[0])
    num_bg = batch_per_im - fg_take.sum()
    bg_mask = sample_mask_by_priority(labels == 0, rng_bg, batch_per_im,
                                      limit=num_bg)
    return fg_mask, bg_mask, fg_idx, fg_take


@jax.named_scope("rpn_nms")
def generate_proposals(
    per_level_logits: Sequence[jnp.ndarray],   # [(A_l,), ...] one image
    per_level_deltas: Sequence[jnp.ndarray],   # [(A_l, 4), ...]
    per_level_anchors: Sequence[jnp.ndarray],  # [(A_l, 4), ...]
    image_hw: jnp.ndarray,                     # (2,) true h, w
    pre_nms_topk: int, post_nms_topk: int, nms_thresh: float,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fixed-count proposal boxes for one image.

    Per level: top-k by score → decode → clip → NMS(mask) ; then global
    top-k to ``post_nms_topk``.  Returns (boxes [P,4], scores [P]) with
    -inf scores marking padding.
    """
    all_boxes, all_scores = [], []
    for logits, deltas, anchors in zip(per_level_logits, per_level_deltas,
                                       per_level_anchors):
        k = min(pre_nms_topk, logits.shape[0])
        scores, idx = jax.lax.top_k(logits, k)
        boxes = decode_boxes(deltas[idx], anchors[idx])
        boxes = clip_boxes(boxes, image_hw[0], image_hw[1])
        # degenerate boxes → invalid
        wh_ok = ((boxes[:, 2] - boxes[:, 0]) > 1e-3) & \
                ((boxes[:, 3] - boxes[:, 1]) > 1e-3)
        scores = jnp.where(wh_ok, scores, -jnp.inf)
        all_boxes.append(boxes)
        all_scores.append(scores)
    # Per-level NMS as ONE vmapped call over a [L, kmax] stack (pad
    # short levels with zero-area/-inf rows — inert under NMS): the
    # per-level python loop emitted L sequential NMS fusions per image
    # on the profile; stacking runs them lane-parallel on the VPU.
    # Semantics are unchanged — NMS is still strictly within-level.
    kmax = max(b.shape[0] for b in all_boxes)
    boxes_lv = jnp.stack([
        jnp.pad(b, ((0, kmax - b.shape[0]), (0, 0))) for b in all_boxes])
    scores_lv = jnp.stack([
        jnp.pad(s, (0, kmax - s.shape[0]), constant_values=-jnp.inf)
        for s in all_scores])
    keep = jax.vmap(
        lambda bb, ss: nms_mask(bb, ss, nms_thresh))(boxes_lv, scores_lv)
    scores_lv = jnp.where(keep, scores_lv, -jnp.inf)
    boxes = boxes_lv.reshape(-1, 4)
    scores = scores_lv.reshape(-1)
    top_scores, top_idx = jax.lax.top_k(scores, post_nms_topk)
    return boxes[top_idx], top_scores


@jax.named_scope("rpn_loss")
def rpn_losses(logits: jnp.ndarray, deltas: jnp.ndarray,
               anchors: jnp.ndarray, labels: jnp.ndarray,
               matched_gt: jnp.ndarray, gt_boxes: jnp.ndarray,
               fg_mask: jnp.ndarray, bg_mask: jnp.ndarray,
               fg_idx: jnp.ndarray, fg_take: jnp.ndarray):
    """RPN objectness BCE + box smooth-L1, normalized by sample count
    (matching the standard Faster-RCNN / TensorPack normalization).

    The objectness term is a masked sum over [A] (lane-dense).  The
    box term is formed on ``sample_anchors``' k foreground rows alone
    (``fg_idx`` [k], ``fg_take`` [k]): three picks of k rows, never an
    [A, 4] array (4 of 128 lanes; encoding the ~450k anchors of a
    1344 px image to keep at most 128 costs ~11 ms a step at b4 on a
    v5e).  Slots with ``fg_take`` false read an arbitrary anchor, whose
    target stays finite through ``encode_boxes`` (padded gt rows are
    zeros: ``log(EPS / aw)``), and add exactly 0 to the value and to
    the gradient."""
    from eksml_tpu.ops.boxes import encode_boxes

    sel = fg_mask | bg_mask
    target = (labels == 1).astype(logits.dtype)
    cls_loss_all = optax.sigmoid_binary_cross_entropy(logits, target)
    n_sel = jnp.maximum(sel.sum(), 1)
    cls_loss = jnp.where(sel, cls_loss_all, 0.0).sum() / n_sel

    box_targets = encode_boxes(gt_boxes[matched_gt[fg_idx]], anchors[fg_idx])
    box_loss_rows = smooth_l1(deltas[fg_idx] - box_targets,
                              beta=1.0 / 9).sum(-1)
    box_loss = jnp.where(fg_take, box_loss_rows, 0.0).sum() / n_sel
    return cls_loss, box_loss


def smooth_l1(x, beta: float):
    ax = jnp.abs(x)
    return jnp.where(ax < beta, 0.5 * x * x / beta, ax - 0.5 * beta)
