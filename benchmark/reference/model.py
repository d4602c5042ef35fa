"""Plain reference: Faster/Mask R-CNN R50-FPN training loss for ONE image.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``; gather ROIAlign, sequential greedy NMS, no
kernels, no batching (the caller loops over images), one switch for the
mask branch (``spec["mask"]``).  Imports nothing of ``eksml_tpu``.

Follows He et al. (arXiv:1703.06870) on FPN (arXiv:1612.03144) with the
tensorpack example's hyper-parameters.  Departures, each because the
program under test defines the model that way and a discrete choice
cannot be compared across two definitions:

* ROI -> pyramid level: the FPN heuristic, then bumped to a coarser
  level while the ROI's longer side exceeds ``roi_tile_usable`` feature
  pixels at the level (the program's kernel reads one 64-wide tile per
  ROI; ``usable`` is stated in the configuration file).
* fg/bg subsampling draws uniform priorities and takes the top-k
  (choice without replacement), with the key schedule of
  ``reference/train.py``.
* proposals per level are padded to one common length before the
  image-wide top-k, which fixes how ties between -inf rows break.
* GT masks arrive cropped to their box at 56x56 and are resampled to
  the ROI by a 2x2-sample ROIAlign, thresholded at 0.5.

``quant`` (None, or a rounding function applied to both operands and
to the result of every convolution and matrix product) is how the
low-precision CONTROL is made: the same arithmetic with activations
and weights held in int8.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-8
BN_EPS = 1e-5
CLIP_EXP = 4.135  # log(1000/16)


# ----------------------------------------------------------------- layers


def _q(quant, x):
    return x if quant is None else quant(x)


def conv(x, w, b=None, stride=1, quant=None):
    """NHWC 'SAME' convolution, HWIO kernel."""
    y = jax.lax.conv_general_dilated(
        _q(quant, x), _q(quant, w), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    return _q(quant, y if b is None else y + b)


def dense(x, w, b, quant=None):
    return _q(quant, jnp.matmul(_q(quant, x), _q(quant, w),
                                precision=HIGHEST) + b)


def deconv2x(x, w, b, quant=None):
    """2x2 stride-2 transposed convolution: each input pixel writes a
    2x2 block, out[2i+a, 2j+b'] = x[i, j] . w[1-a, 1-b'] (the
    un-flipped-kernel convention of ``lax.conv_transpose``)."""
    n, h, wd, _ = x.shape
    o = w.shape[-1]
    y = jnp.einsum("nijc,abco->niajbo", _q(quant, x),
                   _q(quant, w[::-1, ::-1]), precision=HIGHEST)
    return _q(quant, y.reshape(n, 2 * h, 2 * wd, o) + b)


def frozen_bn(x):
    """Frozen batch norm at its initial statistics (scale 1, bias 0,
    mean 0, var 1): a constant multiply.  The weights are random from
    the seed, so no trained statistics exist."""
    return x * np.float32(1.0 / np.sqrt(1.0 + BN_EPS))


def max_pool_3x3_s2(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        ((0, 0), (1, 1), (1, 1), (0, 0)))


# --------------------------------------------------------------- backbone


def bottleneck(p, x, stride, quant):
    out = jax.nn.relu(frozen_bn(conv(x, p["conv1"]["kernel"], quant=quant)))
    out = jax.nn.relu(frozen_bn(conv(out, p["conv2"]["kernel"],
                                     stride=stride, quant=quant)))
    out = frozen_bn(conv(out, p["conv3"]["kernel"], quant=quant))
    if "convshortcut" in p:
        x = frozen_bn(conv(x, p["convshortcut"]["kernel"], stride=stride,
                           quant=quant))
    return jax.nn.relu(out + x)


def backbone(p, x, spec, quant):
    x = jax.nn.relu(frozen_bn(conv(x, p["conv0"]["kernel"], stride=2,
                                   quant=quant)))
    x = max_pool_3x3_s2(x)
    feats = []
    for stage, blocks in enumerate(spec["resnet_blocks"]):
        for b in range(blocks):
            stride = 2 if (b == 0 and stage > 0) else 1
            x = bottleneck(p[f"group{stage}_block{b}"], x, stride, quant)
        if stage + 2 <= spec["freeze_at"]:
            x = jax.lax.stop_gradient(x)
        feats.append(x)
    return feats


def fpn(p, feats, quant):
    lats = [conv(c, p[f"lateral_{i + 2}"]["kernel"],
                 p[f"lateral_{i + 2}"]["bias"], quant=quant)
            for i, c in enumerate(feats)]
    merged = [lats[-1]]
    for lat in lats[-2::-1]:
        up = jnp.repeat(jnp.repeat(merged[-1], 2, axis=1), 2, axis=2)
        merged.append(lat + up)
    merged = merged[::-1]
    outs = [conv(m, p[f"posthoc_{i + 2}"]["kernel"],
                 p[f"posthoc_{i + 2}"]["bias"], quant=quant)
            for i, m in enumerate(merged)]
    return outs + [outs[-1][:, ::2, ::2]]  # P6: stride-2 subsample of P5


def rpn_head(p, feats, quant):
    logits, deltas = [], []
    for f in feats:
        h = jax.nn.relu(conv(f, p["conv0"]["kernel"], p["conv0"]["bias"],
                             quant=quant))
        logits.append(conv(h, p["class"]["kernel"], p["class"]["bias"],
                           quant=quant).reshape(-1))
        deltas.append(conv(h, p["box"]["kernel"], p["box"]["bias"],
                           quant=quant).reshape(-1, 4))
    return logits, deltas


# ------------------------------------------------------------------ boxes


def anchors_for(image_hw, strides, sizes, ratios):
    """Per-level [(H_l*W_l*A, 4)] anchors, (y, x, ratio) order,
    centred on the cell centres."""
    levels = []
    for stride, size in zip(strides, sizes):
        cell = np.asarray(
            [[-size / np.sqrt(r) / 2.0, -size * np.sqrt(r) / 2.0,
              size / np.sqrt(r) / 2.0, size * np.sqrt(r) / 2.0]
             for r in ratios], np.float32)
        fh, fw = image_hw[0] // stride, image_hw[1] // stride
        sx = (np.arange(fw, dtype=np.float32) + 0.5) * stride
        sy = (np.arange(fh, dtype=np.float32) + 0.5) * stride
        gx, gy = np.meshgrid(sx, sy)
        shifts = np.stack([gx, gy, gx, gy], axis=-1)
        levels.append((shifts[:, :, None, :] + cell[None, None])
                      .reshape(-1, 4).astype(np.float32))
    return levels


def area(b):
    return (jnp.maximum(b[..., 2] - b[..., 0], 0.0)
            * jnp.maximum(b[..., 3] - b[..., 1], 0.0))


def iou_matrix(a, b):
    """[N, M] IoU of a [N,4] against b [M,4]."""
    lt = jnp.maximum(a[:, None, :2], b[None, :, :2])
    rb = jnp.minimum(a[:, None, 2:], b[None, :, 2:])
    wh = jnp.maximum(rb - lt, 0.0)
    inter = wh[..., 0] * wh[..., 1]
    union = area(a)[:, None] + area(b)[None, :] - inter
    return inter / jnp.maximum(union, EPS)


def encode(boxes, anchors, weights=(1.0, 1.0, 1.0, 1.0)):
    aw = jnp.maximum(anchors[..., 2] - anchors[..., 0], EPS)
    ah = jnp.maximum(anchors[..., 3] - anchors[..., 1], EPS)
    ax = anchors[..., 0] + 0.5 * aw
    ay = anchors[..., 1] + 0.5 * ah
    bw = jnp.maximum(boxes[..., 2] - boxes[..., 0], EPS)
    bh = jnp.maximum(boxes[..., 3] - boxes[..., 1], EPS)
    bx = boxes[..., 0] + 0.5 * bw
    by = boxes[..., 1] + 0.5 * bh
    return jnp.stack([weights[0] * (bx - ax) / aw,
                      weights[1] * (by - ay) / ah,
                      weights[2] * jnp.log(bw / aw),
                      weights[3] * jnp.log(bh / ah)], axis=-1)


def decode(deltas, anchors, weights=(1.0, 1.0, 1.0, 1.0)):
    aw = jnp.maximum(anchors[..., 2] - anchors[..., 0], EPS)
    ah = jnp.maximum(anchors[..., 3] - anchors[..., 1], EPS)
    ax = anchors[..., 0] + 0.5 * aw
    ay = anchors[..., 1] + 0.5 * ah
    dx = deltas[..., 0] / weights[0]
    dy = deltas[..., 1] / weights[1]
    dw = jnp.minimum(deltas[..., 2] / weights[2], CLIP_EXP)
    dh = jnp.minimum(deltas[..., 3] / weights[3], CLIP_EXP)
    cx, cy = dx * aw + ax, dy * ah + ay
    w, h = jnp.exp(dw) * aw, jnp.exp(dh) * ah
    return jnp.stack([cx - 0.5 * w, cy - 0.5 * h,
                      cx + 0.5 * w, cy + 0.5 * h], axis=-1)


def clip(boxes, h, w):
    return jnp.stack([jnp.clip(boxes[..., 0], 0, w),
                      jnp.clip(boxes[..., 1], 0, h),
                      jnp.clip(boxes[..., 2], 0, w),
                      jnp.clip(boxes[..., 3], 0, h)], axis=-1)


def smooth_l1(x, beta):
    ax = jnp.abs(x)
    return jnp.where(ax < beta, 0.5 * x * x / beta, ax - 0.5 * beta)


def bce_with_logits(x, z):
    return jnp.maximum(x, 0.0) - x * z + jnp.log1p(jnp.exp(-jnp.abs(x)))


def greedy_nms(boxes, scores, thresh):
    """Textbook greedy NMS, one box at a time in score order.  Returns
    the keep mask in input order; -inf scores never keep or suppress."""
    k = boxes.shape[0]
    order = jnp.argsort(-scores)
    sb = boxes[order]
    iou = iou_matrix(sb, sb)
    later = jnp.arange(k)

    def body(i, keep):
        kill = (iou[i] > thresh) & (later > i) & keep[i]
        return keep & ~kill

    keep = jax.lax.fori_loop(0, k, body, jnp.isfinite(scores[order]))
    return jnp.zeros((k,), bool).at[order].set(keep)


def sample_top(cand, key, k, limit=None):
    """Up to k of the true entries of ``cand``, uniformly without
    replacement: uniform priorities, top-k."""
    pri = jnp.where(cand, jax.random.uniform(key, cand.shape), -jnp.inf)
    top, idx = jax.lax.top_k(pri, k)
    take = jnp.isfinite(top)
    if limit is not None:
        take = take & (jnp.arange(k) < limit)
    return idx, take


# -------------------------------------------------------------------- RPN


def rpn_targets_and_loss(logits, deltas, anchors, gt_boxes, gt_valid,
                         gt_crowd, key, spec):
    ok = (gt_valid > 0) & (gt_crowd == 0)
    iou_all = iou_matrix(gt_boxes, anchors)            # [G, A]
    iou = iou_all * ok[:, None].astype(iou_all.dtype)
    best = iou.max(axis=0)
    matched = iou.argmax(axis=0)
    labels = jnp.full(anchors.shape[0], -1, jnp.int32)
    labels = jnp.where(best < spec["rpn_neg_thresh"], 0, labels)
    labels = jnp.where(best >= spec["rpn_pos_thresh"], 1, labels)
    crowd_iou = (iou_all * ((gt_valid > 0) & (gt_crowd > 0))[:, None]
                 ).max(axis=0)
    labels = jnp.where((labels == 0)
                       & (crowd_iou >= spec["rpn_neg_thresh"]), -1, labels)
    # every usable GT keeps its best anchor as a positive
    best_anchor = iou.argmax(axis=1)
    force = ok & (iou.max(axis=1) > 1e-3)
    labels = labels.at[best_anchor].set(
        jnp.where(force, 1, labels[best_anchor]))
    labels = jnp.where(ok.sum() > 0, labels,
                       jnp.where(labels == 1, 0, labels))

    n = spec["rpn_batch_per_im"]
    k_fg, k_bg = jax.random.split(key)
    idx, take = sample_top(labels == 1, k_fg, int(n * spec["rpn_fg_ratio"]))
    fg = jnp.zeros(labels.shape, bool).at[idx].set(take)
    idx, take = sample_top(labels == 0, k_bg, n, limit=n - fg.sum())
    bg = jnp.zeros(labels.shape, bool).at[idx].set(take)

    sel = fg | bg
    n_sel = jnp.maximum(sel.sum(), 1)
    cls = jnp.where(sel, bce_with_logits(
        logits, (labels == 1).astype(logits.dtype)), 0.0).sum() / n_sel
    targets = encode(gt_boxes[matched], anchors)
    box = jnp.where(fg, smooth_l1(deltas - targets, 1.0 / 9).sum(-1),
                    0.0).sum() / n_sel
    return cls, box


def proposals(lv_logits, lv_deltas, lv_anchors, image_hw, spec):
    pre, post = spec["rpn_pre_nms_topk"], spec["rpn_post_nms_topk"]
    boxes_l, scores_l = [], []
    for logits, deltas, anchors in zip(lv_logits, lv_deltas, lv_anchors):
        k = min(pre, logits.shape[0])
        scores, idx = jax.lax.top_k(logits, k)
        boxes = clip(decode(deltas[idx], anchors[idx]),
                     image_hw[0], image_hw[1])
        ok = (((boxes[:, 2] - boxes[:, 0]) > 1e-3)
              & ((boxes[:, 3] - boxes[:, 1]) > 1e-3))
        boxes_l.append(boxes)
        scores_l.append(jnp.where(ok, scores, -jnp.inf))
    kmax = max(b.shape[0] for b in boxes_l)
    boxes = jnp.stack([jnp.pad(b, ((0, kmax - b.shape[0]), (0, 0)))
                       for b in boxes_l])
    scores = jnp.stack([jnp.pad(s, (0, kmax - s.shape[0]),
                                constant_values=-jnp.inf)
                        for s in scores_l])
    keep = jnp.stack([greedy_nms(b, s, spec["rpn_nms_thresh"])
                      for b, s in zip(boxes, scores)])
    scores = jnp.where(keep, scores, -jnp.inf).reshape(-1)
    top, idx = jax.lax.top_k(scores, post)
    return boxes.reshape(-1, 4)[idx], top


# -------------------------------------------------------------- ROI heads


def sample_rois(props, prop_scores, gt_boxes, gt_classes, gt_valid,
                gt_crowd, key, spec):
    n = spec["frcnn_batch_per_im"]
    thr = spec["frcnn_fg_thresh"]
    ok = (gt_valid > 0) & (gt_crowd == 0)
    pool = jnp.concatenate([props, gt_boxes], axis=0)
    pool_ok = jnp.concatenate([jnp.isfinite(prop_scores), ok], axis=0)
    iou_all = iou_matrix(pool, gt_boxes)
    iou = iou_all * ok[None, :].astype(iou_all.dtype)
    best = iou.max(axis=1)
    matched = iou.argmax(axis=1)
    crowd_iou = (iou_all * ((gt_valid > 0) & (gt_crowd > 0))[None, :]
                 ).max(axis=1)
    fg_cand = (best >= thr) & pool_ok
    bg_cand = (best < thr) & pool_ok & (crowd_iou < thr)
    max_fg = max(1, int(n * spec["frcnn_fg_ratio"]))
    k_fg, k_bg = jax.random.split(key)
    fg_idx, fg_take = sample_top(fg_cand, k_fg, max_fg)
    bg_idx, bg_take = sample_top(bg_cand, k_bg, n, limit=n - fg_take.sum())
    idx = jnp.concatenate([fg_idx, bg_idx])
    take = jnp.concatenate([fg_take, bg_take])
    order = jnp.argsort(~take)          # taken first, stable: fg lead
    idx, take = idx[order][:n], take[order][:n]
    is_fg = (jnp.arange(max_fg + n)[order] < max_fg)[:n] & take
    sel = matched[idx]
    labels = jnp.where(is_fg, gt_classes[sel], 0)
    return pool[idx], labels, sel, is_fg, take, max_fg


def roi_levels(rois, spec):
    """Level index in [0, 4) per ROI: k = 4 + log2(sqrt(wh)/224),
    clipped to P2..P5, then coarsened until the longer side fits."""
    w = jnp.maximum(rois[:, 2] - rois[:, 0], 0.0)
    h = jnp.maximum(rois[:, 3] - rois[:, 1], 0.0)
    scale = jnp.sqrt(jnp.maximum(w * h, 1e-8))
    lvl = jnp.floor(4 + jnp.log2(scale / 224.0 + 1e-8))
    lvl = jnp.clip(lvl, 2, 5).astype(jnp.int32) - 2
    extent = jnp.maximum(jnp.maximum(w, h), 1e-4)
    need = jnp.ceil(jnp.log2(
        extent / (float(spec["roi_tile_usable"]) * spec["strides"][0])))
    return jnp.clip(jnp.maximum(lvl, need.astype(jnp.int32)), 0, 3)


def roi_align(flat, lv_h, lv_w, lv_off, lv_scale, rois, levels, out,
              sampling=2):
    """Aligned ROIAlign by gathers.  ``flat`` [sum(H_l*W_l), C] holds
    the levels one after another; each ROI reads its own level.  Every
    bin averages sampling x sampling bilinear samples; a tap outside
    the map contributes 0."""
    h = lv_h[levels].astype(jnp.float32)
    w = lv_w[levels].astype(jnp.float32)
    r = rois.astype(jnp.float32) * lv_scale[levels][:, None]
    x1, y1, x2, y2 = r[:, 0], r[:, 1], r[:, 2], r[:, 3]
    bin_w = jnp.maximum(x2 - x1, 1e-4) / out
    bin_h = jnp.maximum(y2 - y1, 1e-4) / out
    frac = (jnp.arange(sampling, dtype=jnp.float32) + 0.5) / sampling
    pos = (jnp.arange(out, dtype=jnp.float32)[:, None]
           + frac[None, :]).reshape(-1)                     # [out*s]
    ys = y1[:, None] - 0.5 + pos[None, :] * bin_h[:, None]  # [N, out*s]
    xs = x1[:, None] - 0.5 + pos[None, :] * bin_w[:, None]
    y0, x0 = jnp.floor(ys), jnp.floor(xs)
    ly, lx = ys - y0, xs - x0

    def tap(yi, xi, wy, wx):
        yy, xx = yi[:, :, None], xi[:, None, :]
        inb = ((yy >= 0) & (yy <= h[:, None, None] - 1)
               & (xx >= 0) & (xx <= w[:, None, None] - 1))
        yc = jnp.clip(yy, 0, h[:, None, None] - 1).astype(jnp.int32)
        xc = jnp.clip(xx, 0, w[:, None, None] - 1).astype(jnp.int32)
        idx = (lv_off[levels][:, None, None]
               + yc * lv_w[levels][:, None, None] + xc)
        wgt = wy[:, :, None] * wx[:, None, :] * inb
        return flat[idx] * wgt[..., None]

    vals = (tap(y0, x0, 1 - ly, 1 - lx) + tap(y0, x0 + 1, 1 - ly, lx)
            + tap(y0 + 1, x0, ly, 1 - lx) + tap(y0 + 1, x0 + 1, ly, lx))
    n, c = rois.shape[0], flat.shape[-1]
    return vals.reshape(n, out, sampling, out, sampling, c).mean(axis=(2, 4))


def pyramid_tables(feats, strides):
    hs = np.asarray([f.shape[0] for f in feats], np.int32)
    ws = np.asarray([f.shape[1] for f in feats], np.int32)
    offs = np.concatenate([[0], np.cumsum(hs * ws)[:-1]]).astype(np.int32)
    scale = np.asarray([1.0 / s for s in strides], np.float32)
    flat = jnp.concatenate([f.reshape(-1, f.shape[-1]) for f in feats])
    return flat, jnp.asarray(hs), jnp.asarray(ws), jnp.asarray(offs), \
        jnp.asarray(scale)


def box_head_loss(p, roi_feats, rois, labels, matched, gt_boxes, is_fg,
                  take, spec, quant):
    x = roi_feats.reshape(roi_feats.shape[0], -1)
    x = jax.nn.relu(dense(x, p["fc6"]["kernel"], p["fc6"]["bias"], quant))
    x = jax.nn.relu(dense(x, p["fc7"]["kernel"], p["fc7"]["bias"], quant))
    logits = dense(x, p["class"]["kernel"], p["class"]["bias"], quant)
    deltas = dense(x, p["box"]["kernel"], p["box"]["bias"],
                   quant).reshape(-1, spec["num_classes"], 4)
    n_valid = jnp.maximum(take.sum(), 1)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    cls = jnp.where(take, ce, 0.0).sum() / n_valid
    targets = encode(gt_boxes[matched], rois, spec["bbox_reg_weights"])
    sel = jnp.take_along_axis(deltas, labels[:, None, None], axis=1)[:, 0]
    box = jnp.where(is_fg, smooth_l1(sel - targets, 1.0).sum(-1),
                    0.0).sum() / n_valid
    return cls, box


def mask_targets(rois, matched, gt_boxes, gt_masks, res):
    """Resample each ROI's matched GT mask (stored cropped to its box)
    onto the ROI's res x res grid, threshold at 0.5."""
    gb = gt_boxes[matched]
    gm = gt_masks[matched]                                 # [K, M0, M0]
    m0 = gm.shape[-1]
    gw = jnp.maximum(gb[:, 2] - gb[:, 0], 1e-4)
    gh = jnp.maximum(gb[:, 3] - gb[:, 1], 1e-4)
    mr = jnp.stack([(rois[:, 0] - gb[:, 0]) / gw * m0,
                    (rois[:, 1] - gb[:, 1]) / gh * m0,
                    (rois[:, 2] - gb[:, 0]) / gw * m0,
                    (rois[:, 3] - gb[:, 1]) / gh * m0], axis=-1)
    k = rois.shape[0]
    flat = gm.reshape(k * m0 * m0, 1).astype(jnp.float32)
    one = jnp.full((k,), m0, jnp.int32)
    out = roi_align(flat, one, one, jnp.arange(k, dtype=jnp.int32) * m0 * m0,
                    jnp.ones((k,), jnp.float32), mr,
                    jnp.arange(k, dtype=jnp.int32), res)
    return (out[..., 0] >= 0.5).astype(jnp.float32)


def mask_head_loss(p, feats, labels, targets, is_fg, spec, quant):
    x = feats
    for i in range(4):
        x = jax.nn.relu(conv(x, p[f"fcn{i}"]["kernel"], p[f"fcn{i}"]["bias"],
                             quant=quant))
    x = jax.nn.relu(deconv2x(x, p["deconv"]["kernel"], p["deconv"]["bias"],
                             quant))
    logits = conv(x, p["conv"]["kernel"], p["conv"]["bias"], quant=quant)
    sel = jnp.take_along_axis(
        logits, labels[:, None, None, None], axis=-1)[..., 0]
    per_roi = bce_with_logits(sel, targets).mean(axis=(1, 2))
    return jnp.where(is_fg, per_roi, 0.0).sum() / jnp.maximum(is_fg.sum(), 1)


# ------------------------------------------------------------- whole image


def image_losses(params, ex, keys, spec, quant=None):
    """Loss terms of one image.  ``ex``: images [H,W,3] uint8,
    image_hw [2], gt_boxes [G,4], gt_classes [G], gt_valid [G],
    gt_crowd [G], gt_masks [G,M0,M0] (mask branch only).  ``keys``
    [2]: the image's RPN-sampling and ROI-sampling keys."""
    mean = jnp.asarray(spec["pixel_mean"], jnp.float32)
    std = jnp.asarray(spec["pixel_std"], jnp.float32)
    x = ((ex["images"].astype(jnp.float32) - mean) / std)[None]
    canvas = x.shape[1:3]
    strides = spec["strides"]
    feats = fpn(params["fpn"], backbone(params["backbone"], x, spec, quant),
                quant)
    lv_logits, lv_deltas = rpn_head(params["rpn"], feats, quant)
    lv_anchors = [jnp.asarray(a) for a in anchors_for(
        canvas, strides, spec["anchor_sizes"], spec["anchor_ratios"])]
    rpn_cls, rpn_box = rpn_targets_and_loss(
        jnp.concatenate(lv_logits), jnp.concatenate(lv_deltas),
        jnp.concatenate(lv_anchors), ex["gt_boxes"], ex["gt_valid"],
        ex["gt_crowd"], keys[0], spec)

    props, prop_scores = proposals(lv_logits, lv_deltas, lv_anchors,
                                   ex["image_hw"], spec)
    props = jax.lax.stop_gradient(props)
    prop_scores = jax.lax.stop_gradient(prop_scores)
    rois, labels, matched, is_fg, take, max_fg = sample_rois(
        props, prop_scores, ex["gt_boxes"], ex["gt_classes"],
        ex["gt_valid"], ex["gt_crowd"], keys[1], spec)

    tables = pyramid_tables([f[0] for f in feats[:4]], strides[:4])
    levels = roi_levels(rois, spec)
    box_feats = roi_align(*tables, rois, levels, 7)
    frcnn_cls, frcnn_box = box_head_loss(
        params["fastrcnn"], box_feats, rois, labels, matched,
        ex["gt_boxes"], is_fg, take, spec, quant)
    losses = {"rpn_cls_loss": rpn_cls, "rpn_box_loss": rpn_box,
              "frcnn_cls_loss": frcnn_cls, "frcnn_box_loss": frcnn_box}
    if spec["mask"]:
        res = spec["mask_resolution"]
        k = max_fg                       # sampled fg ROIs lead the list
        m_feats = roi_align(*tables, rois[:k], levels[:k], res // 2)
        targets = mask_targets(rois[:k], matched[:k], ex["gt_boxes"],
                               ex["gt_masks"], res)
        losses["mrcnn_loss"] = mask_head_loss(
            params["maskrcnn"], m_feats, labels[:k], targets, is_fg[:k],
            spec, quant)
    losses["total_loss"] = sum(losses.values())
    return losses
