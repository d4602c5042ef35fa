"""Op-level microbench: settle per-op step-time attribution in seconds
of chip time instead of a full profiled bench run.

Round-5 part-3 motivation: the tiled+stacked NMS and [G, A] anchor
matching were projected (from the banked r5 trace: NMS fusions 82.6
ms/step, matching 10.8 ms/step at 1344/b4) to cut ~90 ms/step, but the
first post-fix headline measured step-time-neutral vs part 1.  This
tool times the production ops — and vendored copies of the PREVIOUS
formulations — directly on whatever backend is up, so one short
healthy window answers which side of the projection was wrong.

Reference cost model being replaced: TF's CUDA NMS kernel + host
matching inside TensorPack (external, /root/reference/container/
Dockerfile:16-19); see ops/nms.py and models/rpn.py for the TPU-first
designs under test.

Usage:
    python tools/op_microbench.py [--iters 20] [--image-size 1344]
        [--batch 4] [--pre-nms 2000] [--ops nms_new,nms_old,...]
        [--out artifacts/op_microbench.json]

Emits one JSON object: {device_kind, params, results: {op: ms}}.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------
# Vendored PREVIOUS formulations (pre-24ee096 / pre-2f1ee08), kept
# verbatim-in-spirit so old-vs-new is measured on identical inputs.
# Do not use outside this tool.
# ---------------------------------------------------------------------

def nms_mask_global_fixedpoint(boxes, scores, iou_threshold):
    """The pre-tiling formulation: one synchronous fixed point over the
    full [K, K] suppression matrix (profiled 20.6 ms per FPN level at
    1344 px — the motivation for the tiled rewrite)."""
    from eksml_tpu.ops.boxes import pairwise_iou

    k = boxes.shape[0]
    order = jnp.argsort(-scores)
    sboxes = boxes[order]
    svalid = jnp.isfinite(scores[order])
    iou = pairwise_iou(sboxes, sboxes)
    rank = jnp.arange(k)
    sup = (iou > iou_threshold) & (rank[:, None] < rank[None, :])

    def cond(state):
        keep, prev, it = state
        return (it < k) & jnp.any(keep != prev)

    def body(state):
        keep, _, it = state
        new = svalid & ~jnp.any(sup & keep[:, None], axis=0)
        return new, keep, it + 1

    keep_sorted, _, _ = jax.lax.while_loop(
        cond, body,
        (svalid, jnp.zeros_like(svalid), jnp.zeros((), jnp.int32)))
    return jnp.zeros((k,), dtype=bool).at[order].set(keep_sorted)


def match_anchors_ag(anchors, gt_boxes, gt_valid, pos, neg,
                     gt_crowd=None):
    """The pre-2f1ee08 [A, G] orientation (8 of 128 lanes used;
    profiled fusion.35, 10.8 ms/step) — including BOTH of its full
    [A, G] reductions (the crowd-ignore pass runs even with the
    default all-zero crowd vector, exactly as the production code
    timed as matching_ga still does), so the old-vs-new comparison is
    not biased in old's favor (code review r5c)."""
    from eksml_tpu.ops.boxes import pairwise_iou

    crowd = jnp.zeros_like(gt_valid) if gt_crowd is None else gt_crowd
    target_ok = (gt_valid > 0) & (crowd == 0)
    iou_all = pairwise_iou(anchors, gt_boxes)  # [A, G]
    iou = iou_all * target_ok[None, :].astype(iou_all.dtype)
    best_iou = iou.max(axis=1)
    matched_gt = iou.argmax(axis=1)
    labels = jnp.full(anchors.shape[0], -1, jnp.int32)
    labels = jnp.where(best_iou < neg, 0, labels)
    labels = jnp.where(best_iou >= pos, 1, labels)
    crowd_iou = (iou_all * ((gt_valid > 0) & (crowd > 0))[None, :]
                 ).max(axis=1)
    labels = jnp.where((labels == 0) & (crowd_iou >= neg), -1, labels)
    best_anchor_per_gt = iou.argmax(axis=0)
    force = target_ok & (iou.max(axis=0) > 1e-3)
    labels = labels.at[best_anchor_per_gt].set(
        jnp.where(force, 1, labels[best_anchor_per_gt]))
    has_gt = (target_ok.sum() > 0)
    labels = jnp.where(has_gt, labels,
                       jnp.where(labels == 1, 0, labels))
    return labels, matched_gt


# ---------------------------------------------------------------------
# Realistic inputs: RPN-decoded boxes cluster around objects, which is
# exactly the regime that builds deep suppression chains.
# ---------------------------------------------------------------------

def clustered_boxes(rng, n, img, n_clusters=12):
    centers = rng.rand(n_clusters, 2) * img * 0.8 + img * 0.1
    which = rng.randint(0, n_clusters, size=n)
    ctr = centers[which] + rng.randn(n, 2) * img * 0.02
    size = np.exp(rng.randn(n) * 0.4) * img * 0.08
    ar = np.exp(rng.randn(n) * 0.25)
    w, h = size * ar, size / ar
    x1 = np.clip(ctr[:, 0] - w / 2, 0, img - 2)
    y1 = np.clip(ctr[:, 1] - h / 2, 0, img - 2)
    x2 = np.clip(x1 + w, None, img - 1)
    y2 = np.clip(y1 + h, None, img - 1)
    return np.stack([x1, y1, x2, y2], 1).astype(np.float32)


def timeit(fn, args, iters, warmup=3):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1000.0


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--image-size", type=int, default=1344)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--pre-nms", type=int, default=2000)
    p.add_argument("--nms-thresh", type=float, default=0.7)
    p.add_argument("--ops", default="nms_new,nms_old,nms_new_stacked,"
                   "nms_old_stacked,matching_ga,matching_ag,proposals")
    p.add_argument("--out", default="")
    p.add_argument("--platform", default="")
    p.add_argument("--bank", action="store_true",
                   help="banked-artifact mode (VERDICT r5 next #3): "
                        "timestamp the result and write it to "
                        "<artifacts-dir>/op_microbench_{tpu,cpu}.json "
                        "by the device's platform, so the old-vs-new "
                        "attribution question is answerable from the "
                        "ledger")
    p.add_argument("--artifacts-dir",
                   default=os.path.join(os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__))), "artifacts"))
    args = p.parse_args(argv)
    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    from eksml_tpu.models.rpn import generate_proposals, match_anchors
    from eksml_tpu.ops.anchors import generate_fpn_anchors
    from eksml_tpu.ops.nms import NMS_TILE, nms_mask

    dev = jax.devices()[0]
    rng = np.random.RandomState(0)
    img, K, B = args.image_size, args.pre_nms, args.batch
    L = 5
    thresh = args.nms_thresh

    # [B*L, K] stacked NMS inputs (the production shape after vmap
    # over batch x level), plus a single [K] lane
    stack = np.stack([clustered_boxes(rng, K, img)
                      for _ in range(B * L)])
    sscores = rng.rand(B * L, K).astype(np.float32)
    boxes1, scores1 = jnp.asarray(stack[0]), jnp.asarray(sscores[0])
    boxes_s, scores_s = jnp.asarray(stack), jnp.asarray(sscores)

    strides = (4, 8, 16, 32, 64)
    anchors_np = generate_fpn_anchors(
        (img, img), strides, tuple(s * 8 for s in strides),
        (0.5, 1.0, 2.0))
    A = sum(a.shape[0] for a in anchors_np)
    anchors_all = jnp.asarray(np.concatenate(anchors_np, 0))
    G = 8
    gt = jnp.asarray(np.stack([clustered_boxes(rng, G, img)
                               for _ in range(B)]))
    gt_valid = jnp.asarray((np.arange(G)[None, :]
                            < rng.randint(2, G + 1, (B, 1))
                            ).astype(np.int32))

    # per-level proposal inputs for the end-to-end path
    logits_lv = [jnp.asarray(rng.randn(B, a.shape[0]).astype(np.float32))
                 for a in anchors_np]
    deltas_lv = [jnp.asarray(
        (rng.randn(B, a.shape[0], 4) * 0.1).astype(np.float32))
        for a in anchors_np]
    anchors_lv = [jnp.asarray(a) for a in anchors_np]
    hw = jnp.asarray([[img, img]] * B, jnp.float32)

    ops = {}
    ops["nms_new"] = (jax.jit(lambda b, s: nms_mask(b, s, thresh)),
                      (boxes1, scores1))
    ops["nms_old"] = (jax.jit(lambda b, s: nms_mask_global_fixedpoint(
        b, s, thresh)), (boxes1, scores1))
    ops["nms_new_stacked"] = (jax.jit(jax.vmap(
        lambda b, s: nms_mask(b, s, thresh))), (boxes_s, scores_s))
    ops["nms_old_stacked"] = (jax.jit(jax.vmap(
        lambda b, s: nms_mask_global_fixedpoint(b, s, thresh))),
        (boxes_s, scores_s))
    ops["matching_ga"] = (jax.jit(jax.vmap(
        lambda g, v: match_anchors(anchors_all, g, v, 0.7, 0.3))),
        (gt, gt_valid))
    ops["matching_ag"] = (jax.jit(jax.vmap(
        lambda g, v: match_anchors_ag(anchors_all, g, v, 0.7, 0.3))),
        (gt, gt_valid))
    ops["proposals"] = (jax.jit(jax.vmap(
        lambda lg, dl, h: generate_proposals(
            lg, dl, anchors_lv, h, K, 512, thresh),
        in_axes=(0, 0, 0))),
        (logits_lv, deltas_lv, hw))

    wanted = [w.strip() for w in args.ops.split(",") if w.strip()]
    bad = [w for w in wanted if w not in ops]
    if bad:
        raise SystemExit(f"unknown ops {bad}; known: {sorted(ops)}")

    results = {}
    for name in wanted:
        fn, a = ops[name]
        try:
            results[name] = round(timeit(fn, a, args.iters), 3)
        except Exception as e:  # noqa: BLE001 — record, keep measuring
            results[name] = f"ERROR: {type(e).__name__}: {e}"[:300]
        print(f"{name}: {results[name]}", file=sys.stderr)

    out = {
        "device_kind": dev.device_kind,
        "params": {"image_size": img, "batch": B, "pre_nms": K,
                   "levels": L, "anchors_total": int(A),
                   "iters": args.iters,
                   "nms_tile": NMS_TILE},
        "results": results,
        "unit": "ms_per_call",
    }
    # the question this tool exists to answer, precomputed: how much
    # did each rewrite actually move on identical inputs (negative =
    # the new formulation is faster)
    deltas = {}
    for new, old in (("nms_new", "nms_old"),
                     ("nms_new_stacked", "nms_old_stacked"),
                     ("matching_ga", "matching_ag")):
        a, b = results.get(new), results.get(old)
        if isinstance(a, (int, float)) and isinstance(b, (int, float)):
            deltas[f"{new}_minus_{old}"] = round(a - b, 3)
    if deltas:
        out["new_minus_old_ms"] = deltas
    line = json.dumps(out)
    print(line)
    if args.out:
        tmp = args.out + ".tmp"
        with open(tmp, "w") as f:
            f.write(line + "\n")
        os.replace(tmp, args.out)
    if args.bank:
        # a CPU run self-labels instead of masquerading as the TPU
        # answer the round is waiting on
        out["banked_at"] = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                         time.gmtime())
        name = ("op_microbench_cpu.json" if dev.platform == "cpu"
                else "op_microbench_tpu.json")
        path = os.path.join(args.artifacts_dir, name)
        os.makedirs(args.artifacts_dir, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write(json.dumps(out) + "\n")
        os.replace(tmp, path)
        print(f"op_microbench: banked to {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
