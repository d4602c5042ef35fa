"""The one general traffic generator: a mix's parameters (data, in
``benchmark/mixes/<traffic>.json``, found by the cell's ``traffic``
name) and ``--seed`` give the records the program's loader is fed.
The program gets only records.

Every seed gets the SAME multiset of image sizes and instance counts
(exact shares, not draws), in another order and with other pixels,
boxes and polygons: the seed changes the data, not the amount of work.

Parameters: ``records`` (count), ``sizes`` ([[height, width, share],
..]), ``instances`` ([lo, hi] per image, every count equally often) or
``instance_counts`` ([[count, share], ..], any histogram, e.g. a
dataset's own), ``polygon_vertices``, ``box_side_px`` ([lo, hi],
log-uniform), ``num_classes``.
"""

from __future__ import annotations

import numpy as np


def effective_seed(seed: int) -> int:
    """``--seed`` may exceed 32 signed bits; numpy and the program's
    ``PRNGKey(int)`` take less.  One fold, used everywhere."""
    return int(seed) % 2147483647


def _exact_shares(n, shares):
    """Counts summing to n, each within one of n*share (largest
    remainder)."""
    raw = [n * s for s in shares]
    counts = [int(x) for x in raw]
    order = sorted(range(len(raw)), key=lambda i: raw[i] - counts[i],
                   reverse=True)
    for i in order[:n - sum(counts)]:
        counts[i] += 1
    return counts


def _polygon(rng, cx, cy, rx, ry, vertices):
    """A star-shaped polygon around (cx, cy): jittered radii on evenly
    spaced angles, as one flat COCO list [x0, y0, x1, y1, ...]."""
    ang = (np.arange(vertices) + rng.uniform(0, 1)) * (2 * np.pi / vertices)
    rad = rng.uniform(0.6, 1.0, vertices)
    xs = cx + rx * rad * np.cos(ang)
    ys = cy + ry * rad * np.sin(ang)
    return np.stack([xs, ys], axis=1)


def generate(params: dict, seed: int) -> list:
    rng = np.random.RandomState(effective_seed(seed))
    n = int(params["records"])
    sizes = params["sizes"]
    size_counts = _exact_shares(n, [s[2] for s in sizes])
    size_of = np.repeat(np.arange(len(sizes)), size_counts)
    if "instance_counts" in params:
        hist = params["instance_counts"]
        inst = np.repeat([int(c[0]) for c in hist],
                         _exact_shares(n, [c[1] for c in hist]))
    else:
        lo, hi = params["instances"]
        inst = np.resize(np.arange(lo, hi + 1), n)
    rng.shuffle(size_of)
    rng.shuffle(inst)
    side_lo, side_hi = params["box_side_px"]
    verts = int(params["polygon_vertices"])
    records = []
    for i in range(n):
        h, w = int(sizes[size_of[i]][0]), int(sizes[size_of[i]][1])
        k = int(inst[i])
        boxes = np.zeros((k, 4), np.float32)
        segs = []
        for j in range(k):
            bw = min(np.exp(rng.uniform(np.log(side_lo), np.log(side_hi))),
                     0.9 * w)
            bh = min(np.exp(rng.uniform(np.log(side_lo), np.log(side_hi))),
                     0.9 * h)
            cx = rng.uniform(bw / 2, w - 1 - bw / 2)
            cy = rng.uniform(bh / 2, h - 1 - bh / 2)
            poly = _polygon(rng, cx, cy, bw / 2, bh / 2, verts)
            # the box is the polygon's own extent, as COCO derives it
            boxes[j] = [poly[:, 0].min(), poly[:, 1].min(),
                        poly[:, 0].max(), poly[:, 1].max()]
            segs.append([poly.reshape(-1).tolist()])
        records.append({
            "image_id": i, "path": None, "height": h, "width": w,
            "boxes": boxes,
            "classes": rng.randint(1, int(params["num_classes"]),
                                   k).astype(np.int32),
            "iscrowd": np.zeros(k, np.int32),
            "segmentation": segs,
            "_image": rng.randint(0, 256, (h, w, 3), dtype=np.uint8),
        })
    return records


def feed(gen, count=None, deadline=None, clock=None, on_batch=None):
    """Batches from the loader's generator ``gen``: ``count`` of them,
    or until ``clock() >= deadline``.  ``on_batch`` sees every host
    batch handed on."""
    n = 0
    while True:
        if count is not None and n >= count:
            return
        if deadline is not None and clock() >= deadline:
            return
        batch = next(gen)
        if on_batch is not None:
            on_batch(batch)
        n += 1
        yield batch
