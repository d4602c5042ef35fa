"""NMS tests: fixed-shape greedy NMS vs a numpy greedy reference."""

import numpy as np
import jax.numpy as jnp

from eksml_tpu.ops import batched_nms, nms_mask
from eksml_tpu.ops.nms import class_aware_nms


def _np_greedy_nms(boxes, scores, thresh):
    order = np.argsort(-scores)
    keep = []
    suppressed = np.zeros(len(boxes), bool)
    for i in order:
        if not np.isfinite(scores[i]) or suppressed[i]:
            continue
        keep.append(i)
        for j in order:
            if j == i or suppressed[j]:
                continue
            xx1 = max(boxes[i, 0], boxes[j, 0]); yy1 = max(boxes[i, 1], boxes[j, 1])
            xx2 = min(boxes[i, 2], boxes[j, 2]); yy2 = min(boxes[i, 3], boxes[j, 3])
            inter = max(xx2 - xx1, 0) * max(yy2 - yy1, 0)
            a = (boxes[i, 2] - boxes[i, 0]) * (boxes[i, 3] - boxes[i, 1])
            b = (boxes[j, 2] - boxes[j, 0]) * (boxes[j, 3] - boxes[j, 1])
            u = a + b - inter
            if u > 0 and inter / u > thresh and scores[j] < scores[i]:
                suppressed[j] = True
    return sorted(keep)


def _rand_cluster_boxes(n):
    # clusters of overlapping boxes so NMS actually suppresses
    centers = np.random.rand(n // 4 + 1, 2) * 80
    boxes = []
    for _ in range(n):
        c = centers[np.random.randint(len(centers))]
        jitter = np.random.randn(2) * 3
        wh = np.random.rand(2) * 20 + 10
        xy = c + jitter
        boxes.append([xy[0], xy[1], xy[0] + wh[0], xy[1] + wh[1]])
    return np.asarray(boxes, np.float32)


def test_nms_mask_matches_numpy():
    n = 64
    boxes = _rand_cluster_boxes(n)
    scores = np.random.rand(n).astype(np.float32)
    keep = np.asarray(nms_mask(jnp.asarray(boxes), jnp.asarray(scores), 0.5))
    expected = _np_greedy_nms(boxes, scores, 0.5)
    assert sorted(np.nonzero(keep)[0].tolist()) == expected


def test_nms_padding_excluded():
    boxes = np.zeros((8, 4), np.float32)
    boxes[:2] = [[0, 0, 10, 10], [100, 100, 110, 110]]
    scores = np.full(8, -np.inf, np.float32)
    scores[:2] = [0.9, 0.8]
    keep = np.asarray(nms_mask(jnp.asarray(boxes), jnp.asarray(scores), 0.5))
    assert keep[:2].all() and not keep[2:].any()


def test_batched_nms_shapes_and_validity():
    b, k, m = 3, 32, 8
    boxes = np.stack([_rand_cluster_boxes(k) for _ in range(b)])
    scores = np.random.rand(b, k).astype(np.float32)
    idx, top_scores, valid = batched_nms(jnp.asarray(boxes),
                                         jnp.asarray(scores), 0.5, m)
    assert idx.shape == (b, k)[:1] + (m,)
    assert top_scores.shape == (b, m) and valid.shape == (b, m)
    # top scores are descending where valid
    ts = np.asarray(top_scores)
    v = np.asarray(valid)
    for i in range(b):
        s = ts[i][v[i]]
        assert (np.diff(s) <= 1e-6).all()


def test_class_aware_nms_keeps_cross_class_overlaps():
    boxes = jnp.asarray([[0, 0, 10, 10], [1, 1, 11, 11]], dtype=jnp.float32)
    scores = jnp.asarray([0.9, 0.8])
    cls = jnp.asarray([1, 2])
    _, s, valid = class_aware_nms(boxes, scores, 0.5, 2, class_ids=cls)
    assert np.asarray(valid).all()  # different classes → both kept
    _, _, valid_same = class_aware_nms(boxes, scores, 0.5, 2,
                                       class_ids=jnp.asarray([1, 1]))
    assert np.asarray(valid_same).sum() == 1


def test_fixed_point_equals_sequential_greedy():
    """The while-loop fixed point must reproduce exact greedy NMS,
    including multi-level suppression chains (A kills B, so B cannot
    kill C)."""
    from eksml_tpu.ops.nms import nms_mask, nms_mask_sequential

    rng = np.random.RandomState(0)
    for trial in range(8):
        n = 64
        ctr = rng.rand(n, 2) * 60
        wh = rng.rand(n, 2) * 30 + 5
        boxes = jnp.asarray(np.concatenate([ctr, ctr + wh], 1)
                            .astype(np.float32))
        scores = jnp.asarray(rng.rand(n).astype(np.float32))
        # add padding rows
        boxes = jnp.concatenate([boxes, jnp.zeros((8, 4))])
        scores = jnp.concatenate([scores, jnp.full((8,), -jnp.inf)])
        a = np.asarray(nms_mask(boxes, scores, 0.5))
        b = np.asarray(nms_mask_sequential(boxes, scores, 0.5))
        np.testing.assert_array_equal(a, b, err_msg=f"trial {trial}")


def test_fixed_point_chain():
    # hand-built chain: A(0.9) suppresses B(0.8); B would suppress
    # C(0.7) but is dead, so C survives
    boxes = jnp.asarray([[0, 0, 10, 10],
                         [0, 0, 10, 8],      # IoU(A,B)=0.8
                         [0, 6.5, 10, 14]],  # IoU(B,C)~0.51, IoU(A,C)~0.27
                        jnp.float32)
    scores = jnp.asarray([0.9, 0.8, 0.7])
    from eksml_tpu.ops.nms import nms_mask

    keep = np.asarray(nms_mask(boxes, scores, 0.5))
    assert keep.tolist() == [True, False, True]


def test_tiled_multi_tile_equals_sequential():
    """Exactness across tile boundaries: with a small tile size, random
    clustered boxes spanning many tiles must still match the O(K)-step
    greedy recurrence (cross-tile suppression + per-tile fixed point)."""
    from eksml_tpu.ops.nms import nms_mask, nms_mask_sequential

    rng = np.random.RandomState(7)
    for trial, (n, tile) in enumerate([(100, 16), (97, 32), (256, 64),
                                       (130, 128), (33, 8)]):
        ctr = rng.rand(n, 2) * 50
        wh = rng.rand(n, 2) * 30 + 5
        boxes = jnp.asarray(np.concatenate([ctr, ctr + wh], 1)
                            .astype(np.float32))
        scores = jnp.asarray(rng.rand(n).astype(np.float32))
        a = np.asarray(nms_mask(boxes, scores, 0.5, tile=tile))
        b = np.asarray(nms_mask_sequential(boxes, scores, 0.5))
        np.testing.assert_array_equal(a, b, err_msg=f"trial {trial}")


def test_tiled_chain_spans_tiles():
    """A suppression chain laid across tile boundaries: box i overlaps
    only box i+1 (IoU≈0.54) with descending scores, so greedy keeps
    every EVEN-ranked box.  With tile=4 the chain's keep/kill
    alternation must propagate through cross-tile suppression."""
    from eksml_tpu.ops.nms import nms_mask

    n = 16
    boxes = np.zeros((n, 4), np.float32)
    for i in range(n):
        # unit-height boxes slid by 0.3: IoU(i, i+1) = 0.7/1.3 ≈ 0.54,
        # IoU(i, i+2) = 0.4/1.6 = 0.25 < 0.5
        boxes[i] = [i * 0.3, 0, i * 0.3 + 1.0, 1.0]
    scores = np.linspace(0.9, 0.1, n).astype(np.float32)
    keep = np.asarray(nms_mask(jnp.asarray(boxes), jnp.asarray(scores),
                               0.5, tile=4))
    assert keep.tolist() == [i % 2 == 0 for i in range(n)]


def test_tiled_padding_not_multiple_of_tile():
    """K deliberately not a multiple of tile: internal -inf padding
    rows must neither keep nor suppress."""
    from eksml_tpu.ops.nms import nms_mask, nms_mask_sequential

    rng = np.random.RandomState(3)
    n = 45
    ctr = rng.rand(n, 2) * 30
    wh = rng.rand(n, 2) * 20 + 4
    boxes = jnp.asarray(np.concatenate([ctr, ctr + wh], 1)
                        .astype(np.float32))
    scores = jnp.asarray(rng.rand(n).astype(np.float32))
    a = np.asarray(nms_mask(boxes, scores, 0.5, tile=32))
    b = np.asarray(nms_mask_sequential(boxes, scores, 0.5))
    np.testing.assert_array_equal(a, b)


def test_stacked_level_nms_equals_per_level_loop():
    """models/rpn.py stacks unequal-k levels into one [L, kmax] vmapped
    nms_mask call (padding with zero-area/-inf rows).  The stack must
    reproduce a plain per-level loop exactly, including on levels
    shorter than kmax."""
    import jax

    rng = np.random.RandomState(5)
    level_ks = [96, 96, 96, 40, 13]   # mimics P2-P5 at pre_nms_topk + short P6
    kmax = max(level_ks)
    per_level, stack_b, stack_s = [], [], []
    for k in level_ks:
        ctr = rng.rand(k, 2) * 60
        wh = rng.rand(k, 2) * 30 + 5
        b = np.concatenate([ctr, ctr + wh], 1).astype(np.float32)
        s = rng.rand(k).astype(np.float32)
        per_level.append(np.asarray(
            nms_mask(jnp.asarray(b), jnp.asarray(s), 0.5, tile=32)))
        stack_b.append(np.pad(b, ((0, kmax - k), (0, 0))))
        stack_s.append(np.pad(s, (0, kmax - k),
                              constant_values=-np.inf))
    keep = jax.vmap(
        lambda bb, ss: nms_mask(bb, ss, 0.5, tile=32))(
        jnp.asarray(np.stack(stack_b)), jnp.asarray(np.stack(stack_s)))
    keep = np.asarray(keep)
    for lvl, k in enumerate(level_ks):
        np.testing.assert_array_equal(
            keep[lvl, :k], per_level[lvl], err_msg=f"level {lvl}")
        assert not keep[lvl, k:].any()   # padding never kept


def test_nms_tile_argument():
    """``tile`` is validated, and a small one (several tiles, padding
    in the last) gives the sequential recurrence's mask."""
    import pytest

    from eksml_tpu.ops.nms import nms_mask_sequential

    rng = np.random.RandomState(11)
    ctr = rng.rand(27, 2) * 40
    boxes = jnp.asarray(np.concatenate(
        [ctr, ctr + rng.rand(27, 2) * 30 + 5], 1).astype(np.float32))
    scores = jnp.asarray(rng.rand(27).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(nms_mask(boxes, scores, 0.5, tile=8)),
        np.asarray(nms_mask_sequential(boxes, scores, 0.5)))
    with pytest.raises(ValueError, match="tile size must be positive"):
        nms_mask(boxes, scores, 0.5, tile=0)


def test_microbench_vendored_old_nms_agrees():
    """tools/op_microbench.py vendors the pre-tiling global fixed
    point for on-device old-vs-new attribution; the comparison is only
    meaningful if the vendored copy still computes exact greedy NMS —
    pin it to the production mask on clustered inputs."""
    from tools.op_microbench import nms_mask_global_fixedpoint

    np.random.seed(5)
    for _ in range(3):
        boxes = _rand_cluster_boxes(96)
        scores = np.random.rand(96).astype(np.float32)
        scores[::7] = -np.inf  # padding lanes stay inert in both
        new = np.asarray(nms_mask(jnp.asarray(boxes),
                                  jnp.asarray(scores), 0.5))
        old = np.asarray(nms_mask_global_fixedpoint(
            jnp.asarray(boxes), jnp.asarray(scores), 0.5))
        np.testing.assert_array_equal(new, old)
