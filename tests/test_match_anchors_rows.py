"""Anchor matching one ground-truth row at a time
(``rpn.match_reductions``) against the dense ``[G, A]`` IoU reductions
it replaced (the oracle below): the five vectors and ``match_anchors``'
labels and matches bit for bit, no ``[G, A]`` array in the program, and
a tiny detector's training step on either."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eksml_tpu.models import rpn
from eksml_tpu.ops.anchors import generate_fpn_anchors
from eksml_tpu.ops.boxes import pairwise_iou

CANVAS = 128


def _anchors(duplicates=0, extra=0, seed=0):
    """The FPN anchors of a 128 px canvas (4,092), with ``duplicates``
    of them repeated at the end (exact ties between anchors) and
    ``extra`` random boxes after those."""
    levels = generate_fpn_anchors((CANVAS, CANVAS), (4, 8, 16, 32, 64),
                                  (32, 64, 128, 256, 512), (0.5, 1.0, 2.0))
    a = np.concatenate(levels, 0).astype(np.float32)
    rng = np.random.RandomState(seed)
    parts = [a, a[rng.choice(len(a), duplicates, replace=False)],
             _boxes(rng, extra)]
    return np.concatenate(parts, 0)


def _boxes(rng, n):
    xy = rng.uniform(-8, CANVAS, (n, 2))
    wh = rng.uniform(2, 90, (n, 2))
    return np.concatenate([xy, xy + wh], 1).astype(np.float32)


def _case(case):
    """(anchors, gt_boxes, gt_valid, gt_crowd) of each case.  Rows that
    are not valid hold boxes of their own (not the loader's zeros), so
    a row the loop should skip and does not shows."""
    rng = np.random.RandomState(len(case))
    g, dup = 12, 0
    valid = np.zeros(g, np.float32)
    crowd = np.zeros(g, np.float32)
    if case == "ties":
        # duplicate anchors, duplicate rows, a row equal to an anchor
        # that is repeated later (a row's best anchor ties), a row that
        # overlaps nothing
        dup = 300
        anchors = _anchors(duplicates=dup)
        gt = _boxes(rng, g)
        gt[3] = gt[1]
        gt[6] = anchors[len(anchors) - dup + 5]
        gt[7] = [500, 500, 520, 530]
        valid[:9] = 1
    elif case == "crowd":
        anchors = _anchors(extra=100)
        gt = _boxes(rng, g)
        gt[4] = gt[2]
        valid[:10] = 1
        crowd[[2, 5, 9]] = 1
    elif case == "no_valid_row":
        anchors = _anchors()
        gt = _boxes(rng, g)
        crowd[[1, 2]] = 1
    elif case == "all_100_valid":
        g = 100
        anchors = _anchors(duplicates=40)
        gt = _boxes(rng, g)
        gt[50] = gt[10]
        valid = np.ones(g, np.float32)
        crowd = np.zeros(g, np.float32)
    elif case == "not_a_prefix":
        anchors = _anchors()
        gt = _boxes(rng, g)
        valid[[2, 5, 6, 11]] = 1
        crowd[[6, 8]] = 1
    else:  # "only_crowd_rows": no target row, two crowd rows
        anchors = _anchors(extra=57)
        gt = _boxes(rng, g)
        valid[[3, 7]] = 1
        crowd[[3, 7]] = 1
    return (jnp.asarray(anchors), jnp.asarray(gt), jnp.asarray(valid),
            jnp.asarray(crowd))


CASES = ["ties", "crowd", "no_valid_row", "all_100_valid", "not_a_prefix",
         "only_crowd_rows"]


def dense_match_reductions(anchors, gt_boxes, target_ok, crowd_ok):
    """The oracle: ``match_anchors``' reductions as it formed them over
    the dense ``[G, A]`` IoU matrix, rows outside ``target_ok`` (and
    outside ``crowd_ok`` for the crowd maximum) multiplied by 0."""
    iou_all = pairwise_iou(gt_boxes, anchors)  # [G, A]
    iou = iou_all * target_ok[:, None].astype(iou_all.dtype)
    crowd_iou = (iou_all * crowd_ok[:, None]).max(axis=0)
    return (iou.max(axis=0), iou.argmax(axis=0), crowd_iou, iou.max(axis=1),
            iou.argmax(axis=1))


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


@pytest.mark.parametrize("case", CASES)
def test_the_row_loop_is_the_dense_matching_bit_for_bit(case, monkeypatch):
    """The five reductions, then ``match_anchors``' labels and matches
    against the same arithmetic on the oracle's, to the bit."""
    anchors, gt, valid, crowd = _case(case)
    target_ok = (valid > 0) & (crowd == 0)
    crowd_ok = (valid > 0) & (crowd > 0)
    want = dense_match_reductions(anchors, gt, target_ok, crowd_ok)
    got = jax.jit(rpn.match_reductions)(anchors, gt, target_ok, crowd_ok)
    names = ("best_iou", "matched_gt", "crowd_iou", "gt_best_iou",
             "best_anchor")
    for name, a, b in zip(names, got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_array_equal(_bits(a), _bits(b), err_msg=name)
    # the cases hold what they are named for
    if case == "ties":
        # row 6 is a repeated anchor: IoU 1 at the first copy
        assert float(got[3][6]) == 1.0
        assert int(got[4][6]) < anchors.shape[0] - 300
        assert not np.asarray(got[1] == 3).any()   # row 1 wins its ties
        assert float(got[3][7]) == 0.0 and int(got[4][7]) == 0
    if case in ("no_valid_row", "only_crowd_rows"):
        assert not np.asarray(got[0]).any()
    assert np.asarray(got[2]).any() == bool(np.asarray(crowd_ok).any())
    labels = rpn.match_anchors(anchors, gt, valid, 0.7, 0.3, gt_crowd=crowd)
    monkeypatch.setattr(rpn, "match_reductions", dense_match_reductions)
    oracle = rpn.match_anchors(anchors, gt, valid, 0.7, 0.3, gt_crowd=crowd)
    for a, b in zip(labels, oracle):
        np.testing.assert_array_equal(a, b)
    assert (np.asarray(labels[0]) == 1).any() == bool(
        np.asarray(target_ok).any())


def test_no_dense_iou_matrix_at_the_cells_size():
    """At the detector cells' size (4 images of ``DATA.MAX_GT_BOXES`` =
    100 rows against the 451,143 anchors of a 1344 px canvas) no array
    of the matching holds a row per ground-truth row and an anchor per
    column: the IoUs are one row's at a time, in a loop.  Traced, not
    compiled."""
    a, g = 451_143, 100

    def match(anchors, gt, valid, crowd):
        return jax.vmap(lambda b, v, c: rpn.match_anchors(
            anchors, b, v, 0.7, 0.3, gt_crowd=c))(gt, valid, crowd)

    from test_models import _eqns

    closed = jax.make_jaxpr(match)(
        jnp.zeros((a, 4)), jnp.zeros((4, g, 4)), jnp.zeros((4, g)),
        jnp.zeros((4, g)))
    shapes = {tuple(v.aval.shape) for e in _eqns(closed.jaxpr)
              for v in e.outvars}
    assert not [s for s in shapes if g in s and a in s], shapes
    assert (4, a) in shapes     # the rows' IoUs are there, one at a time
    assert any(e.primitive.name == "while" for e in _eqns(closed.jaxpr))


def test_a_training_step_on_the_row_loop_is_the_dense_forms(monkeypatch):
    """A tiny ``MaskRCNN``'s losses and every parameter's gradient, to
    the bit, with the matching one row at a time and with the oracle's
    dense reductions; the step's ``match_gt_rows`` is the batch mean of
    the valid rows, which the loop visits."""
    from test_models import tiny_batch, tiny_model

    model = tiny_model(with_masks=True)
    batch = tiny_batch()
    # a crowd row and valid rows that are not a prefix
    batch["gt_valid"] = jnp.asarray([[1, 0, 1, 1, 0, 1], [0, 1, 1, 0, 0, 0]],
                                    jnp.float32)
    batch["gt_crowd"] = jnp.asarray([[0, 0, 0, 1, 0, 0], [0] * 6],
                                    jnp.float32)
    rng = jax.random.PRNGKey(0)
    params = model.init(rng, batch, rng)["params"]

    def step():
        def loss_fn(p):
            losses = model.apply({"params": p}, batch, rng)
            return losses["total_loss"], losses

        return jax.jit(jax.grad(loss_fn, has_aux=True))(params)

    g_loop, l_loop = step()
    monkeypatch.setattr(rpn, "match_reductions", dense_match_reductions)
    g_dense, l_dense = step()
    assert set(l_loop) == set(l_dense)
    for key in l_dense:
        np.testing.assert_array_equal(l_loop[key], l_dense[key], err_msg=key)
    for a, b in zip(jax.tree.leaves(g_loop), jax.tree.leaves(g_dense)):
        np.testing.assert_array_equal(a, b)
    assert float(l_loop["match_gt_rows"]) == 3.0   # (4 + 2) / 2
    assert float(l_loop["rpn_box_loss"]) > 0
