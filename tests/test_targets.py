"""Target-assignment tests: anchor matching, crowd handling, sampling."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from eksml_tpu.models.rpn import (match_anchors, rpn_losses, sample_anchors,
                                  smooth_l1)
from eksml_tpu.ops.boxes import encode_boxes
from eksml_tpu.models.heads import (max_fg_proposals,
                                    sample_proposal_targets)
from eksml_tpu.ops.sampling import sample_by_priority, sample_mask_by_priority


def test_match_anchors_basic():
    anchors = jnp.asarray([
        [0, 0, 10, 10],      # matches gt0 exactly
        [100, 100, 110, 110],  # far from everything → bg
        [0, 0, 9, 10],       # high IoU with gt0
    ], dtype=jnp.float32)
    gt = jnp.asarray([[0, 0, 10, 10], [0, 0, 0, 0]], dtype=jnp.float32)
    valid = jnp.asarray([1.0, 0.0])
    labels, matched = match_anchors(anchors, gt, valid, 0.7, 0.3)
    assert int(labels[0]) == 1
    assert int(labels[1]) == 0
    assert int(labels[2]) == 1
    assert int(matched[0]) == 0


def test_match_anchors_padding_never_positive():
    anchors = jnp.asarray([[0, 0, 10, 10]], dtype=jnp.float32)
    gt = jnp.zeros((3, 4))
    valid = jnp.zeros(3)
    labels, _ = match_anchors(anchors, gt, valid, 0.7, 0.3)
    assert int(labels[0]) == 0  # no GT → everything bg, never fg


def test_match_anchors_crowd_ignored_not_negative():
    anchors = jnp.asarray([
        [0, 0, 10, 10],        # overlaps the crowd region
        [50, 50, 60, 60],      # overlaps real GT
        [200, 200, 210, 210],  # clean background
    ], dtype=jnp.float32)
    gt = jnp.asarray([[0, 0, 10, 10], [50, 50, 60, 60]], dtype=jnp.float32)
    valid = jnp.asarray([1.0, 1.0])
    crowd = jnp.asarray([1.0, 0.0])
    labels, matched = match_anchors(anchors, gt, valid, 0.7, 0.3,
                                    gt_crowd=crowd)
    assert int(labels[0]) == -1  # crowd overlap → ignore, not bg, not fg
    assert int(labels[1]) == 1 and int(matched[1]) == 1
    assert int(labels[2]) == 0


def test_sample_by_priority_counts_and_limit():
    cand = jnp.asarray([True] * 10 + [False] * 20)
    idx, take = sample_by_priority(cand, jax.random.PRNGKey(0), 16)
    assert int(take.sum()) == 10  # only 10 candidates exist
    assert set(np.asarray(idx[np.asarray(take)])) <= set(range(10))
    _, take2 = sample_by_priority(cand, jax.random.PRNGKey(0), 16,
                                  limit=jnp.asarray(4))
    assert int(take2.sum()) == 4


def test_sample_anchors_respects_budget():
    labels = jnp.asarray([1] * 5 + [0] * 500 + [-1] * 10)
    fg, bg, fg_idx, fg_take = sample_anchors(
        labels, jax.random.PRNGKey(1), 64, 0.5)
    assert int(fg.sum()) == 5          # all fg kept (≤ 32)
    assert int(bg.sum()) == 64 - 5     # bg fills the rest
    assert not np.asarray(fg & bg).any()
    # the draw itself: k = int(64 * 0.5) slots, the real picks exactly
    # the set bits of the mask
    assert fg_idx.shape == fg_take.shape == (32,)
    assert sorted(np.asarray(fg_idx)[np.asarray(fg_take)]) == list(
        np.flatnonzero(np.asarray(fg)))


def _dense_box_term(deltas, anchors, matched_gt, gt_boxes, fg_mask, n_sel):
    """The oracle: the box term as ``rpn_losses`` formed it before it
    read the sampled rows: a ground-truth row picked for every anchor,
    every anchor encoded, all but ``fg_mask``'s thrown away."""
    box_targets = encode_boxes(gt_boxes[matched_gt], anchors)
    box_loss_all = smooth_l1(deltas - box_targets, beta=1.0 / 9).sum(-1)
    return jnp.where(fg_mask, box_loss_all, 0.0).sum() / n_sel


def _box_term_case(case):
    """(labels, matched_gt, gt_boxes, dtype of deltas) over 600 anchors
    and 8 ground-truth rows of which the last 3 are all-zero padding;
    the budget is 64 at ratio 0.5, so k = 32."""
    rng = np.random.RandomState(3)
    a, g = 600, 8
    labels = np.zeros(a, np.int32)
    labels[rng.choice(a, 40, replace=False)] = -1
    n_fg = {"some_fg": 11, "no_fg": 0, "more_than_k": 90,
            "padded_gt_rows": 20, "bf16_deltas": 11}[case]
    labels[rng.choice(np.flatnonzero(labels == 0), n_fg,
                      replace=False)] = 1
    matched = rng.randint(0, 5, a)
    if case == "padded_gt_rows":
        # foreground anchors matched to all-zero rows too (as every
        # slot without a pick may be): log(EPS / aw), finite
        matched[labels == 1] = rng.randint(3, g, int((labels == 1).sum()))
    xy = rng.uniform(0, 200, (g, 2))
    gt = np.concatenate([xy, xy + rng.uniform(8, 120, (g, 2))], 1)
    gt[5:] = 0.0
    dtype = jnp.bfloat16 if case == "bf16_deltas" else jnp.float32
    return (jnp.asarray(labels), jnp.asarray(matched),
            jnp.asarray(gt, jnp.float32), dtype)


@pytest.mark.parametrize("case", ["some_fg", "no_fg", "more_than_k",
                                  "padded_gt_rows", "bf16_deltas"])
def test_sampled_row_box_term_equals_the_dense_masked_form(case):
    """``rpn_losses`` forms the box term on ``sample_anchors``' k rows;
    value and gradient with respect to ``deltas`` are the dense masked
    form's (the same sum over the same rows)."""
    labels, matched, gt, dtype = _box_term_case(case)
    a = labels.shape[0]
    rng = np.random.RandomState(4)
    xy = rng.uniform(0, 200, (a, 2))
    anchors = jnp.asarray(
        np.concatenate([xy, xy + rng.uniform(4, 150, (a, 2))], 1),
        jnp.float32)
    logits = jnp.asarray(rng.normal(size=a), jnp.float32)
    deltas = jnp.asarray(rng.normal(size=(a, 4)) * 0.5, dtype)
    fg, bg, fg_idx, fg_take = sample_anchors(
        labels, jax.random.PRNGKey(5), 64, 0.5)
    n_fg = int((labels == 1).sum())
    assert int(fg_take.sum()) == min(n_fg, 32)

    def new(d):
        return rpn_losses(logits, d, anchors, labels, matched, gt, fg, bg,
                          fg_idx, fg_take)[1].astype(jnp.float32)

    def dense(d):
        n_sel = jnp.maximum((fg | bg).sum(), 1)
        return _dense_box_term(d, anchors, matched, gt, fg,
                               n_sel).astype(jnp.float32)

    (v_new, g_new), (v_old, g_old) = (
        jax.value_and_grad(f)(deltas) for f in (new, dense))
    assert g_new.dtype == deltas.dtype and g_new.shape == deltas.shape
    g_new, g_old = (np.asarray(x, np.float32) for x in (g_new, g_old))
    assert np.isfinite(float(v_new)) and np.isfinite(g_new).all()
    np.testing.assert_allclose(float(v_new), float(v_old), rtol=1e-6,
                               atol=0)
    np.testing.assert_allclose(g_new, g_old, rtol=1e-6, atol=0)
    # rows outside the draw get no gradient; with none drawn, nothing
    assert not g_new[~np.asarray(fg)].any()
    if case == "no_fg":
        assert float(v_new) == 0.0 and not g_new.any()
    else:
        assert float(v_new) > 0 and g_new[np.asarray(fg)].any()


def test_sample_proposal_targets_static_shapes():
    p = 20
    props = jnp.asarray(np.random.rand(p, 4) * 50 +
                        np.array([0, 0, 30, 30]), jnp.float32)
    scores = jnp.where(jnp.arange(p) < 15, 0.5, -jnp.inf)
    gt = jnp.asarray([[10, 10, 40, 40], [0, 0, 0, 0]], jnp.float32)
    gt_cls = jnp.asarray([3, 0])
    gt_valid = jnp.asarray([1.0, 0.0])
    rois, labels, matched, fg, valid = sample_proposal_targets(
        props, scores, gt, gt_cls, gt_valid, jax.random.PRNGKey(0),
        batch_per_im=16, fg_thresh=0.5, fg_ratio=0.25)
    assert rois.shape == (16, 4) and labels.shape == (16,)
    assert int(fg.sum()) >= 1  # GT added to pool guarantees a positive
    # fg rois carry the GT class, bg rois class 0
    lab = np.asarray(labels)
    assert (lab[np.asarray(fg)] == 3).all()
    assert (lab[~np.asarray(fg)] == 0).all()


def test_fg_proposals_occupy_leading_slots():
    """The mask head slices the FIRST int(S·fg_ratio) slots instead of
    running on all S sampled ROIs (mask_rcnn.py mask-head section) —
    valid only because the sampler compacts taken-fg entries to the
    front.  Pin that invariant: every fg slot index < max_fg, and the
    fg region is a prefix of the taken-fg count, across seeds."""
    p = 64
    rng = np.random.RandomState(7)
    for seed in range(5):
        props = jnp.asarray(rng.rand(p, 4) * 60 +
                            np.array([0, 0, 20, 20]), jnp.float32)
        scores = jnp.where(jnp.arange(p) < 50, 0.5, -jnp.inf)
        gt = jnp.asarray([[10, 10, 40, 40], [30, 30, 70, 70]],
                         jnp.float32)
        gt_cls = jnp.asarray([3, 5])
        gt_valid = jnp.asarray([1.0, 1.0])
        _, _, _, fg, _ = sample_proposal_targets(
            props, scores, gt, gt_cls, gt_valid,
            jax.random.PRNGKey(seed), batch_per_im=16,
            fg_thresh=0.5, fg_ratio=0.25)
        fg = np.asarray(fg)
        max_fg = max_fg_proposals(16, 0.25)
        n_fg = int(fg.sum())
        assert fg[:n_fg].all(), fg          # fg is a contiguous prefix
        assert not fg[n_fg:].any(), fg
        assert n_fg <= max_fg
