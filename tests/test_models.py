"""Model tests: backbone/FPN shapes, FrozenBN semantics, npz loader
round-trip, and a tiny end-to-end train forward + gradients.

A reduced MaskRCNN (1-block stages, 32-ch FPN, small proposal counts)
keeps CPU compiles tractable; shapes and code paths are the same as the
full R50 model.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from eksml_tpu.models import (FPN, MaskRCNN, ResNetBackbone, load_r50_npz)
from eksml_tpu.models.backbone_loader import save_r50_npz
from eksml_tpu.models.mask_rcnn import COUNTER_SPANS
from eksml_tpu.models.resnet import FrozenBN


def tiny_model(**kw):
    defaults = dict(
        num_classes=5, resnet_blocks=(1, 1, 1, 1), fpn_channels=32,
        pre_nms_topk=64, post_nms_topk=32, frcnn_batch_per_im=16,
        rpn_batch_per_im=32, fc_head_dim=64, mask_head_dim=16,
        test_results_per_im=8, freeze_at=2)
    defaults.update(kw)
    return MaskRCNN(**defaults)


def tiny_batch(b=2, hw=128, g=6, mr0=28):
    rng = np.random.RandomState(0)
    boxes = []
    for _ in range(b):
        xy = rng.rand(g, 2) * hw * 0.5
        wh = rng.rand(g, 2) * hw * 0.3 + 8
        boxes.append(np.concatenate([xy, np.minimum(xy + wh, hw - 1)], 1))
    return {
        "images": jnp.asarray(rng.randn(b, hw, hw, 3), jnp.float32),
        "image_hw": jnp.full((b, 2), hw, jnp.float32),
        "gt_boxes": jnp.asarray(np.stack(boxes), jnp.float32),
        "gt_classes": jnp.asarray(rng.randint(1, 5, (b, g))),
        "gt_valid": jnp.asarray((np.arange(g) < 4)[None].repeat(b, 0)
                                .astype(np.float32)),
        "gt_masks": jnp.asarray(rng.rand(b, g, mr0, mr0) > 0.5,
                                jnp.float32),
    }


def test_backbone_feature_shapes():
    m = ResNetBackbone(num_blocks=(1, 1, 1, 1))
    x = jnp.zeros((1, 64, 64, 3))
    params = m.init(jax.random.PRNGKey(0), x)
    feats = m.apply(params, x)
    assert [f.shape for f in feats] == [
        (1, 16, 16, 256), (1, 8, 8, 512), (1, 4, 4, 1024), (1, 2, 2, 2048)]


def test_fpn_shapes():
    fpn = FPN(num_channels=32)
    feats = [jnp.zeros((1, 16, 16, 256)), jnp.zeros((1, 8, 8, 512)),
             jnp.zeros((1, 4, 4, 1024)), jnp.zeros((1, 2, 2, 2048))]
    params = fpn.init(jax.random.PRNGKey(0), feats)
    outs = fpn.apply(params, feats)
    assert [o.shape for o in outs] == [
        (1, 16, 16, 32), (1, 8, 8, 32), (1, 4, 4, 32), (1, 2, 2, 32),
        (1, 1, 1, 32)]


def test_frozen_bn_is_affine_and_gradient_free():
    bn = FrozenBN()
    x = jnp.ones((1, 4, 4, 3)) * 2.0
    params = bn.init(jax.random.PRNGKey(0), x)
    # with default params (scale=1, bias=0, mean=0, var=1) ≈ identity
    y = bn.apply(params, x)
    np.testing.assert_allclose(np.asarray(y), 2.0, atol=1e-3)
    # gradients w.r.t. bn params must be zero (frozen)
    g = jax.grad(lambda p: bn.apply(p, x).sum())(params)
    for leaf in jax.tree.leaves(g):
        np.testing.assert_allclose(np.asarray(leaf), 0.0)


def test_npz_loader_roundtrip(tmp_path):
    m = ResNetBackbone(num_blocks=(1, 1, 1, 1))
    x = jnp.zeros((1, 64, 64, 3))
    variables = m.init(jax.random.PRNGKey(1), x)
    src_params = jax.tree.map(
        lambda a: np.asarray(a) + np.random.rand(*a.shape).astype(a.dtype),
        variables["params"])
    path = str(tmp_path / "r50.npz")
    n_saved = save_r50_npz(path, src_params)
    assert n_saved > 20

    fresh = m.init(jax.random.PRNGKey(2), x)["params"]
    fresh = jax.tree.map(np.asarray, fresh)
    import flax
    fresh = flax.core.unfreeze(fresh) if hasattr(flax.core, "unfreeze") else fresh
    loaded, n_loaded, n_expected = load_r50_npz(path, fresh)
    assert n_loaded == n_expected, (n_loaded, n_expected)
    # loaded tree equals source tree
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=0),
                 loaded, src_params)


@pytest.mark.slow
def test_train_forward_losses_finite_and_differentiable():
    model = tiny_model()
    batch = tiny_batch()
    rng = jax.random.PRNGKey(0)
    params = model.init(rng, batch, rng)["params"]

    def loss_fn(p):
        losses = model.apply({"params": p}, batch, rng)
        return losses["total_loss"], losses

    (total, losses), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    assert np.isfinite(float(total))
    for k in ("rpn_cls_loss", "rpn_box_loss", "frcnn_cls_loss",
              "frcnn_box_loss", "mrcnn_loss"):
        assert k in losses and np.isfinite(float(losses[k])), k
    # gradients flow to trainable params (e.g. FPN), are finite,
    # and are nonzero somewhere
    leaves = jax.tree.leaves(grads)
    assert all(np.isfinite(np.asarray(l)).all() for l in leaves)
    assert any(np.abs(np.asarray(l)).max() > 0 for l in leaves)


@pytest.mark.slow
def test_predict_shapes_and_validity():
    model = tiny_model(with_masks=True)
    batch = tiny_batch()
    rng = jax.random.PRNGKey(0)
    params = model.init(rng, batch, rng)["params"]
    out = model.apply({"params": params}, batch["images"],
                      batch["image_hw"], method=model.predict)
    d = 8
    assert out["boxes"].shape == (2, d, 4)
    assert out["scores"].shape == (2, d)
    assert out["classes"].shape == (2, d)
    assert out["masks"].shape == (2, d, 28, 28)
    m = np.asarray(out["masks"])
    assert ((m >= 0) & (m <= 1)).all()
    # boxes are clipped to the image
    bx = np.asarray(out["boxes"])
    assert bx.min() >= 0 and bx.max() <= 128


@pytest.mark.slow
def test_remat_bf16_train_grads_compile():
    """TRAIN.REMAT is the HBM-OOM escape hatch (an operating point
    that does not fit reruns with remat on), so the nn.remat-wrapped
    backbone/FPN must actually compile and differentiate — including
    under the bf16 policy threaded through their dtype attrs."""
    m = tiny_model(remat=True, compute_dtype=jnp.bfloat16)
    batch = tiny_batch()
    rng = jax.random.PRNGKey(0)
    params = m.init(rng, batch, rng)["params"]

    def loss_fn(p):
        return m.apply({"params": p}, batch, rng)["total_loss"]

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    assert np.isfinite(float(loss))
    gn = sum(float((np.asarray(g, np.float32) ** 2).sum())
             for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0


@pytest.mark.parametrize("norm", ["FreezeBN", "GN"])
def test_bf16_policy_reaches_backbone_and_fpn(fresh_config, norm):
    """Round-3 perf regression: backbone/FPN convs carried no explicit
    dtype, so flax promoted their bf16 inputs back to the f32 param
    dtype — silently running ~80% of model FLOPs in f32 under the
    bf16 policy (visible as f32 conv temps in the round-3 HBM dump);
    the GN variant additionally pinned every norm output to f32.  The
    trunk features must come out in compute_dtype.  Only the trunk is
    initialized (method=_features) — the full training graph is not
    needed to pin feature dtypes."""
    import jax
    import jax.numpy as jnp
    from eksml_tpu.models import MaskRCNN

    cfg = fresh_config
    cfg.FPN.NUM_CHANNEL = 32
    cfg.BACKBONE.RESNET_NUM_BLOCKS = (1, 1, 1, 1)
    cfg.BACKBONE.NORM = norm
    cfg.TRAIN.PRECISION = "bfloat16"
    cfg.freeze()

    model = MaskRCNN.from_config(cfg)
    images = jnp.zeros((1, 64, 64, 3), jnp.uint8)
    rng = jax.random.PRNGKey(0)
    variables = model.init(rng, images, method=MaskRCNN._features)
    feats = model.apply(variables, images, method=MaskRCNN._features)
    for i, f in enumerate(feats):
        assert f.dtype == jnp.bfloat16, (norm, i, f.dtype)
    # params stay f32 (mixed precision, not a cast-everything policy)
    kernel = variables["params"]["backbone"]["conv0"]["kernel"]
    assert kernel.dtype == jnp.float32


@pytest.mark.slow
def test_gn_and_bf16_variants(fresh_config):
    """The two advertised model variants off the default path: GroupNorm
    backbone (BACKBONE.NORM=GN) and bfloat16 compute (the optimized
    chart's TENSORPACK_FP16 analogue) both produce finite losses."""
    import jax
    import jax.numpy as jnp
    from eksml_tpu.data.loader import make_synthetic_batch
    from eksml_tpu.models import MaskRCNN

    cfg = fresh_config
    cfg.PREPROC.MAX_SIZE = 128
    cfg.PREPROC.TRAIN_SHORT_EDGE_SIZE = (128, 128)
    cfg.DATA.MAX_GT_BOXES = 8
    cfg.RPN.TRAIN_PRE_NMS_TOPK = 64
    cfg.RPN.TRAIN_POST_NMS_TOPK = 32
    cfg.FRCNN.BATCH_PER_IM = 16
    cfg.FPN.NUM_CHANNEL = 32
    cfg.FPN.FRCNN_FC_HEAD_DIM = 64
    cfg.MRCNN.HEAD_DIM = 16
    cfg.BACKBONE.RESNET_NUM_BLOCKS = (1, 1, 1, 1)
    cfg.BACKBONE.NORM = "GN"
    cfg.TRAIN.PRECISION = "bfloat16"
    cfg.freeze()

    model = MaskRCNN.from_config(cfg)
    assert model.compute_dtype == jnp.bfloat16
    batch = make_synthetic_batch(cfg, 1, 128, gt_mask_size=28)
    batch = {k: jnp.asarray(v) for k, v in batch.items()
             if k not in ("image_scale", "image_id")}
    rng = jax.random.PRNGKey(0)
    params = model.init(rng, batch, rng)["params"]
    # GN: GroupNorm params present, no FrozenBN
    stem_keys = set(params["backbone"].keys())
    assert any(k.startswith("GroupNorm") for k in stem_keys), stem_keys
    losses = jax.jit(lambda p, b, r: model.apply({"params": p}, b, r))(
        params, batch, rng)
    assert all(np.isfinite(float(v)) for v in losses.values()), losses
    # losses stay f32 even under bf16 compute
    assert losses["total_loss"].dtype == jnp.float32


def test_mask_targets_identity_and_subregion_resample():
    """Pin the mask-target resampling semantics (VERDICT r3 next #4
    suspect): a ROI equal to its matched GT box must reproduce the
    stored bbox-cropped mask exactly, and a ROI covering one quadrant
    of the GT box must reproduce that quadrant — any half-pixel shift
    or axis swap here silently degrades segm AP while bbox AP stays
    healthy."""
    model = tiny_model(mask_resolution=28)
    mr0 = 28
    rng = np.random.RandomState(3)
    # blocky 7x7 pattern upsampled 4x: piecewise-constant regions make
    # the identity resample exact under bilinear sampling
    coarse = (rng.rand(7, 7) > 0.5).astype(np.float32)
    stored = np.kron(coarse, np.ones((4, 4), np.float32))  # [28,28]
    gt_boxes = jnp.asarray([[10.0, 20.0, 74.0, 116.0]])    # w=64 h=96
    gt_masks = jnp.asarray(stored)[None]                   # [1,28,28]
    matched = jnp.zeros((2,), jnp.int32)
    rois = jnp.asarray([
        [10.0, 20.0, 74.0, 116.0],   # identical to the GT box
        [10.0, 20.0, 42.0, 68.0],    # top-left quadrant
    ])
    out = model.apply({}, rois, matched, gt_boxes, gt_masks,
                      method=MaskRCNN._mask_targets)
    out = np.asarray(out)
    np.testing.assert_array_equal(out[0], stored)
    # quadrant ROI: top-left 14x14 of the stored mask, upsampled 2x
    want = np.kron(stored[:14, :14], np.ones((2, 2)))
    np.testing.assert_array_equal(out[1], (want >= 0.5).astype(
        np.float32))


def _gather_mask_targets(rois, matched_gt, gt_boxes, gt_masks, mr):
    """One image's mask targets before the threshold, by the gather
    formulation, ROI by ROI: the oracle of the matmul path."""
    from eksml_tpu.ops import roi_align

    out = []
    for roi, g in zip(np.asarray(rois), np.asarray(matched_gt)):
        box, mask = np.asarray(gt_boxes[g]), gt_masks[g]
        wh = np.maximum(box[2:] - box[:2], 1e-4)
        frame = np.concatenate([(roi[:2] - box[:2]) / wh,
                                (roi[2:] - box[:2]) / wh]) * mask.shape[-1]
        out.append(roi_align(jnp.asarray(mask, jnp.float32)[:, :, None],
                             jnp.asarray(frame, jnp.float32)[None],
                             1.0, mr)[0, :, :, 0])
    return np.stack(out)


def test_mask_targets_56_to_28_at_the_cell_sizes():
    """The benchmark cells' sizes (``gt_mask_size`` 56 →
    ``mask_resolution`` 28): a ROI equal to its GT box averages 2×2
    stored pixels per bin with its samples on pixel centres, so bin
    means are multiples of 0.25 and ``== 0.5`` is common — the targets
    must be exact there, not close."""
    model = tiny_model(mask_resolution=28)
    rng = np.random.RandomState(5)
    stored = (rng.rand(56, 56) > 0.5).astype(np.float32)
    gt_boxes = jnp.asarray([[10.0, 20.0, 74.0, 116.0]])
    gt_masks = jnp.asarray(stored)[None]
    rois = jnp.asarray([
        [10.0, 20.0, 74.0, 116.0],   # identical to the GT box
        [42.0, 68.0, 74.0, 116.0],   # bottom-right quadrant
        [17.3, 31.9, 66.1, 97.7],    # inside, off the pixel grid
        [-5.0, 40.0, 50.0, 140.0],   # overhangs the GT box: zero padding
    ])
    matched = jnp.zeros((4,), jnp.int32)
    out = np.asarray(model.apply({}, rois, matched, gt_boxes, gt_masks,
                                 method=MaskRCNN._mask_targets))
    blocks = stored.reshape(28, 2, 28, 2).mean(axis=(1, 3))
    assert (blocks == 0.5).any()
    np.testing.assert_array_equal(out[0], (blocks >= 0.5))
    # quadrant: one stored pixel per bin, centre tap 0.75² ≥ 0.5
    np.testing.assert_array_equal(out[1], stored[28:, 28:])
    want = _gather_mask_targets(rois, matched, gt_boxes, gt_masks, 28)
    decided = np.abs(want - 0.5) > 1e-6
    np.testing.assert_array_equal(out[decided], (want >= 0.5)[decided])
    assert out[3][:, :4].sum() == 0 and out[3].sum() > 0


def test_mask_targets_under_vmap_pick_each_rois_own_gt_row():
    """The train forward's call: ``vmap`` over images, ``matched_gt``
    pointing every ROI at its own GT row (box and stored mask)."""
    model = tiny_model(mask_resolution=28)
    rng = np.random.RandomState(7)
    b, g, s = 2, 3, 6
    lo = rng.rand(b, g, 2) * 60
    gt_boxes = np.concatenate([lo, lo + 20 + rng.rand(b, g, 2) * 50],
                              -1).astype(np.float32)
    gt_masks = (rng.rand(b, g, 56, 56) > 0.5).astype(np.float32)
    matched = np.asarray([[2, 0, 1, 1, 2, 0], [1, 1, 0, 2, 0, 2]], np.int32)
    rois = np.take_along_axis(gt_boxes, matched[..., None], 1)
    jitter = rng.randn(b, s, 4).astype(np.float32) * 4
    jitter[:, :g] = 0        # the first three ROIs ARE their GT boxes
    rois = rois + jitter

    out = np.asarray(jax.vmap(
        lambda r, m, gb, gm: model.apply(
            {}, r, m, gb, gm, method=MaskRCNN._mask_targets)
    )(jnp.asarray(rois), jnp.asarray(matched), jnp.asarray(gt_boxes),
      jnp.asarray(gt_masks)))
    assert out.shape == (b, s, 28, 28)
    for i in range(b):
        for j in range(g):
            blocks = gt_masks[i, matched[i, j]].reshape(
                28, 2, 28, 2).mean(axis=(1, 3))
            # (x − x1)/w·56 is exactly 0 and 56 whatever the box, so
            # the samples sit on pixel centres: exact, 0.5s included
            np.testing.assert_array_equal(out[i, j], blocks >= 0.5)
        want = _gather_mask_targets(rois[i], matched[i], gt_boxes[i],
                                    gt_masks[i], 28)
        decided = np.abs(want - 0.5) > 1e-6
        np.testing.assert_array_equal(out[i][decided],
                                      (want >= 0.5)[decided])
    # rows differ: a wrong pick would repeat one GT's mask
    assert not np.array_equal(out[0, 0], out[0, 1])


def _eqns(jaxpr):
    """Every equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.mark.parametrize("under_vmap", [False, True])
def test_mask_targets_is_two_highest_precision_matmuls(under_vmap):
    """Structure, so the one-lane gathers (four fusions, 83 ms of a
    388 ms step on a v5e: PERF.md §6, PR 26) cannot come back unseen:
    ``_mask_targets`` resamples with two ``dot_general``s at
    ``Precision.HIGHEST`` (default precision rounds the weights to
    bfloat16 on a TPU and flips boundary pixels), and its only gathers
    are the ``[matched_gt]`` row picks of boxes and masks."""
    model = tiny_model(mask_resolution=28)

    def targets(r, m, gb, gm):
        return model.apply({}, r, m, gb, gm, method=MaskRCNN._mask_targets)

    args = (jnp.zeros((5, 4)), jnp.zeros((5,), jnp.int32),
            jnp.zeros((3, 4)), jnp.zeros((3, 56, 56)))
    if under_vmap:
        targets = jax.vmap(targets)
        args = tuple(jnp.stack([a, a]) for a in args)
    eqns = list(_eqns(jax.make_jaxpr(targets)(*args).jaxpr))
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 2
    for e in dots:
        assert e.params["precision"] == (jax.lax.Precision.HIGHEST,) * 2
        assert e.params["preferred_element_type"] == jnp.float32
        assert all(v.aval.dtype == jnp.float32 for v in e.invars)
    gathers = [e for e in eqns if e.primitive.name == "gather"]
    picked = sorted(e.invars[0].aval.shape[-2:] for e in gathers)
    assert picked == [(3, 4), (56, 56)], picked   # gt_boxes, gt_masks rows


@pytest.mark.parametrize("kind", ["mask", "frcnn", "cascade"])
def test_no_per_anchor_row_pick_under_rpn_loss(kind):
    """The RPN's box term reads the sampled foreground rows
    (``rpn.rpn_losses``): in the training loss and its gradient no
    ``gather`` under the ``rpn_loss`` scope yields an array with the
    anchor count among its dimensions (the dense ``gt_boxes[matched_gt]``
    over every anchor cannot come back unnoticed), the picks that are
    there yield k rows, and ``rpn_fg_rows`` leaves the step beside the
    losses.  Traced, not compiled."""
    model = tiny_model(with_masks=kind == "mask", cascade=kind == "cascade")
    batch = tiny_batch()
    rng = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda: model.init(rng, batch, rng)["params"])

    def loss_fn(p):
        losses = model.apply({"params": p}, batch, rng)
        return losses["total_loss"], losses

    closed, out = jax.make_jaxpr(
        jax.value_and_grad(loss_fn, has_aux=True), return_shape=True)(params)
    (_, losses), _ = out
    assert losses["rpn_fg_rows"].shape == ()
    assert losses["match_gt_rows"].shape == ()
    assert COUNTER_SPANS["rpn_targets"] == ("rpn_fg_rows", "match_gt_rows")
    n_anchors = sum(3 * (128 // s) ** 2 for s in (4, 8, 16, 32, 64))
    k = int(model.rpn_batch_per_im * model.rpn_fg_ratio)
    scoped = [eqn for eqn in _eqns(closed.jaxpr)
              if "rpn_loss" in str(eqn.source_info.name_stack)]
    shapes = sorted({tuple(v.aval.shape) for eqn in scoped
                     if eqn.primitive.name == "gather"
                     for v in eqn.outvars})
    assert shapes, "the sampled rows are picked under rpn_loss"
    assert not [s for s in shapes if n_anchors in s], shapes
    assert all(k in s for s in shapes), shapes
    # the scope's name is the one the equations carry, so a dense pick
    # would be seen: the objectness term's per-anchor arrays are
    assert any(n_anchors in v.aval.shape
               for eqn in scoped for v in eqn.outvars)
