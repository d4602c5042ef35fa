"""The seam ``MODEL.NAME`` (eksml_tpu/models/__init__.py): the detector
is built and optimised exactly as before it existed, the sequence
model is reached through the same ``Trainer`` and ``main``, a detector
run imports nothing of it, and an unknown name is an error."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from eksml_tpu import models
from eksml_tpu.config import (LM_TINY_OVERRIDES, SMOKE_OVERRIDES,
                              finalize_configs)
from eksml_tpu.models.mask_rcnn import decay_mask as detector_decay_mask
from eksml_tpu.train import make_optimizer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_the_default_is_the_detector_built_as_before(fresh_config):
    cfg = finalize_configs(is_training=True)
    assert cfg.MODEL.NAME == "maskrcnn" and cfg.TRAIN.OPTIMIZER == "sgd"
    assert models.build_model(cfg) == models.MaskRCNN.from_config(cfg)
    assert models.counter_spans(cfg) == {
        "roi_bwd_strips": ("roi_bwd_tile_share", "roi_fwd_tile_share"),
        "rpn_targets": ("rpn_fg_rows", "match_gt_rows")}
    assert models.pretrained_loader(cfg) is None
    fresh_config.freeze(False)
    fresh_config.BACKBONE.WEIGHTS = "/no/such/file.npz"
    loader = models.pretrained_loader(fresh_config)
    assert loader.keywords == {"path": "/no/such/file.npz"}


def test_sgd_chain_is_the_one_it_was(fresh_config):
    """``make_optimizer`` under the default ``TRAIN.OPTIMIZER`` gives
    the updates of the chain train.py spelled out before the seam:
    clip, decay added to the gradient (masked), momentum SGD."""
    fresh_config.update_args(["TRAIN.GRADIENT_CLIP=0.36"])
    cfg = finalize_configs(is_training=True)
    tx, sched = make_optimizer(cfg)
    old = optax.chain(
        optax.clip_by_global_norm(cfg.TRAIN.GRADIENT_CLIP),
        optax.add_decayed_weights(
            cfg.TRAIN.WEIGHT_DECAY,
            mask=detector_decay_mask(cfg.BACKBONE.FREEZE_AT)),
        optax.sgd(sched, momentum=cfg.TRAIN.MOMENTUM))
    rng = np.random.RandomState(0)
    params = {"backbone": {"conv0": {"kernel": jnp.asarray(
        rng.normal(size=(3, 3)), jnp.float32)}},
        "fpn": {"lateral_2": {"kernel": jnp.asarray(
            rng.normal(size=(3, 3)), jnp.float32),
            "bias": jnp.ones((3,))}}}
    grads = jax.tree.map(lambda p: 0.5 * p + 1.0, params)
    new, ref = tx.init(params), old.init(params)
    for _ in range(3):
        got, new = tx.update(grads, new, params)
        want, ref = old.update(grads, ref, params)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(a, b)


def test_adamw_chain(fresh_config):
    """clip -> Adam moments -> decoupled decay (matrices only) ->
    schedule; the held router bias and the norm scales do not decay."""
    fresh_config.update_args(list(LM_TINY_OVERRIDES) + [
        "TRAIN.WEIGHT_DECAY=0.1", "TRAIN.GRADIENT_CLIP=1.0",
        "TRAIN.BASE_LR=0.008", "TRAIN.WARMUP_STEPS=0"])
    cfg = finalize_configs(is_training=True)
    tx, sched = make_optimizer(cfg)
    lr = float(sched(0))
    params = {"a": {"kernel": jnp.full((2, 2), 2.0)},
              "n": {"scale": jnp.ones((2,))},
              "b": {"bias": jnp.ones((2,))}}
    zero = jax.tree.map(jnp.zeros_like, params)
    updates, state = tx.update(zero, tx.init(params), params)
    # zero gradient: Adam's part is 0 / (0 + eps); what is left is the
    # decay, on the matrix alone
    np.testing.assert_allclose(updates["a"]["kernel"], -lr * 0.1 * 2.0,
                               rtol=1e-6)
    assert float(jnp.max(jnp.abs(updates["n"]["scale"]))) == 0.0
    assert float(jnp.max(jnp.abs(updates["b"]["bias"]))) == 0.0
    (adam,) = [s for s in state if isinstance(s, optax.ScaleByAdamState)]
    assert float(jnp.max(jnp.abs(adam.mu["a"]["kernel"]))) == 0.0
    # a gradient of norm 4 is clipped to 1, and the first Adam step is
    # lr x sign
    g = jax.tree.map(jnp.zeros_like, params)
    g["a"]["kernel"] = jnp.full((2, 2), 2.0)
    updates, state = tx.update(g, tx.init(params), params)
    (adam,) = [s for s in state if isinstance(s, optax.ScaleByAdamState)]
    np.testing.assert_allclose(adam.mu["a"]["kernel"], 0.1 * 0.5, rtol=1e-6)
    np.testing.assert_allclose(updates["a"]["kernel"],
                               -lr * (1.0 + 0.1 * 2.0), rtol=1e-4)


def test_an_unknown_model_or_optimizer_is_an_error(fresh_config):
    fresh_config.MODEL.NAME = "resnet"
    for fn in (models.build_model, models.decay_mask,
               models.pretrained_loader, models.counter_spans):
        with pytest.raises(ValueError, match="MODEL.NAME='resnet'"):
            fn(fresh_config)
    fresh_config.MODEL.NAME = "maskrcnn"
    fresh_config.TRAIN.OPTIMIZER = "lion"
    with pytest.raises(AssertionError):
        finalize_configs(is_training=True)


def test_the_sequence_model_is_chosen_by_configuration(fresh_config):
    fresh_config.update_args(list(LM_TINY_OVERRIDES))
    cfg = finalize_configs(is_training=True)
    from eksml_tpu.models import lm

    model = models.build_model(cfg)
    assert isinstance(model, lm.JoyAIFlash)
    assert model.remat and model.dtype == jnp.float32
    assert models.decay_mask(cfg) is lm.decay_mask
    assert models.pretrained_loader(cfg) is None
    assert models.counter_spans(cfg) == {"moe_route": (
        "moe_pairs_held", "moe_load_max_over_mean", "moe_pairs_dropped",
        "moe_rows_moved_share")}


def _python(code, *args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    return subprocess.run([sys.executable, "-c", code, *args], env=env,
                          capture_output=True, text=True, timeout=timeout,
                          cwd=ROOT)


def test_a_detector_run_imports_nothing_of_the_sequence_model():
    """Trainer construction and the loader seam under the default
    MODEL.NAME: no ``models.lm`` and no ``data.tokens`` module is
    imported (the detectors' setup_s is judged at 10%)."""
    out = _python(
        "import sys, tempfile\n"
        "from eksml_tpu.config import config as cfg, finalize_configs, "
        "SMOKE_OVERRIDES\n"
        "cfg.update_args(list(SMOKE_OVERRIDES) + ['DATA.SYNTHETIC=True', "
        "'TPU.MESH_SHAPE=(1,1)', 'TELEMETRY.PORT=0'])\n"
        "cfg = finalize_configs(True)\n"
        "from eksml_tpu.train import Trainer\n"
        "from eksml_tpu.data import build_train_loader\n"
        "t = Trainer(cfg, tempfile.mkdtemp())\n"
        "build_train_loader(cfg, 1)\n"
        "t.ckpt.close()\n"
        "print(sorted(m for m in sys.modules if 'models.lm' in m "
        "or 'data.tokens' in m or 'splash' in m))\n")
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"


def test_main_trains_the_sequence_model_as_it_trains_the_detector(tmp_path):
    """``python -m eksml_tpu.train --synthetic --config
    MODEL.NAME=joyai_llm_flash ..``: the same entry point, Trainer.fit,
    token loader, log rows with both loss terms and the routing
    counters, the ``moe_route`` span at log steps, a checkpoint."""
    logdir = str(tmp_path / "run")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    out = subprocess.run(
        [sys.executable, "-m", "eksml_tpu.train", "--synthetic",
         "--logdir", logdir, "--total-steps", "4", "--config",
         *LM_TINY_OVERRIDES, "TRAIN.BATCH_SIZE_PER_CHIP=2",
         "TRAIN.LOG_PERIOD=2", "TRAIN.WEIGHT_DECAY=0.1",
         "TRAIN.GRADIENT_CLIP=1.0", "TPU.MESH_SHAPE=(1,1)",
         "TRAIN.STEPS_PER_EPOCH=4", "TRAIN.MAX_EPOCHS=1",
         "TELEMETRY.TRACING.ENABLED=True", "TELEMETRY.PORT=0"],
        env=env, capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "training complete at 4 steps" in out.stderr
    with open(os.path.join(logdir, "metrics.jsonl")) as f:
        rows = [json.loads(line) for line in f]
    logged = [r for r in rows if "total_loss" in r]
    assert [r["step"] for r in logged] == [2, 4]
    for r in logged:
        assert r["total_loss"] == pytest.approx(
            r["ce_loss"] + 0.3 * r["mtp_loss"], rel=1e-5)
        assert r["moe_pairs_dropped"] == 0.0 and r["moe_pairs_held"] > 0
    assert logged[-1]["total_loss"] < logged[0]["total_loss"] + 0.5
    with open(os.path.join(logdir, "trace-host0.json")) as f:
        events = json.load(f)["traceEvents"]
    routes = [e for e in events if e["name"] == "moe_route"]
    assert [e["args"]["step"] for e in routes] == [2, 4]
    assert routes[0]["args"]["moe_pairs_held"] == logged[0]["moe_pairs_held"]
    assert os.path.isdir(os.path.join(logdir, "checkpoints", "4"))


def test_the_detectors_step_carries_its_counter_to_a_span(tmp_path):
    """The detector's side of the counter seam: the step's output holds
    ``roi_bwd_tile_share`` and ``roi_fwd_tile_share`` (shares of the
    64 x 64 tile, outside the summed loss), the log rows carry them,
    and at log steps they ride a zero-length ``roi_bwd_strips`` span,
    as ``moe_route`` does.  Likewise ``rpn_fg_rows`` (the filled slots
    of the RPN box term's ``int(BATCH_PER_IM * FG_RATIO)`` an image) and
    ``match_gt_rows`` (the ground-truth rows the anchor matching
    visited an image: its valid ones, of ``DATA.MAX_GT_BOXES``) on an
    ``rpn_targets`` span."""
    logdir = str(tmp_path / "run")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    out = subprocess.run(
        [sys.executable, "-m", "eksml_tpu.train", "--synthetic",
         "--logdir", logdir, "--total-steps", "4", "--config",
         *SMOKE_OVERRIDES, "TRAIN.BATCH_SIZE_PER_CHIP=1",
         "TRAIN.LOG_PERIOD=2", "TPU.MESH_SHAPE=(1,1)",
         "TRAIN.STEPS_PER_EPOCH=4", "TRAIN.MAX_EPOCHS=1",
         "TRAIN.EVAL_PERIOD=0", "TELEMETRY.TRACING.ENABLED=True",
         "TELEMETRY.PORT=0"],
        env=env, capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    with open(os.path.join(logdir, "metrics.jsonl")) as f:
        rows = [json.loads(line) for line in f]
    logged = [r for r in rows if "total_loss" in r]
    assert [r["step"] for r in logged] == [2, 4]
    for r in logged:
        # 16 x 16 strips: a sixteenth of the tile at the least
        assert 1 / 16 <= r["roi_bwd_tile_share"] <= 1.0
        # the forward's W origin is no finer than the backward's (equal
        # in float32, as here): it never covers less
        assert r["roi_bwd_tile_share"] <= r["roi_fwd_tile_share"] <= 1.0
        # RPN.BATCH_PER_IM 256 x RPN.FG_RATIO 0.5 slots; the synthetic
        # records have boxes, so some are filled
        assert 0 < r["rpn_fg_rows"] <= 128
        # the synthetic records have boxes, padded to 8 rows an image
        assert 0 < r["match_gt_rows"] <= 8
        assert r["total_loss"] == pytest.approx(sum(
            v for k, v in r.items()
            if k.endswith("_loss") and k != "total_loss"), rel=1e-5)
    with open(os.path.join(logdir, "trace-host0.json")) as f:
        events = json.load(f)["traceEvents"]
    strips = [e for e in events if e["name"] == "roi_bwd_strips"]
    assert [e["args"]["step"] for e in strips] == [2, 4]
    for key in ("roi_bwd_tile_share", "roi_fwd_tile_share"):
        assert [e["args"][key] for e in strips] == [
            r[key] for r in logged]
    targets = [e for e in events if e["name"] == "rpn_targets"]
    for key in ("rpn_fg_rows", "match_gt_rows"):
        assert [(e["args"]["step"], e["args"][key]) for e in targets] == [
            (r["step"], r[key]) for r in logged]
