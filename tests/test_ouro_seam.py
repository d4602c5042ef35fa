"""``MODEL.NAME=ouro`` through the seam (eksml_tpu/models/__init__.py):
the third name is looked up like the other two, the same entry point
and ``Trainer.fit`` train it, and its counters ride the ``loop_exit``
span at log steps."""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from eksml_tpu import models
from eksml_tpu.config import OURO_TINY_OVERRIDES, finalize_configs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_the_looped_model_is_chosen_by_configuration(fresh_config):
    fresh_config.update_args(list(OURO_TINY_OVERRIDES))
    cfg = finalize_configs(is_training=True)
    from eksml_tpu.models.lm import ouro

    model = models.build_model(cfg)
    assert isinstance(model, ouro.Ouro)
    assert model.remat and model.dtype == jnp.float32
    assert model.cfg.UT_STEPS == 3 and model.cfg.HEAD_DIM == 16
    assert models.decay_mask(cfg) is ouro.decay_mask
    assert models.pretrained_loader(cfg) is None
    assert models.counter_spans(cfg) == {"loop_exit": (
        "loop_exit_p1", "loop_exit_p2", "loop_exit_p3",
        "loop_exit_entropy", "loop_ce_pass1", "loop_ce_pass2",
        "loop_ce_pass3")}


def test_the_seam_knows_its_names_and_says_so(fresh_config):
    assert models.MODEL_NAMES[:3] == ("maskrcnn", "joyai_llm_flash", "ouro")
    fresh_config.MODEL.NAME = "looplm"
    with pytest.raises(ValueError) as e:
        models.build_model(fresh_config)
    for name in models.MODEL_NAMES:
        assert name in str(e.value)
    fresh_config.MODEL.NAME = "maskrcnn"


def test_main_trains_the_looped_model_as_it_trains_the_others(tmp_path):
    """``python -m eksml_tpu.train --synthetic --config MODEL.NAME=ouro
    ..``: the same entry point, Trainer.fit and token loader; log rows
    with every loss term, the exit distribution and the per-pass
    cross-entropies; the ``loop_exit`` span at log steps; a checkpoint."""
    logdir = str(tmp_path / "run")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    out = subprocess.run(
        [sys.executable, "-m", "eksml_tpu.train", "--synthetic",
         "--logdir", logdir, "--total-steps", "4", "--config",
         *OURO_TINY_OVERRIDES, "TRAIN.BATCH_SIZE_PER_CHIP=2",
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
            r["expected_ce_loss"] + r["exit_entropy_loss"], rel=1e-5)
        p = [r[f"loop_exit_p{t}"] for t in (1, 2, 3)]
        assert sum(p) == pytest.approx(1.0, abs=1e-5)
        # a gate a few steps old: about (1/2, 1/4, 1/4)
        assert p == pytest.approx([0.5, 0.25, 0.25], abs=0.05)
        assert r["exit_entropy_loss"] == pytest.approx(
            -0.1 * r["loop_exit_entropy"], rel=1e-5)
        assert min(p) * r["ce_pass1_loss"] < r["expected_ce_loss"]
        for t in (1, 2, 3):
            assert r[f"loop_ce_pass{t}"] == r[f"ce_pass{t}_loss"]
        assert "ce_pass4_loss" not in r
    with open(os.path.join(logdir, "trace-host0.json")) as f:
        events = json.load(f)["traceEvents"]
    exits = [e for e in events if e["name"] == "loop_exit"]
    assert [e["args"]["step"] for e in exits] == [2, 4]
    for key in ("loop_exit_p1", "loop_exit_p3", "loop_exit_entropy",
                "loop_ce_pass2"):
        assert [e["args"][key] for e in exits] == [r[key] for r in logged]
    assert os.path.isdir(os.path.join(logdir, "checkpoints", "4"))
