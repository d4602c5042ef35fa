"""``MODEL.NAME=xing4`` through the seam (eksml_tpu/models/__init__.py):
the fifth name is looked up like the others, the same entry point and
``Trainer.fit`` train it, its counters ride the ``moe_route`` and
``mhc`` spans at log steps, and ``LM_KEYS_OF`` holds each model's keys
to it."""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from eksml_tpu import models
from eksml_tpu.config import (LAGUNA_TINY_OVERRIDES, LM_KEYS_OF,
                              LM_TINY_OVERRIDES, OURO_TINY_OVERRIDES,
                              XING_TINY_OVERRIDES, finalize_configs)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = {"xing4": XING_TINY_OVERRIDES, "laguna": LAGUNA_TINY_OVERRIDES,
        "ouro": OURO_TINY_OVERRIDES, "joyai_llm_flash": LM_TINY_OVERRIDES}


def test_xing_is_chosen_by_configuration(fresh_config):
    fresh_config.update_args(list(XING_TINY_OVERRIDES))
    cfg = finalize_configs(is_training=True)
    from eksml_tpu.models.lm import xing

    model = models.build_model(cfg)
    assert isinstance(model, xing.Xing)
    assert model.remat and model.dtype == jnp.float32
    assert model.cfg.HC_MULT == 4 and model.cfg.NUM_MTP == 0
    assert models.decay_mask(cfg) is xing.decay_mask
    assert models.pretrained_loader(cfg) is None
    assert models.counter_spans(cfg) == {
        "moe_route": ("moe_pairs_held", "moe_load_max_over_mean",
                      "moe_pairs_dropped", "moe_rows_moved_share"),
        "mhc": ("mhc_sinkhorn_err", "mhc_res_diag")}
    assert "xing4" in models.MODEL_NAMES


@pytest.mark.parametrize("name, stray", [
    ("xing4", "LM.HEAD_DIM=64"), ("xing4", "LM.UT_STEPS=2"),
    ("xing4", "LM.SLIDING_WINDOW=128"), ("xing4", "LM.MTP_LOSS_WEIGHT=0.5"),
    ("xing4", "LM.ROPE_FULL.THETA=10000"),
    ("joyai_llm_flash", "LM.HC_MULT=2"),
    ("joyai_llm_flash", "LM.ROPE_SCALING.FACTOR=8"),
    ("laguna", "LM.HC_SINKHORN_ITERS=5"), ("ouro", "LM.HC_EPS=1e-5")])
def test_lm_keys_of_refuses_another_models_keys(fresh_config, name, stray):
    """A key this model never reads, moved from its default, is an
    error named by the key (a whole rotary block by the block's)."""
    fresh_config.update_args(list(TINY[name]) + [stray])
    key = ".".join(stray.split("=")[0].split(".")[:2])
    with pytest.raises(AssertionError, match=key):
        finalize_configs(is_training=True)


def test_xings_keys_are_joyais_attention_and_experts_and_its_own():
    """The hyper-connections' keys and YaRN's block are Xing's alone;
    MLA's and the expert layer's it shares with JoyAI."""
    own = set(LM_KEYS_OF["xing4"]) - set().union(
        *(LM_KEYS_OF[m] for m in LM_KEYS_OF if m != "xing4"))
    assert own == {"HC_MULT", "HC_SINKHORN_ITERS", "HC_EPS", "HC_RES_CLAMP",
                   "ROPE_SCALING"}
    assert set(LM_KEYS_OF["xing4"]) - own == set(
        LM_KEYS_OF["joyai_llm_flash"]) - {"MTP_LOSS_WEIGHT"}


def test_main_trains_xing_as_it_trains_the_others(tmp_path):
    """``python -m eksml_tpu.train --synthetic --config MODEL.NAME=xing4
    ..``: the same entry point, Trainer.fit and token loader, two log
    steps; log rows with the loss, the routing and the streams'
    counters; the ``moe_route`` and ``mhc`` spans at log steps; a
    checkpoint."""
    logdir = str(tmp_path / "run")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    out = subprocess.run(
        [sys.executable, "-m", "eksml_tpu.train", "--synthetic",
         "--logdir", logdir, "--total-steps", "2", "--config",
         *XING_TINY_OVERRIDES, "TRAIN.BATCH_SIZE_PER_CHIP=2",
         "TRAIN.LOG_PERIOD=1", "TRAIN.WEIGHT_DECAY=0.1",
         "TRAIN.GRADIENT_CLIP=1.0", "TPU.MESH_SHAPE=(1,1)",
         "TRAIN.STEPS_PER_EPOCH=2", "TRAIN.MAX_EPOCHS=1",
         "TELEMETRY.TRACING.ENABLED=True", "TELEMETRY.PORT=0"],
        env=env, capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "training complete at 2 steps" in out.stderr
    with open(os.path.join(logdir, "metrics.jsonl")) as f:
        rows = [json.loads(line) for line in f]
    logged = [r for r in rows if "total_loss" in r]
    assert [r["step"] for r in logged] == [1, 2]
    for r in logged:
        assert r["total_loss"] == r["ce_loss"]
        assert 4.0 < r["total_loss"] < 5.2         # ln 96 = 4.56
        assert 0 < r["moe_pairs_held"] < 2 * 2 * 64 * 2
        assert r["moe_pairs_dropped"] == 0.0
        assert 0.0 < r["mhc_sinkhorn_err"] < 1e-5
        assert 0.25 < r["mhc_res_diag"] < 0.4
        assert "mtp_loss" not in r
    with open(os.path.join(logdir, "trace-host0.json")) as f:
        events = json.load(f)["traceEvents"]
    for span, key in (("moe_route", "moe_pairs_held"),
                      ("mhc", "mhc_sinkhorn_err"), ("mhc", "mhc_res_diag")):
        found = [e for e in events if e["name"] == span]
        assert [e["args"]["step"] for e in found] == [1, 2]
        assert [e["args"][key] for e in found] == [r[key] for r in logged]
    assert os.path.isdir(os.path.join(logdir, "checkpoints", "2"))
