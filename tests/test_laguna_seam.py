"""``MODEL.NAME=laguna`` through the seam (eksml_tpu/models/__init__.py):
the fourth name is looked up like the other three, the same entry point
and ``Trainer.fit`` train it, its counters ride the ``moe_route`` and
``attn_window`` spans at log steps, and ``LM_KEYS_OF`` holds each
model's keys to it."""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from eksml_tpu import models
from eksml_tpu.config import (LAGUNA_TINY_OVERRIDES, LM_KEYS_OF,
                              LM_TINY_OVERRIDES, OURO_TINY_OVERRIDES,
                              finalize_configs)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = {"laguna": LAGUNA_TINY_OVERRIDES, "ouro": OURO_TINY_OVERRIDES,
        "joyai_llm_flash": LM_TINY_OVERRIDES}


def test_laguna_is_chosen_by_configuration(fresh_config):
    fresh_config.update_args(list(LAGUNA_TINY_OVERRIDES))
    cfg = finalize_configs(is_training=True)
    from eksml_tpu.models.lm import laguna

    model = models.build_model(cfg)
    assert isinstance(model, laguna.Laguna)
    assert model.remat and model.dtype == jnp.float32
    assert model.cfg.HEADS_PER_LAYER == (4, 6, 4)
    assert models.decay_mask(cfg) is laguna.decay_mask
    assert models.pretrained_loader(cfg) is None
    assert models.counter_spans(cfg) == {
        "moe_route": ("moe_pairs_held", "moe_load_max_over_mean",
                      "moe_pairs_dropped", "moe_rows_moved_share"),
        "attn_window": ("window_tile_share",)}
    assert models.MODEL_NAMES == ("maskrcnn", "joyai_llm_flash", "ouro",
                                  "laguna", "xing4")


@pytest.mark.parametrize("name, stray", [
    ("laguna", "LM.Q_LORA_RANK=64"), ("laguna", "LM.UT_STEPS=2"),
    ("laguna", "LM.NUM_MTP=0"), ("laguna", "LM.NUM_HEADS=8"),
    ("laguna", "LM.ROPE_THETA=10000"),
    ("ouro", "LM.NUM_KV_HEADS=4"), ("ouro", "LM.SLIDING_WINDOW=128"),
    ("ouro", "LM.ROPE_FULL.THETA=10000"),
    ("ouro", "LM.N_ROUTED_EXPERTS=64"),
    ("joyai_llm_flash", "LM.HEADS_PER_LAYER=(4,4,4)"),
    ("joyai_llm_flash", "LM.ROPE_WINDOW.PARTIAL_ROTARY_FACTOR=0.5"),
    ("joyai_llm_flash", "LM.LAYER_TYPES=('full_attention',)")])
def test_lm_keys_of_refuses_another_models_keys(fresh_config, name, stray):
    """A key this model never reads, moved from its default, is an
    error named by the key (a whole rotary block by the block's); the
    keys two models share are refused to neither."""
    fresh_config.update_args(list(TINY[name]) + [stray])
    key = ".".join(stray.split("=")[0].split(".")[:2])
    with pytest.raises(AssertionError, match=key):
        finalize_configs(is_training=True)


def test_shared_keys_are_listed_under_every_model_that_reads_them():
    shared = set(LM_KEYS_OF["laguna"]) & set(LM_KEYS_OF["joyai_llm_flash"])
    assert shared == {"MOE_INTERMEDIATE_SIZE", "FIRST_K_DENSE",
                      "N_ROUTED_EXPERTS", "NUM_EXPERTS_PER_TOK",
                      "N_SHARED_EXPERTS", "ROUTED_SCALING_FACTOR",
                      "EXPERTS_HELD"}
    assert set(LM_KEYS_OF["laguna"]) & set(LM_KEYS_OF["ouro"]) == {
        "HEAD_DIM"}
    assert set(LM_KEYS_OF["ouro"]) & set(LM_KEYS_OF["joyai_llm_flash"]) == {
        "NUM_HEADS", "ROPE_THETA"}


def test_main_trains_laguna_as_it_trains_the_others(tmp_path):
    """``python -m eksml_tpu.train --synthetic --config MODEL.NAME=laguna
    ..``: the same entry point, Trainer.fit and token loader, two log
    steps; log rows with the loss and the routing counters; the
    ``moe_route`` and ``attn_window`` spans at log steps; a checkpoint."""
    logdir = str(tmp_path / "run")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    out = subprocess.run(
        [sys.executable, "-m", "eksml_tpu.train", "--synthetic",
         "--logdir", logdir, "--total-steps", "2", "--config",
         *LAGUNA_TINY_OVERRIDES, "TRAIN.BATCH_SIZE_PER_CHIP=2",
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
        assert r["window_tile_share"] == pytest.approx(0.546875)
        assert "mtp_loss" not in r
    with open(os.path.join(logdir, "trace-host0.json")) as f:
        events = json.load(f)["traceEvents"]
    for span, key in (("moe_route", "moe_pairs_held"),
                      ("attn_window", "window_tile_share")):
        found = [e for e in events if e["name"] == span]
        assert [e["args"]["step"] for e in found] == [1, 2]
        assert [e["args"][key] for e in found] == [r[key] for r in logged]
    assert os.path.isdir(os.path.join(logdir, "checkpoints", "2"))
