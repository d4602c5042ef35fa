"""The window-and-experts task (benchmark/tasks/swa_moe.py) on the CPU
at the tiny preset: program and reference agree through
``harness.run_cell`` over three AdamW steps (the losses, Adam's mu and
the parameters' change per leaf), planted faults read not ``correct``,
the file's spec is held against the program's config, and the operation
counts and readers give what ISSUE 35 reckons.  Never a device number.

Tolerances (``swa_moe_smoke.TINY_LIMITS``): float32 on both sides from
equal weights, so the gaps are summation order (seen: 1e-7 on losses,
2e-6 on the worst leaf of the change).
"""

import inspect
import json
import os

import pytest

import bench_smoke
import swa_moe_smoke
from bench_smoke import program_config_put_back  # noqa: F401
from benchmark import harness, swa_moe_flops, tasks
from benchmark.tasks import lm as lm_task, swa_moe as swa_task

INTERFACE = {"spec_mismatches", "build_loader", "first_moment",
             "reference_steps", "extra_numbers", "train_ops_per_row"}
READERS = {"swa_step_mfu_pct", "swa_tokens_per_sec_per_chip",
           "swa_step_ms_p50", "swa_step_ms_p75", "swa_input_wait_ms",
           "swa_batch_build_ms", "swa_h2d_prefetch_ms",
           "swa_device_idle_pct", "swa_device_peak_hbm_gb",
           "swa_moe_load_max_over_mean", "swa_moe_pairs_per_held_expert",
           "swa_moe_ragged_dot_roofline_pct", "swa_splash_fwd_roofline_pct",
           "swa_splash_bwd_roofline_pct", "swa_window_tile_share_pct"}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def run(cell, seed, on_trainer=None):
    import jax

    return harness.run_cell(cell, seed=seed, seconds=0.5, trace=False,
                            t_start=0.0, devices=jax.devices()[:1],
                            peaks=bench_smoke.CPU_PEAK,
                            on_trainer=on_trainer)


@pytest.mark.parametrize("seed", [2147483999, 35])
def test_program_and_reference_agree_through_the_harness(seed):
    import math

    cell = swa_moe_smoke.smoke_cell()
    out = run(cell, seed=seed)              # one seed past 32 signed bits
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"images_per_sec_per_chip", "setup_s"}
    assert set(out["compared"]) == set(cell.workload["limits"]) | {
        "compiles_in_window"}
    for name, row in out["compared"].items():
        assert row["value"] <= row["limit"], name
    assert len(out["window"]["program_loss"]) == 3
    # a fresh model with a small head: ln(vocabulary rows)
    assert out["window"]["reference_loss"][0] == pytest.approx(
        math.log(96), rel=0.02)
    # every leaf takes part (no held bias in this model)
    assert out["window"]["numbers"]["frozen_moved"] == 0.0


def _clone_with(trainer, **changes):
    lm = trainer.cfg.LM.clone()
    lm.freeze(False)
    for key, value in changes.items():      # BLOCK__KEY: a nested key
        *blocks, leaf = key.split("__")
        node = lm
        for block in blocks:
            node = getattr(node, block)
        setattr(node, leaf, value)
    trainer.model = trainer.model.clone(cfg=lm)


@pytest.mark.parametrize("fault", [
    {"SLIDING_WINDOW": 64},                 # the window not applied
    {"ROPE_FULL__ATTENTION_FACTOR": 1.0},   # YaRN's factor left out
    {"ROPE_WINDOW__THETA": 500000},         # one table for both types
    {"ROUTED_SCALING_FACTOR": 1.0},         # gates not scaled
    {"HEADS_PER_LAYER": (4, 6, 2)},         # a full layer's heads cut
], ids=lambda f: next(iter(f)).lower())
def test_a_planted_fault_reads_not_correct(fault):
    out = run(swa_moe_smoke.smoke_cell(), seed=11,
              on_trainer=lambda t: _clone_with(t, **fault))
    assert not out["correct"]
    over = {k for k, row in out["compared"].items()
            if not row["value"] <= row["limit"]}
    # a fault in the forward pass shows in the first loss already,
    # from equal weights
    assert "loss_step1" in over or "first_grad_worst_leaf" in over, over
    assert "compiles_in_window" not in over


def test_the_interface_is_the_six_functions():
    own = {n for n, f in vars(swa_task).items()
           if inspect.isfunction(f) and f.__module__ == swa_task.__name__
           and not n.startswith("_")}
    # the loader's wiring and Adam's first moment are the sequence
    # task's, imported: one TokenLoader, one optimizer
    assert own == INTERFACE - {"build_loader", "first_moment"}
    assert swa_task.build_loader is lm_task.build_loader
    assert swa_task.first_moment is lm_task.first_moment
    for name in INTERFACE:
        assert callable(getattr(swa_task, name))
        assert name in (tasks.__doc__ or "")
    cell = harness.load_cell(bench_smoke.ROOT, swa_moe_smoke.CELL)
    assert cell.task is swa_task and cell.config["task"] == "swa_moe"
    # one loss term: the direction numbers are all the task adds
    assert swa_task.extra_numbers({"terms": [{"ce_loss": 1.0}]},
                                  {"terms": [{"ce_loss": 2.0}]}) == {}


def test_the_file_holds_the_published_config_and_is_held_to_the_program():
    """Every key of the catalog's ``config`` unchanged (in ``model`` and
    at the top level, where the driver's catalog check reads), the cut's
    keys beside them, and ``spec_mismatches`` empty against the
    program's config; one changed key of each kind is named."""
    cell = harness.load_cell(bench_smoke.ROOT, swa_moe_smoke.CELL)
    published = {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
        "intermediate_size": 8192, "num_hidden_layers": 40,
        "num_attention_heads": 48, "num_key_value_heads": 8,
        "head_dim": 128, "max_position_embeddings": 262144,
        "attention_bias": False, "rms_norm_eps": 1e-06, "num_experts": 256,
        "num_experts_per_tok": 8, "moe_intermediate_size": 512,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
        "moe_apply_router_weight_on_input": False,
        "partial_rotary_factor": 0.5, "moe_routed_scaling_factor": 2.5}
    for key, value in published.items():
        assert cell.spec[key] == value, key
        assert cell.config[key] == value, key
    period = ["full_attention"] + ["sliding_attention"] * 3
    assert cell.spec["layer_types"] == period * 10
    assert cell.spec["num_attention_heads_per_layer"] == [48, 64, 64, 64] * 10
    assert cell.spec["mlp_layer_types"] == ["dense"] + ["sparse"] * 39
    ropes = cell.spec["rope_parameters"]
    assert ropes["full_attention"] == {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}
    assert ropes["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Laguna-XS.2")
        assert cell.config["source"].startswith(row["source_url"])
        assert set(row["config"]) == set(published) | {
            "layer_types", "num_attention_heads_per_layer",
            "mlp_layer_types", "rope_parameters"}
        for key, value in row["config"].items():
            assert cell.spec[key] == value and cell.config[key] == value
    assert len(cell.config["source"]) <= 200
    assert "arXiv:2309.00071" in cell.config["source"]
    assert (cell.spec["layers_held"], cell.spec["experts_held"],
            cell.spec["vocab_rows"], cell.spec["seq_len"],
            cell.spec["init_std"], cell.spec["embed_init_std"]) == (
                5, [0, 32], 12544, 8192, 0.02, 1.0)
    assert set(cell.config["reduced"]) == {
        "depth", "experts_held", "vocabulary", "schedule", "weights",
        "data"}
    assert set(cell.config["assumed"]) >= {
        "gating", "router", "rope", "blocks", "recipe", "init", "seq_len",
        "document_mix", "log_period"}
    assert "8 pipeline stages of 5" in cell.config["deployment"]
    assert "32 of 256" in cell.config["deployment"]
    assert cell.hyper["global_batch"] == 2
    # peak 3e-4 at a global batch of 2, in the program's per-8-rows terms
    assert cell.hyper["base_lr"] * 2 / 8 == pytest.approx(3e-4)
    assert cell.workload["traffic"]["seq_len"] == 8192
    assert cell.workload["traffic"]["rows_per_chip"] == 2

    cfg = harness.program_config(cell, 1, "/tmp/none", False)
    assert cfg.MODEL.NAME == "laguna" and cfg.TRAIN.LOG_PERIOD == 5
    assert swa_task.spec_mismatches(cfg, cell.spec, cell.hyper) == []
    # the program's count of what the file's reduced.depth states
    assert "691,623,936" in cell.config["reduced"]["depth"]
    # a width, a hard-wired choice, the window, a rotary number, the
    # routing's scale, the optimizer, a per-layer list, a published
    # count under what runs
    wrong = swa_task.spec_mismatches(
        cfg, dict(
            cell.spec, intermediate_size=4096, gating=False,
            sliding_window=1024, moe_routed_scaling_factor=1.0,
            rope_parameters=dict(ropes, full_attention=dict(
                ropes["full_attention"], beta_fast=32)),
            num_attention_heads_per_layer=[64] * 40,
            num_hidden_layers=4, vocab_size=8192),
        dict(cell.hyper, adam_b2=0.999))
    assert [w.split(":")[0] for w in wrong] == [
        "gating", "sliding_window", "intermediate_size",
        "moe_routed_scaling_factor", "adam_b2",
        "rope_parameters.full_attention", "num_attention_heads_per_layer",
        "layer_types", "mlp_layer_types", "num_hidden_layers",
        "vocab_size"]


def test_required_operations_are_the_issues_arithmetic():
    spec = harness.load_cell(bench_smoke.ROOT, swa_moe_smoke.CELL).spec
    f = swa_moe_flops
    # projections: q and o over the layer's own heads, k and v over 8,
    # a gate's column a head
    assert f.attention_macs_per_token(spec, 0) == 2048 * (
        2 * 48 * 128 + 2 * 8 * 128 + 48) == 29_458_432
    assert f.attention_macs_per_token(spec, 1) == 2048 * (
        2 * 64 * 128 + 2 * 8 * 128 + 64) == 37_879_808
    assert f.held_pairs_per_token(spec) == 1.0
    assert f.mlp_macs_per_token(spec, 0) == 3 * 2048 * 8192
    assert f.mlp_macs_per_token(spec, 1) == (
        2048 * 256 + 3 * 2048 * 512 + 3 * 2048 * 512)
    assert f.expert_layers(spec) == 4
    assert [f.window_of(spec, i) for i in range(5)] == [
        None, 512, 512, 512, None]
    # 275.8 M multiply-adds a token in the products
    assert f.forward_macs_per_token(spec) == 275_841_024
    # a window's area is sum_i min(i + 1, 512), not S x 512
    assert f.visible_scores(8192, 512) == sum(
        min(i + 1, 512) for i in range(8192)) == 4_063_488
    assert f.visible_scores(8192, 512) < 8192 * 512
    assert f.visible_scores(8192, None) == 8192 * 8193 // 2
    assert f.visible_scores(64, 512) == 64 * 65 // 2
    full = f.attention_core_forward_ops(spec, 0, 8192)
    window = f.attention_core_forward_ops(spec, 1, 8192)
    assert full == 2 * (8192 * 8193 // 2) * 48 * 256
    assert window == 2 * 4_063_488 * 64 * 256
    assert full == pytest.approx(0.825e12, rel=1e-3)
    assert window == pytest.approx(0.133e12, rel=2e-3)
    row = f.train_ops_per_row(spec)
    assert row == 3 * (2 * 275_841_024 * 8192 + 2 * full + 3 * window)
    # 1.97e13 a row, 3.94e13 a step of two rows: 200 ms at 197 TFLOP/s
    assert row == pytest.approx(1.97e13, rel=1e-3)
    assert 2 * row / 197e12 == pytest.approx(0.200, rel=2e-3)
    assert swa_task.train_ops_per_row(spec) == row
    # the cores are 31% of the required work
    assert 3 * (2 * full + 3 * window) / row == pytest.approx(0.31, abs=0.01)
    # a grouped core reads K and V once: operations bound both kinds
    assert f.attention_core_forward_bytes(spec, 0, 8192, 2) == (
        8192 * (2 * 48 + 2 * 8) * 128 * 2)
    assert f.attention_core_forward_bytes(spec, 1, 8192, 2) == (
        8192 * (2 * 64 + 2 * 8) * 128 * 2)
    for layer, ops in ((0, full), (1, window)):
        assert f.attention_core_seconds(spec, layer, 8192, 2, PEAK) == (
            pytest.approx(ops / 197e12))
    # one grouped product over 32 held experts at 512 pairs each: the
    # operations (0.174 ms) and the bank's bytes (0.184 ms) nearly meet
    call = f.grouped_product_call(spec, 32 * 512, 2)
    assert call == {"ops": 2 * 16384 * 2048 * 512,
                    "bytes": (32 * 2048 * 512 + 16384 * 2560) * 2}
    assert call["ops"] / 197e12 < call["bytes"] / 819e9


def _ctx(cell, spans=(), trace=None, traced_steps=0, rows_per_s=2.5):
    return harness.TraceContext(
        spec=cell.spec, task=cell.task, chips=1, images_per_step=2,
        images_per_sec_per_chip=rows_per_s, window_s=20.0, window_steps=20,
        traced_steps=traced_steps, feature_itemsize=2, peak=dict(PEAK),
        spans=list(spans), trace=trace)


def test_the_cells_readers():
    from benchmark import trace_reduce

    cell = harness.load_cell(bench_smoke.ROOT, swa_moe_smoke.CELL)
    assert {m["name"] for m in cell.per_layer} == READERS
    with open(os.path.join(bench_smoke.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    # the readers that exist under another cell's name are those readers
    for alias, original in (
            ("swa_step_mfu_pct", "step_mfu_pct"),
            ("swa_step_ms_p50", "step_ms_p50"),
            ("swa_step_ms_p75", "loop_step_ms_p75"),
            ("swa_input_wait_ms", "input_wait_ms"),
            ("swa_batch_build_ms", "batch_build_ms"),
            ("swa_h2d_prefetch_ms", "h2d_prefetch_ms"),
            ("swa_device_idle_pct", "device_idle_pct"),
            ("swa_device_peak_hbm_gb", "lm_device_peak_hbm_gb"),
            ("swa_tokens_per_sec_per_chip", "lm_tokens_per_sec_per_chip"),
            ("swa_moe_load_max_over_mean", "moe_load_max_over_mean")):
        assert (harness._module("metrics", alias).read
                is harness._module("metrics", original).read)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert by_name[alias][key] == by_name[original][key], alias
    # readers of their own under the accepted ones' layer and unit: the
    # accepted ones read keys this config.json names otherwise
    for own, like in (
            ("swa_moe_pairs_per_held_expert", "moe_pairs_per_held_expert"),
            ("swa_moe_ragged_dot_roofline_pct",
             "moe_ragged_dot_roofline_pct"),
            ("swa_splash_fwd_roofline_pct",
             "loop_splash_mha_fwd_roofline_pct"),
            ("swa_splash_bwd_roofline_pct",
             "loop_splash_mha_bwd_roofline_pct")):
        assert (harness._module("metrics", own).read
                is not harness._module("metrics", like).read)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert by_name[own][key] == by_name[like][key], own
    assert "first_k_dense_replace" not in cell.spec
    assert "n_routed_experts" not in cell.spec
    for name in READERS:
        assert by_name[name]["workloads"] == [swa_moe_smoke.CELL]
    assert "mfu" in "swa_step_mfu_pct"
    assert by_name["swa_window_tile_share_pct"]["source"] == (
        "program_counter")
    # nothing to read: no rate, no span, no trace
    assert harness.read_per_layer(cell, _ctx(cell, rows_per_s=0.0)) == {}

    spans = [{"name": "moe_route", "dur": 0.0, "args": {
        "step": s, "moe_pairs_held": p, "moe_load_max_over_mean": m,
        "moe_pairs_dropped": 0.0}}
        for s, p, m in ((5, 65536.0, 1.4), (10, 69632.0, 1.8))]
    spans += [{"name": "attn_window", "dur": 0.0, "args": {
        "step": s, "window_tile_share": 0.25834}} for s in (5, 10)]
    spans += [{"name": "data_wait", "dur": 200.0, "args": {}}] * 3
    spans += [{"name": "batch_build", "dur": 2800.0, "args": {}},
              {"name": "h2d_prefetch", "dur": 800.0, "args": {}}]
    # 18 completions: twelve differences of 850 ms, five of 870 ms
    ends = [1e6 + 850e3 * i for i in range(13)]
    ends += [ends[-1] + 870e3 * i for i in range(1, 6)]
    spans += [{"name": "device_step", "ts": end - 100.0, "dur": 100.0,
               "args": {"step": i}} for i, end in enumerate(ends)]
    # two traced steps: 10 forward sites (5 layers, twice under remat):
    # the two full layers' 9 ms a run, the three window layers' 3 ms;
    # 5 fused backward sites of 22 and 7 ms; 36 grouped products of
    # 0.4 ms (4 layers x (2 forward + 1, recomputed, + 3 + 3))
    ops = {}
    for i, ms in enumerate((9.0, 3.0, 3.0, 3.0, 9.0)):
        ops[f"splash_mqa_fwd_residuals.{i}"] = 2 * ms * 1e-3
        ops[f"splash_mqa_fwd_no_residuals.{i}"] = 2 * ms * 1e-3
        ops[f"splash_mqa_dkv_no_residuals.{i}"] = 2 * ms * 2.4e-3
    ops.update({f"ragged-dot-none.{i}": 2 * 0.4e-3 for i in range(36)})
    ops["fusion.7"] = 1.0
    trace = trace_reduce.TraceSummary(devices=1, steps=2, window_s=2.0,
                                      busy_s=1.99, op_seconds=ops)
    ctx = _ctx(cell, spans, trace, traced_steps=2)
    ctx.memory_stats = [{"peak_bytes_reserved": 4_000_000_000,
                         "peak_bytes_in_use": 9_500_000_000}]
    got = harness.read_per_layer(cell, ctx)
    value = {k: v["value"] for k, v in got.items()}
    assert set(value) == READERS
    row = swa_moe_flops.train_ops_per_row(cell.spec)
    assert value["swa_step_mfu_pct"] == pytest.approx(
        100 * row * 2.5 / 197e12)
    assert value["swa_tokens_per_sec_per_chip"] == 2.5 * 8192
    assert value["swa_step_ms_p50"] == pytest.approx(850.0)
    assert value["swa_step_ms_p75"] == pytest.approx(870.0)
    assert value["swa_input_wait_ms"] == pytest.approx(0.2)
    assert value["swa_batch_build_ms"] == pytest.approx(2.8)
    assert value["swa_h2d_prefetch_ms"] == pytest.approx(0.8)
    assert value["swa_device_idle_pct"] == pytest.approx(0.5)
    assert value["swa_device_peak_hbm_gb"] == pytest.approx(9.5)
    assert value["swa_moe_load_max_over_mean"] == pytest.approx(1.6)
    # 67,584 pairs a step over 4 expert layers x 32 held experts
    assert value["swa_moe_pairs_per_held_expert"] == pytest.approx(528.0)
    assert value["swa_window_tile_share_pct"] == pytest.approx(25.834)
    # required work from the spec: 2 full + 3 window cores x 2 rows a
    # step whatever the number of call sites; the forward ran twice
    cores = (2 * 2 * (8192 * 8193 // 2) * 48 * 256
             + 3 * 2 * 4_063_488 * 64 * 256) / 197e12
    assert value["swa_splash_fwd_roofline_pct"] == pytest.approx(
        100 * cores * 2 / (2 * 27e-3))
    assert value["swa_splash_bwd_roofline_pct"] == pytest.approx(
        100 * 2 * cores * 2 / (27e-3 * 2.4))
    assert value["swa_splash_fwd_roofline_pct"] < 50
    call = swa_moe_flops.grouped_product_call(cell.spec, 67584.0 / 4, 2)
    assert value["swa_moe_ragged_dot_roofline_pct"] == pytest.approx(
        100 * (call["bytes"] / 819e9) * 36 / (36 * 0.4e-3))
    assert all(v <= 100 for k, v in value.items() if k.endswith("_pct"))
    # a program without the spans or the kernels (the parent commit
    # under this cell's files): those fall silent, nothing raises
    bare = harness.read_per_layer(cell, _ctx(
        cell, [], trace_reduce.TraceSummary(
            devices=1, steps=2, window_s=1.0, busy_s=0.9,
            op_seconds={"fusion.7": 1.0}), traced_steps=2))
    assert set(bare) == {"swa_step_mfu_pct", "swa_tokens_per_sec_per_chip",
                         "swa_device_idle_pct"}


def test_the_cells_limits_pass_the_sound_readings_and_fail_the_control():
    """The readings of PERF.md section 4 (chip runs of PR 35: the largest
    over 13 sound seeds, the int8 control and the half batch through
    ``control.py`` on seeds 35201, 35202 and 35203) against the cell's
    file: every sound run passes, the control and the half batch do not.
    Three numbers tell the control from a sound run on every seed, each
    limit between its two readings: ``first_grad_direction_median_leaf``
    (the control 4.7 times the largest sound reading or more),
    ``delta3_median_leaf`` (6.4 times) and ``first_grad_median_leaf``
    (2.9 times).  Where the control stands inside or beside the sound
    range (the three losses, the two worst leaves) the limit stands
    between the sound readings and half a batch's."""
    from benchmark import compare

    limits = dict(harness.load_cell(
        bench_smoke.ROOT, swa_moe_smoke.CELL).workload["limits"])
    sound = {"loss_step1": 3.95e-5, "loss_step2": 3.44e-5,
             "loss_step3": 4.55e-5, "first_grad_worst_leaf": 5.41e-3,
             "first_grad_median_leaf": 2.91e-4,
             "first_grad_direction_median_leaf": 0.0106,
             "delta3_worst_leaf": 2.27e-3, "delta3_median_leaf": 3.70e-5,
             "frozen_moved": 0.0}
    controls = [
        dict(sound, loss_step1=9.65e-5, loss_step2=4.99e-5,
             loss_step3=4.79e-5, first_grad_worst_leaf=0.0360,
             first_grad_median_leaf=1.28e-3,
             first_grad_direction_median_leaf=0.0514,
             delta3_worst_leaf=1.67e-3, delta3_median_leaf=2.56e-4),
        dict(sound, loss_step1=1.46e-5, loss_step2=2.76e-5,
             loss_step3=5.62e-5, first_grad_worst_leaf=0.0136,
             first_grad_median_leaf=1.44e-3,
             first_grad_direction_median_leaf=0.0580,
             delta3_worst_leaf=3.11e-3, delta3_median_leaf=3.06e-4),
        dict(sound, loss_step1=1.37e-4, loss_step2=6.36e-6,
             loss_step3=6.93e-5, first_grad_worst_leaf=8.81e-3,
             first_grad_median_leaf=8.51e-4,
             first_grad_direction_median_leaf=0.0495,
             delta3_worst_leaf=5.69e-3, delta3_median_leaf=2.35e-4)]
    halves = [
        dict(sound, loss_step1=2.69e-4, loss_step2=6.55e-4,
             loss_step3=2.73e-4, first_grad_worst_leaf=0.0859,
             first_grad_median_leaf=0.0117,
             first_grad_direction_median_leaf=0.319,
             delta3_worst_leaf=0.164, delta3_median_leaf=0.0304),
        dict(sound, loss_step1=7.58e-4, loss_step2=3.63e-4,
             loss_step3=7.21e-4, first_grad_worst_leaf=0.0529,
             first_grad_median_leaf=0.0158,
             first_grad_direction_median_leaf=0.284,
             delta3_worst_leaf=0.164, delta3_median_leaf=0.0298),
        dict(sound, loss_step1=9.45e-4, loss_step2=1.67e-4,
             loss_step3=1.46e-3, first_grad_worst_leaf=0.0883,
             first_grad_median_leaf=0.0323,
             first_grad_direction_median_leaf=0.311,
             delta3_worst_leaf=0.159, delta3_median_leaf=0.0318)]
    unchanged = dict(sound, first_grad_worst_leaf=1.0, delta3_worst_leaf=1.0,
                     first_grad_median_leaf=1.0, delta3_median_leaf=1.0)
    assert set(limits) == set(sound)
    assert compare.judge(sound, limits)[0]
    for faulty in controls + halves + [unchanged]:
        assert not compare.judge(faulty, limits)[0]
    # the three numbers that hold the control on every seed: between
    # the two readings, with room on both sides
    held = {"first_grad_direction_median_leaf": 4.5,
            "delta3_median_leaf": 6.0, "first_grad_median_leaf": 2.9}
    for name, apart in held.items():
        least = min(c[name] for c in controls)
        assert least >= apart * sound[name], name
        assert 1.5 * sound[name] < limits[name] < least / 1.5, name
    for control in controls:
        over = {k for k, v in control.items()
                if k in limits and v > limits[k]}
        assert over >= set(held)
    # every other limit: between the largest sound reading and the
    # half batch's least, twice clear of both
    for name in set(limits) - set(held) - {"frozen_moved"}:
        least = min(h[name] for h in halves)
        assert 2 * sound[name] < limits[name] < least / 2, name
    for half in halves:
        over = {k for k, v in half.items() if k in limits and v > limits[k]}
        assert over == set(limits) - {"frozen_moved"}
