"""The looped-stack task (benchmark/tasks/looplm.py) on the CPU at the
tiny preset: program and reference agree through ``harness.run_cell``
over three AdamW steps (the losses, every compared term, Adam's mu and
the parameters' change per leaf), planted faults read not ``correct``
and by name, the file's spec is held against the program's config, and
the operation counts and readers give what ISSUE 33 reckons.  Never a
device number.

Tolerances (``looplm_smoke.TINY_LIMITS``): float32 on both sides from
equal weights, so the gaps are summation order (seen: 4e-7 on losses,
2e-5 on the worst leaf of the change).
"""

import inspect
import json
import math
import os

import pytest

import bench_smoke
import looplm_smoke
from bench_smoke import program_config_put_back  # noqa: F401
from benchmark import harness, looplm_flops, tasks
from benchmark.tasks import lm as lm_task, looplm as looplm_task

INTERFACE = {"spec_mismatches", "build_loader", "first_moment",
             "reference_steps", "extra_numbers", "train_ops_per_row"}
READERS = {"loop_step_mfu_pct", "loop_tokens_per_sec_per_chip",
           "loop_step_ms_p50", "loop_step_ms_p75", "loop_input_wait_ms",
           "loop_batch_build_ms", "loop_h2d_prefetch_ms",
           "loop_device_idle_pct", "loop_device_peak_hbm_gb",
           "loop_expected_exit_pass", "loop_splash_mha_fwd_roofline_pct",
           "loop_splash_mha_bwd_roofline_pct"}


def run(cell, seed, on_trainer=None):
    import jax

    return harness.run_cell(cell, seed=seed, seconds=0.5, trace=False,
                            t_start=0.0, devices=jax.devices()[:1],
                            peaks=bench_smoke.CPU_PEAK,
                            on_trainer=on_trainer)


@pytest.mark.parametrize("passes, seed", [(3, 2147483999), (4, 33)])
def test_program_and_reference_agree_through_the_harness(passes, seed):
    cell = looplm_smoke.smoke_cell(passes)
    out = run(cell, seed=seed)              # one seed past 32 signed bits
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"images_per_sec_per_chip", "setup_s"}
    assert set(out["compared"]) == set(cell.workload["limits"]) | {
        "compiles_in_window"}
    assert f"ce_pass{passes}_loss_step1" in out["compared"]
    for name, row in out["compared"].items():
        assert row["value"] <= row["limit"], name
    assert len(out["window"]["program_loss"]) == 3
    # a fresh model: ln(vocabulary rows) less beta x the entropy of the
    # fresh gate's (1/2, 1/4, ..) distribution
    h = {3: 1.5, 4: 1.75}[passes] * math.log(2.0)
    assert out["window"]["reference_loss"][0] == pytest.approx(
        math.log(96) - 0.1 * h, rel=0.02)
    # every leaf takes part: the tied stack, the gate and its bias too
    assert out["window"]["numbers"]["frozen_moved"] == 0.0


def _clone_with(trainer, **changes):
    lm = trainer.cfg.LM.clone()
    lm.freeze(False)
    for key, value in changes.items():
        setattr(lm, key, value)
    trainer.model = trainer.model.clone(cfg=lm)


def _a_pass_left_out(trainer):
    _clone_with(trainer, UT_STEPS=2)


def _entropy_mis_weighted(trainer):
    _clone_with(trainer, EXIT_ENTROPY_WEIGHT=0.05)


def _head_on_the_unnormed_state(monkeypatch):
    """The pass's state goes on to the next pass normed, but head and
    gate read it without the final norm's scale."""
    import jax.numpy as jnp

    from eksml_tpu.models.lm import ouro

    real = ouro.Pass.__call__

    def skipping(self, h):
        carry, (state, z) = real(self, h)
        return carry, (state * jnp.asarray(1.25, state.dtype), z)

    monkeypatch.setattr(ouro.Pass, "__call__", skipping)


@pytest.mark.parametrize("fault", ["a_pass_left_out", "entropy_mis_weighted",
                                   "head_on_another_state"])
def test_a_planted_fault_reads_not_correct_and_by_name(fault, monkeypatch):
    on_trainer = {"a_pass_left_out": _a_pass_left_out,
                  "entropy_mis_weighted": _entropy_mis_weighted}.get(fault)
    if on_trainer is None:
        _head_on_the_unnormed_state(monkeypatch)
    out = run(looplm_smoke.smoke_cell(), seed=11, on_trainer=on_trainer)
    assert not out["correct"]
    over = {k for k, row in out["compared"].items()
            if not row["value"] <= row["limit"]}
    if fault == "a_pass_left_out":
        # two passes where the file says three: the last pass's term is
        # missing, the first is sound
        assert "ce_pass3_loss_step1" in over
        assert "ce_pass1_loss_step1" not in over
        assert {"expected_ce_loss_step1", "exit_entropy_loss_step1"} <= over
    elif fault == "entropy_mis_weighted":
        assert "exit_entropy_loss_step1" in over and "loss_step1" in over
        assert not over & {"ce_pass1_loss_step1", "ce_pass3_loss_step1",
                           "expected_ce_loss_step1"}
    else:
        assert {"ce_pass1_loss_step1", "ce_pass3_loss_step1"} <= over
        assert "exit_entropy_loss_step1" not in over   # the gate saw h


def test_the_interface_is_the_six_functions():
    own = {n for n, f in vars(looplm_task).items()
           if inspect.isfunction(f)
           and f.__module__ == looplm_task.__name__}
    # the loader's wiring and Adam's first moment are the sequence
    # task's, imported: one TokenLoader, one optimizer
    assert own == INTERFACE - {"build_loader", "first_moment"}
    assert looplm_task.build_loader is lm_task.build_loader
    assert looplm_task.first_moment is lm_task.first_moment
    for name in INTERFACE:
        assert callable(getattr(looplm_task, name))
        assert name in (tasks.__doc__ or "")
    cell = harness.load_cell(bench_smoke.ROOT, looplm_smoke.CELL)
    assert cell.task is looplm_task and cell.config["task"] == "looplm"


def test_the_file_holds_the_published_config_and_is_held_to_the_program():
    """Every key of the catalog's ``config`` unchanged (in ``model`` and
    at the top level, where the driver's catalog check reads), the cut's
    keys beside them, and ``spec_mismatches`` empty against the
    program's config; one changed key of each kind is named."""
    cell = harness.load_cell(bench_smoke.ROOT, looplm_smoke.CELL)
    published = {
        "model_type": "ouro", "hidden_size": 2048,
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "head_dim": 128, "intermediate_size": 5632,
        "num_hidden_layers": 48, "total_ut_steps": 4,
        "early_exit_threshold": 1, "vocab_size": 49152,
        "rope_theta": 1000000, "rms_norm_eps": 1e-06,
        "max_position_embeddings": 65536, "max_window_layers": 48,
        "hidden_act": "silu", "rope_scaling": None, "sliding_window": None,
        "use_sliding_window": False, "tie_word_embeddings": False,
        "layer_types": ["full_attention"] * 48}
    for key, value in published.items():
        assert cell.spec[key] == value, key
        assert cell.config[key] == value, key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Ouro-2.6B")
        assert cell.config["source"].startswith(row["source_url"])
        assert set(row["config"]) == set(published)
        for key, value in row["config"].items():
            assert cell.spec[key] == value and cell.config[key] == value
    assert "arXiv:2510.25741" in cell.config["source"]
    assert (cell.spec["layers_held"], cell.spec["vocab_rows"],
            cell.spec["seq_len"], cell.spec["exit_entropy_weight"],
            cell.spec["init_std"]) == (6, 49152, 4096, 0.1, 0.02)
    assert set(cell.config["reduced"]) == {"depth", "schedule", "weights",
                                           "data"}
    assert set(cell.config["assumed"]) >= {
        "norms", "rope", "bias", "exit_entropy_weight", "recipe",
        "seq_len", "init", "early_exit_threshold", "window", "log_period"}
    assert "8 pipeline stages of 6" in cell.config["deployment"]
    assert cell.hyper["global_batch"] == 2
    # peak 3e-4 at a global batch of 2, in the program's per-8-rows terms
    assert cell.hyper["base_lr"] * 2 / 8 == pytest.approx(3e-4)

    cfg = harness.program_config(cell, 1, "/tmp/none", False)
    assert cfg.MODEL.NAME == "ouro" and cfg.TRAIN.LOG_PERIOD == 5
    assert looplm_task.spec_mismatches(cfg, cell.spec, cell.hyper) == []
    # a width, a hard-wired choice, the mechanism's two numbers, the
    # optimizer, a published count under what runs, the vocabulary, the
    # layer pattern
    wrong = looplm_task.spec_mismatches(
        cfg, dict(cell.spec, intermediate_size=4096, hidden_act="gelu",
                  total_ut_steps=2, exit_entropy_weight=0.05,
                  num_hidden_layers=4, vocab_size=32000,
                  layer_types=["full_attention"] * 3
                  + ["sliding_attention"]),
        dict(cell.hyper, adam_b2=0.999))
    assert [w.split(":")[0] for w in wrong] == [
        "hidden_act", "intermediate_size", "total_ut_steps",
        "exit_entropy_weight", "adam_b2", "num_hidden_layers",
        "vocab_size", "layer_types"]


def test_required_operations_are_the_issues_arithmetic():
    spec = harness.load_cell(bench_smoke.ROOT, looplm_smoke.CELL).spec
    assert looplm_flops.block_macs_per_token(spec) == 51_380_224
    assert looplm_flops.forward_macs_per_token(spec) == 4 * (
        6 * 51_380_224 + 2048 * 49_152 + 2048)
    assert looplm_flops.attention_cores(spec) == 24
    core = looplm_flops.attention_core_forward_ops(spec, 4096)
    assert core == 2 * (4096 * 4096 / 2) * 16 * 256
    row = looplm_flops.train_ops_per_row(spec)
    assert row == 3 * (2 * looplm_flops.forward_macs_per_token(spec) * 4096
                       + 24 * core)
    # 3 x (1.3400e13 + 1.6493e12) = 4.515e13 a row, 9.03e13 a step of
    # two rows: 0.458 s at 197 TFLOP/s
    assert 2 * looplm_flops.forward_macs_per_token(spec) * 4096 == (
        pytest.approx(1.3400e13, rel=1e-4))
    assert 24 * core == pytest.approx(1.6493e12, rel=1e-4)
    assert row == pytest.approx(4.515e13, rel=1e-4)
    assert 2 * row / 197e12 == pytest.approx(0.458, rel=2e-3)
    assert looplm_task.train_ops_per_row(spec) == row
    # passes 2-4 with their heads, losses and gates: three quarters
    one = looplm_flops.train_ops_per_row(dict(spec, total_ut_steps=1))
    assert (row - one) / row == pytest.approx(0.75)
    # one core over one row: operations bound it (0.349 ms against
    # 0.041 ms of bytes in bfloat16)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert looplm_flops.attention_core_forward_bytes(spec, 4096, 2) == (
        4096 * 16 * 4 * 128 * 2)
    assert looplm_flops.attention_core_seconds(spec, 4096, 2, peak) == (
        pytest.approx(core / 197e12))
    assert core / 197e12 > 4096 * 16 * 4 * 128 * 2 / 819e9


def _ctx(cell, spans=(), trace=None, traced_steps=0, rows_per_s=2.0):
    return harness.TraceContext(
        spec=cell.spec, task=cell.task, chips=1, images_per_step=2,
        images_per_sec_per_chip=rows_per_s, window_s=20.0, window_steps=20,
        traced_steps=traced_steps, feature_itemsize=2,
        peak={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        spans=list(spans), trace=trace)


def test_the_cells_readers():
    from benchmark import trace_reduce

    cell = harness.load_cell(bench_smoke.ROOT, looplm_smoke.CELL)
    assert {m["name"] for m in cell.per_layer} >= READERS
    with open(os.path.join(bench_smoke.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    # the readers that exist under another cell's name are those readers
    for alias, original in (
            ("loop_step_mfu_pct", "step_mfu_pct"),
            ("loop_step_ms_p50", "step_ms_p50"),
            ("loop_input_wait_ms", "input_wait_ms"),
            ("loop_batch_build_ms", "batch_build_ms"),
            ("loop_h2d_prefetch_ms", "h2d_prefetch_ms"),
            ("loop_device_idle_pct", "device_idle_pct"),
            ("loop_device_peak_hbm_gb", "lm_device_peak_hbm_gb"),
            ("loop_tokens_per_sec_per_chip", "lm_tokens_per_sec_per_chip")):
        assert (harness._module("metrics", alias).read
                is harness._module("metrics", original).read)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert by_name[alias][key] == by_name[original][key], alias
    # the 75th percentile is its own reader: the general one wants 40
    # differences and this cell's window holds about 26
    from benchmark.metrics import loop_step_ms_p75, step_ms_p75
    assert loop_step_ms_p75.read is not step_ms_p75.read
    assert loop_step_ms_p75.MIN_DIFFERENCES < 26 < step_ms_p75.MIN_DIFFERENCES
    for key in ("unit", "better", "source", "layer", "moves"):
        assert by_name["loop_step_ms_p75"][key] == by_name[
            "step_ms_p75"][key]
    for name in READERS:
        assert by_name[name]["workloads"] == [looplm_smoke.CELL]
    assert "mfu" in "loop_step_mfu_pct"
    # nothing to read: no rate, no span, no trace
    assert harness.read_per_layer(cell, _ctx(cell, rows_per_s=0.0)) == {}

    spans = [{"name": "loop_exit", "dur": 0.0, "args": {
        "step": s, "loop_exit_p1": p1, "loop_exit_p2": 0.25,
        "loop_exit_p3": 0.125, "loop_exit_p4": 0.625 - p1,
        "loop_exit_entropy": 1.2, "loop_ce_pass1": 10.8}}
        for s, p1 in ((10, 0.5), (15, 0.4))]
    spans += [{"name": "data_wait", "dur": 200.0, "args": {}}] * 3
    spans += [{"name": "batch_build", "dur": 1400.0, "args": {}},
              {"name": "h2d_prefetch", "dur": 800.0, "args": {}}]
    # 18 completions: twelve differences of 990 ms, five of 1010 ms
    ends = [1e6 + 990e3 * i for i in range(13)]
    ends += [ends[-1] + 1010e3 * i for i in range(1, 6)]
    spans += [{"name": "device_step", "ts": end - 100.0, "dur": 100.0,
               "args": {"step": i}} for i, end in enumerate(ends)]
    # two traced steps.  Under the scan one instruction runs in every
    # pass: 12 forward sites (6 blocks, twice under remat) hold 4 passes
    # each, 1.5 ms a run; 6 fused backward sites, 3.2 ms a run
    ops = {f"splash_mha_fwd_residuals.{i}": 2 * 4 * 1.5e-3
           for i in range(6)}
    ops.update({f"splash_mha_fwd.{i}": 2 * 4 * 1.5e-3 for i in range(6)})
    ops.update({f"splash_mha_dkv_no_residuals.{i}": 2 * 4 * 3.2e-3
                for i in range(6)})
    ops["fusion.7"] = 1.0
    trace = trace_reduce.TraceSummary(devices=1, steps=2, window_s=2.0,
                                      busy_s=1.99, op_seconds=ops)
    ctx = _ctx(cell, spans, trace, traced_steps=2)
    ctx.memory_stats = [{"peak_bytes_reserved": 4_000_000_000,
                         "peak_bytes_in_use": 9_500_000_000}]
    got = harness.read_per_layer(cell, ctx)
    value = {k: v["value"] for k, v in got.items() if k in READERS}
    assert set(value) == READERS
    row = looplm_flops.train_ops_per_row(cell.spec)
    assert value["loop_step_mfu_pct"] == pytest.approx(
        100 * row * 2.0 / 197e12)
    assert value["loop_tokens_per_sec_per_chip"] == 2.0 * 4096
    assert value["loop_step_ms_p50"] == pytest.approx(990.0)
    assert value["loop_step_ms_p75"] == pytest.approx(1010.0)
    assert value["loop_input_wait_ms"] == pytest.approx(0.2)
    assert value["loop_batch_build_ms"] == pytest.approx(1.4)
    assert value["loop_h2d_prefetch_ms"] == pytest.approx(0.8)
    assert value["loop_device_idle_pct"] == pytest.approx(0.5)
    assert value["loop_device_peak_hbm_gb"] == pytest.approx(9.5)
    # sum t p_t: 0.5 + 0.5 + 0.375 + 0.5 = 1.875 and 0.4 + .. + 0.9
    assert value["loop_expected_exit_pass"] == pytest.approx(
        (1.875 + 2.175) / 2)
    # required work from the spec: 24 cores x 2 rows a step whatever the
    # number of call sites; the forward ran twice, so at most 50%
    core_s = 2 * (4096 * 4096 / 2) * 16 * 256 / 197e12
    assert value["loop_splash_mha_fwd_roofline_pct"] == pytest.approx(
        100 * 24 * 2 * core_s / (48 * 1.5e-3))
    assert value["loop_splash_mha_bwd_roofline_pct"] == pytest.approx(
        100 * 2 * 24 * 2 * core_s / (24 * 3.2e-3))
    assert value["loop_splash_mha_fwd_roofline_pct"] < 50
    assert all(v <= 100 for k, v in value.items() if k.endswith("_pct"))
    # a program without the span or the kernels: those fall silent
    bare = harness.read_per_layer(cell, _ctx(
        cell, [], trace_reduce.TraceSummary(
            devices=1, steps=2, window_s=1.0, busy_s=0.9,
            op_seconds={"fusion.7": 1.0}), traced_steps=2))
    assert set(bare) & READERS == {
        "loop_step_mfu_pct", "loop_tokens_per_sec_per_chip",
        "loop_device_idle_pct"}


def test_extra_numbers_are_the_four_terms_at_step_one():
    ref = {"terms": [{"ce_pass1_loss": 10.0, "ce_pass2_loss": 10.0,
                      "ce_pass3_loss": 10.0, "ce_pass4_loss": 8.0,
                      "expected_ce_loss": 9.0, "exit_entropy_loss": -0.12,
                      "total_loss": 8.88}]}
    prog = {"terms": [dict(ref["terms"][0], ce_pass4_loss=8.08,
                           exit_entropy_loss=-0.06)]}
    got = looplm_task.extra_numbers(prog, ref)
    assert got == {"ce_pass1_loss_step1": 0.0,
                   "ce_pass4_loss_step1": pytest.approx(0.01),
                   "expected_ce_loss_step1": 0.0,
                   "exit_entropy_loss_step1": pytest.approx(0.5)}
    fewer = {"terms": [{k: v for k, v in ref["terms"][0].items()
                        if k != "ce_pass4_loss"}]}
    assert looplm_task.extra_numbers(fewer, ref)[
        "ce_pass4_loss_step1"] == math.inf
    assert looplm_task.extra_numbers({"terms": []}, ref) == {}


def test_the_cells_limits_pass_the_sound_readings_and_fail_the_control():
    """The readings of PERF.md section 4 (chip runs of PR 33: the largest
    over 23 sound seeds, the int8 control and the half batch on seeds
    33201, 33202 and 33203, faults planted in the float32 reference at
    the cell's size on four seeds) against the cell's file: every sound
    run passes, the control and the half batch do not.  Four numbers
    tell the control from a sound run on every seed, each limit between
    its two readings with room on both sides:
    ``first_grad_direction_median_leaf``, ``first_grad_median_leaf``,
    ``delta3_worst_leaf`` (the control three times the largest sound
    reading or more) and ``loss_step3`` (2.3 times).  ``loss_step1`` and
    ``ce_pass1_loss_step1`` (the control 1.4 to 2.6 times the sound
    reading on some seed, half a batch inside the sound range on one)
    are reported, not compared."""
    from benchmark import compare

    limits = dict(harness.load_cell(
        bench_smoke.ROOT, looplm_smoke.CELL).workload["limits"])
    sound = {"loss_step1": 1.07e-4, "loss_step2": 1.67e-4,
             "loss_step3": 1.37e-4, "ce_pass1_loss_step1": 9.88e-5,
             "ce_pass4_loss_step1": 3.30e-4,
             "expected_ce_loss_step1": 1.06e-4,
             "exit_entropy_loss_step1": 3.60e-3,
             "first_grad_worst_leaf": 0.0293,
             "first_grad_median_leaf": 4.33e-4,
             "first_grad_direction_median_leaf": 0.0230,
             "delta3_worst_leaf": 7.95e-4, "delta3_median_leaf": 2.75e-4,
             "frozen_moved": 0.0}
    controls = [
        dict(sound, loss_step1=1.47e-4, loss_step2=1.30e-4,
             loss_step3=4.34e-4, ce_pass1_loss_step1=2.44e-4,
             ce_pass4_loss_step1=3.23e-4,
             expected_ce_loss_step1=1.11e-4, exit_entropy_loss_step1=3.39e-3,
             first_grad_worst_leaf=0.0541, first_grad_median_leaf=1.40e-3,
             first_grad_direction_median_leaf=0.1136,
             delta3_worst_leaf=3.60e-3, delta3_median_leaf=7.96e-4),
        dict(sound, loss_step1=1.87e-4, loss_step2=1.82e-4,
             loss_step3=4.47e-4, ce_pass1_loss_step1=4.14e-4,
             ce_pass4_loss_step1=5.13e-4,
             expected_ce_loss_step1=1.05e-4, exit_entropy_loss_step1=7.12e-3,
             first_grad_worst_leaf=0.0465, first_grad_median_leaf=1.54e-3,
             first_grad_direction_median_leaf=0.1245,
             delta3_worst_leaf=2.89e-3, delta3_median_leaf=7.57e-4),
        dict(sound, loss_step1=2.15e-4, loss_step2=1.90e-4,
             loss_step3=3.10e-4, ce_pass1_loss_step1=2.56e-4,
             ce_pass4_loss_step1=1.45e-3,
             expected_ce_loss_step1=2.96e-4, exit_entropy_loss_step1=7.68e-3,
             first_grad_worst_leaf=0.322, first_grad_median_leaf=1.92e-3,
             first_grad_direction_median_leaf=0.1091,
             delta3_worst_leaf=2.96e-3, delta3_median_leaf=7.92e-4)]
    halves = [
        dict(sound, loss_step1=1.37e-4, loss_step2=3.20e-3,
             loss_step3=2.87e-5, ce_pass1_loss_step1=1.66e-4,
             ce_pass4_loss_step1=4.83e-3,
             expected_ce_loss_step1=1.50e-3, exit_entropy_loss_step1=0.133,
             first_grad_worst_leaf=0.176, first_grad_median_leaf=7.44e-3,
             first_grad_direction_median_leaf=0.549,
             delta3_worst_leaf=0.239, delta3_median_leaf=0.0204),
        dict(sound, loss_step1=3.31e-3, loss_step2=4.50e-3,
             loss_step3=7.21e-3, ce_pass1_loss_step1=2.57e-3,
             ce_pass4_loss_step1=1.48e-3,
             expected_ce_loss_step1=3.28e-3, exit_entropy_loss_step1=3.9e-4,
             first_grad_worst_leaf=0.491, first_grad_median_leaf=0.0109,
             first_grad_direction_median_leaf=0.557,
             delta3_worst_leaf=0.236, delta3_median_leaf=5.39e-3),
        dict(sound, loss_step1=8.68e-5, loss_step2=1.25e-3,
             loss_step3=2.54e-4, ce_pass1_loss_step1=8.22e-4,
             ce_pass4_loss_step1=3.56e-3,
             expected_ce_loss_step1=5.28e-4, exit_entropy_loss_step1=0.0564,
             first_grad_worst_leaf=0.128, first_grad_median_leaf=0.0189,
             first_grad_direction_median_leaf=0.524,
             delta3_worst_leaf=0.236, delta3_median_leaf=0.0324)]
    unchanged = dict(sound, first_grad_worst_leaf=1.0, delta3_worst_leaf=1.0,
                     first_grad_median_leaf=1.0, delta3_median_leaf=1.0)
    assert compare.judge(sound, limits)[0]
    for faulty in controls + halves + [unchanged]:
        assert not compare.judge(faulty, limits)[0]
    # the four numbers that hold the control: between the two readings
    held = {"first_grad_direction_median_leaf": 3.0,
            "first_grad_median_leaf": 3.0, "delta3_worst_leaf": 3.0,
            "loss_step3": 2.2}
    for name, apart in held.items():
        least = min(c[name] for c in controls)
        assert least >= apart * sound[name], name
        room = 1.4 if apart < 3 else 1.7
        assert room * sound[name] < limits[name] < least / room, name
    for control in controls:
        over = {k for k, v in control.items()
                if k in limits and v > limits[k]}
        assert over >= set(held)
    assert all({k for k, v in c.items() if k in limits and v > limits[k]}
               == set(held) for c in controls[:2])
    for half in halves:
        over = {k for k, v in half.items() if k in limits and v > limits[k]}
        assert over >= {"loss_step2", "ce_pass4_loss_step1",
                        "expected_ce_loss_step1", "first_grad_worst_leaf",
                        "first_grad_median_leaf", "delta3_worst_leaf",
                        "delta3_median_leaf",
                        "first_grad_direction_median_leaf"}
    # the other limits stand at least twice over the largest sound
    # reading
    for name, value in limits.items():
        assert (value == 0.0 or name in held
                or value >= 2 * sound[name]), name
    # the terms' limits against the faults they exist for, planted in the
    # reference at the cell's size (the least of four seeds): the head on
    # the un-normed state, the last gate entering the distribution, a
    # pass left out, beta halved
    assert 3 * limits["ce_pass4_loss_step1"] < 0.567
    assert 2 * limits["exit_entropy_loss_step1"] < 0.0285 < 0.0957 < 0.5
    assert 100 * limits["expected_ce_loss_step1"] < 0.0407
    assert not {"loss_step1", "ce_pass1_loss_step1"} & set(limits)
