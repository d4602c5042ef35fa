"""The sequence task (benchmark/tasks/lm.py) on the CPU at the tiny
preset: program and reference agree through ``harness.run_cell`` over
three AdamW steps (losses, both terms, Adam's mu and the parameters'
change per leaf), planted faults read not ``correct``, the file's spec
is held against the program's config, and the operation counts and
readers give what ISSUE 28 reckons.  Never a device number.

Tolerances (``lm_smoke.TINY_LIMITS``): float32 on both sides from equal
weights, so the gaps are summation order (seen: 1e-7 on losses, 3e-7 /
2e-6 on the worst leaf of mu / of the change).
"""

import inspect
import json
import math
import os

import pytest

import bench_smoke
import lm_smoke
from bench_smoke import program_config_put_back  # noqa: F401
from benchmark import harness, lm_flops, tasks
from benchmark.tasks import lm as lm_task

INTERFACE = {"spec_mismatches", "build_loader", "first_moment",
             "reference_steps", "extra_numbers", "train_ops_per_row"}


def run(cell, seed, on_trainer=None):
    import jax

    return harness.run_cell(cell, seed=seed, seconds=0.5, trace=False,
                            t_start=0.0, devices=jax.devices()[:1],
                            peaks=bench_smoke.CPU_PEAK,
                            on_trainer=on_trainer)


def test_program_and_reference_agree_through_the_harness():
    cell = lm_smoke.smoke_cell()
    out = run(cell, seed=2147483999)        # past 32 signed bits
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"images_per_sec_per_chip", "setup_s"}
    assert set(out["compared"]) == set(cell.workload["limits"]) | {
        "compiles_in_window"}
    for name, row in out["compared"].items():
        assert row["value"] <= row["limit"], name
    assert len(out["window"]["program_loss"]) == 3
    # a fresh model's loss is ln(vocabulary rows) x (1 + lambda)
    assert out["window"]["reference_loss"][0] == pytest.approx(
        1.3 * math.log(96), rel=0.02)


def _without_mtp_loss(trainer):
    lm = trainer.cfg.LM.clone()
    lm.freeze(False)
    lm.MTP_LOSS_WEIGHT = 0.0
    trainer.model = trainer.model.clone(cfg=lm)


def _skipping_a_held_expert(monkeypatch):
    from eksml_tpu.models.lm import moe

    real = moe.held_experts

    def skipping(h, ids, gates, w_gate, w_up, w_down, first):
        return real(h, ids, gates, w_gate, w_up,
                    w_down.at[0].set(0.0), first)

    monkeypatch.setattr(moe, "held_experts", skipping)


@pytest.mark.parametrize("fault", ["mtp_loss_left_out",
                                   "held_expert_skipped"])
def test_a_planted_fault_reads_not_correct(fault, monkeypatch):
    on_trainer = None
    if fault == "mtp_loss_left_out":
        on_trainer = _without_mtp_loss
    else:
        _skipping_a_held_expert(monkeypatch)
    out = run(lm_smoke.smoke_cell(), seed=11, on_trainer=on_trainer)
    assert not out["correct"]
    over = {k for k, row in out["compared"].items()
            if not row["value"] <= row["limit"]}
    if fault == "mtp_loss_left_out":
        # the term itself is computed and reported; its weight is gone
        assert "loss_step1" in over and "mtp_loss_step1" not in over
        assert "first_grad_worst_leaf" in over
    else:
        assert over & {"first_grad_worst_leaf", "delta3_worst_leaf",
                       "loss_step1"}


def test_the_interface_is_the_six_functions():
    own = {n for n, f in vars(lm_task).items()
           if inspect.isfunction(f) and f.__module__ == lm_task.__name__}
    assert own == INTERFACE
    for name in INTERFACE:
        assert name in (tasks.__doc__ or "")
    cell = harness.load_cell(bench_smoke.ROOT, lm_smoke.CELL)
    assert cell.task is lm_task and cell.config["task"] == "lm"


def test_the_file_holds_the_published_config_and_is_held_to_the_program():
    """Every key of the catalog's ``config`` unchanged (in ``model`` and
    at the top level, where the driver's catalog check reads), the three
    cut keys beside them, and ``spec_mismatches`` empty against the
    program's config; a width changed on either side is named."""
    cell = harness.load_cell(bench_smoke.ROOT, lm_smoke.CELL)
    published = {
        "hidden_size": 2048, "num_attention_heads": 32,
        "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "rope_theta": 32000000,
        "intermediate_size": 7168, "moe_intermediate_size": 768,
        "n_routed_experts": 256, "num_experts_per_tok": 8,
        "n_shared_experts": 1, "routed_scaling_factor": 2.5,
        "first_k_dense_replace": 1, "num_hidden_layers": 40,
        "num_nextn_predict_layers": 1, "vocab_size": 129280,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
        "rope_interleave": True, "rope_scaling": None,
        "tie_word_embeddings": False, "rms_norm_eps": 1e-06}
    for key, value in published.items():
        assert cell.spec[key] == value, key
        assert cell.config[key] == value, key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "JoyAI-LLM-Flash")
        assert cell.config["source"].startswith(row["source_url"])
        for key, value in row["config"].items():
            assert cell.spec[key] == value and cell.config[key] == value
    assert (cell.spec["layers_held"], cell.spec["experts_held"],
            cell.spec["vocab_rows"]) == (5, [0, 16], 16160)
    assert set(cell.config["reduced"]) == {
        "depth", "experts_held", "vocabulary", "schedule", "weights",
        "data", "router_bias_update"}
    assert cell.hyper["global_batch"] == 2

    cfg = harness.program_config(cell, 1, "/tmp/none", False)
    assert lm_task.spec_mismatches(cfg, cell.spec, cell.hyper) == []
    wrong = lm_task.spec_mismatches(
        cfg, dict(cell.spec, moe_intermediate_size=512, vocab_size=1000,
                  scoring_func="softmax"),
        dict(cell.hyper, adam_b2=0.999))
    assert [w.split(":")[0] for w in wrong] == [
        "scoring_func", "moe_intermediate_size", "adam_b2", "vocab_size"]


def test_required_operations_are_the_issues_arithmetic():
    spec = harness.load_cell(bench_smoke.ROOT, lm_smoke.CELL).spec
    assert lm_flops.attention_macs_per_token(spec) == 26_345_472
    assert lm_flops.held_pairs_per_token(spec) == 0.5
    # 70.4 M + 5 x 33.95 M + 8.4 M + 2 x 33.1 M multiply-adds a token
    assert lm_flops.forward_macs_per_token(spec) == pytest.approx(
        314.7e6, rel=1e-3)
    core = lm_flops.attention_core_forward_ops(spec, 4096)
    assert core == 4096 * 4096 * 32 * 320
    # 1.89 GFLOP a token in the products + six cores: 2.17e13 a step
    row = lm_flops.train_ops_per_row(spec)
    assert row == 3 * (2 * lm_flops.forward_macs_per_token(spec) * 4096
                       + 6 * core)
    assert 2 * row == pytest.approx(2.17e13, rel=5e-3)
    assert lm_task.train_ops_per_row(spec) == row
    call = lm_flops.grouped_product_call(spec, 4096, 2)
    assert call["ops"] == 2 * 4096 * 2048 * 768
    assert call["bytes"] == (16 * 2048 * 768 + 4096 * 2816) * 2


def _ctx(cell, spans=(), trace=None, traced_steps=0, rows_per_s=5.0):
    return harness.TraceContext(
        spec=cell.spec, task=cell.task, chips=1, images_per_step=2,
        images_per_sec_per_chip=rows_per_s, window_s=20.0, window_steps=50,
        traced_steps=traced_steps, feature_itemsize=2,
        peak={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        spans=list(spans), trace=trace)


def test_the_cells_readers():
    from benchmark import trace_reduce

    cell = harness.load_cell(bench_smoke.ROOT, lm_smoke.CELL)
    names = {m["name"] for m in cell.per_layer}
    general = {"lm_input_wait_ms", "lm_batch_build_ms", "lm_h2d_prefetch_ms",
               "lm_step_ms_p50", "lm_step_ms_p75", "lm_device_idle_pct"}
    # PR 28's fourteen; what later PRs append for this cell reads its
    # own example (test_examples.py), not this hand-made context
    fourteen = general | {
        "lm_step_mfu_pct", "lm_tokens_per_sec_per_chip",
        "lm_device_peak_hbm_gb",
        "moe_load_max_over_mean", "moe_pairs_per_held_expert",
        "splash_mha_fwd_roofline_pct", "splash_mha_bwd_roofline_pct",
        "moe_ragged_dot_roofline_pct"}
    assert names >= fourteen
    # the general readers' aliases are the readers themselves, under
    # the layer names they carry in the detector cells
    with open(os.path.join(bench_smoke.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for alias in general:
        original = by_name[alias[len("lm_"):]]
        assert (harness._module("metrics", alias).read
                is harness._module("metrics", original["name"]).read)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert by_name[alias][key] == original[key], (alias, key)
    # nothing to read: no rate, no span, no trace
    assert harness.read_per_layer(cell, _ctx(cell, rows_per_s=0.0)) == {}

    spans = [{"name": "moe_route", "dur": 1.0, "args": {
        "step": s, "moe_pairs_held": pairs, "moe_pairs_dropped": 0.0,
        "moe_load_max_over_mean": load}}
        for s, pairs, load in ((20, 20480.0, 1.2), (40, 20992.0, 1.4))]
    spans += [{"name": "data_wait", "dur": 5000.0, "args": {}}] * 3
    spans.append({"name": "batch_build", "dur": 1500.0,
                  "args": {"seq": 0, "rows": 2}})
    spans.append({"name": "h2d_prefetch", "dur": 700.0, "args": {"seq": 0}})
    spans += [{"name": "device_step", "ts": 1e6 + 450e3 * i, "dur": 450e3,
               "args": {"step": i}} for i in range(42)]
    # two traced steps: 12 forward call sites (6 cores, twice under
    # remat) of 4 ms a step, 6 fused backward kernels of 8 ms; 45
    # grouped products of 1 ms a step
    ops = {f"splash_mha_fwd_residuals.{i}": 8e-3 for i in range(12)}
    ops.update({f"splash_mha_dkv_no_residuals.{i}": 16e-3 for i in range(6)})
    ops.update({f"ragged-dot-none.{i}": 2e-3 for i in range(45)})
    ops["ragged-dot-metadata.1"] = 1.0          # not the product
    ops["fusion.7"] = 1.0
    trace = trace_reduce.TraceSummary(devices=1, steps=2, window_s=1.0,
                                      busy_s=0.9, op_seconds=ops)
    ctx = _ctx(cell, spans, trace, traced_steps=2)
    # the runtime's two counters as the chip gave them in this cell
    ctx.memory_stats = [{"peak_bytes_reserved": 3_217_801_216,
                         "peak_bytes_in_use": 8_468_550_144}]
    got = harness.read_per_layer(cell, ctx)
    value = {k: v["value"] for k, v in got.items() if k in fourteen}
    assert set(value) == fourteen
    assert value["lm_input_wait_ms"] == pytest.approx(5.0)
    assert value["lm_batch_build_ms"] == pytest.approx(1.5)
    assert value["lm_h2d_prefetch_ms"] == pytest.approx(0.7)
    assert value["lm_step_ms_p50"] == pytest.approx(450.0)
    assert value["lm_step_ms_p75"] == pytest.approx(450.0)
    assert value["lm_device_idle_pct"] == pytest.approx(10.0)
    assert value["lm_device_peak_hbm_gb"] == pytest.approx(8.468550144)
    row = lm_flops.train_ops_per_row(cell.spec)
    assert value["lm_step_mfu_pct"] == pytest.approx(
        100 * row * 5.0 / 197e12)
    assert value["lm_tokens_per_sec_per_chip"] == 5.0 * 4096
    assert value["moe_load_max_over_mean"] == pytest.approx(1.3)
    assert value["moe_pairs_per_held_expert"] == pytest.approx(
        20736.0 / (5 * 16))
    # one core over 2 rows: operations bound it, 3.44e11 / 197e12 s
    core_s = 2 * 4096 * 4096 * 32 * 320 / 197e12
    assert value["splash_mha_fwd_roofline_pct"] == pytest.approx(
        100 * core_s / 4e-3)
    assert value["splash_mha_bwd_roofline_pct"] == pytest.approx(
        100 * 2 * core_s / 8e-3)
    # a grouped product of 4147 pairs: the bank's bytes bound it
    pairs = 20736.0 / 5
    need = (16 * 2048 * 768 + pairs * 2816) * 2 / 819e9
    assert need > 2 * pairs * 2048 * 768 / 197e12
    assert value["moe_ragged_dot_roofline_pct"] == pytest.approx(
        100 * need / 1e-3)
    assert all(v <= 100 for k, v in value.items() if k.endswith("_pct"))
    # a program without the counters or the kernels: those fall silent
    bare = harness.read_per_layer(cell, _ctx(
        cell, [], trace_reduce.TraceSummary(
            devices=1, steps=2, window_s=1.0, busy_s=0.9,
            op_seconds={"fusion.7": 1.0}), traced_steps=2))
    assert set(bare) & fourteen == {
        "lm_step_mfu_pct", "lm_tokens_per_sec_per_chip", "lm_device_idle_pct"}


def test_extra_numbers_are_the_two_terms_at_step_one():
    ref = {"terms": [{"ce_loss": 9.0, "mtp_loss": 10.0}]}
    prog = {"terms": [{"ce_loss": 9.09, "mtp_loss": 10.0}]}
    got = lm_task.extra_numbers(prog, ref)
    assert got == {"ce_loss_step1": pytest.approx(0.01),
                   "mtp_loss_step1": 0.0}
    missing = lm_task.extra_numbers({"terms": [{"ce_loss": 9.0}]}, ref)
    assert missing["mtp_loss_step1"] == math.inf
    assert lm_task.extra_numbers({"terms": []}, ref) == {}


def test_the_direction_number_sees_what_the_norms_do_not():
    """Noise of random sign, 3% of a leaf's norm, moves the norm by
    5e-4 and the direction number by about 3e-2; a leaf without
    projections on either side leaves the number out (and a limit on it
    then fails the run)."""
    import numpy as np

    from benchmark import compare, lm_direction

    rng = np.random.RandomState(0)
    mu = {"a/kernel": rng.normal(size=(64, 48)).astype(np.float32),
          "b/kernel": rng.normal(size=(4, 32, 16)).astype(np.float32),
          "c/bias": np.zeros((8,), np.float32)}
    noisy = {k: v + 0.03 * rng.normal(size=v.shape).astype(np.float32)
             for k, v in mu.items()}

    def side(tree):
        norms = {k: float(np.linalg.norm(v)) for k, v in tree.items()}
        return {"loss": [1.0], "terms": [], "grad_norm": dict(norms),
                "delta_norm": dict(norms), "first_trace_norm": dict(
                    norms, **lm_direction.magnitudes(tree))}

    reference, program = side(mu), side(noisy)
    values, _ = compare.numbers(program, reference, lm_task.extra_numbers)
    assert values["first_grad_median_leaf"] < 2e-3
    assert 0.015 < values["first_grad_direction_median_leaf"] < 0.06
    assert (values["first_grad_direction_worst_leaf"]
            >= values["first_grad_direction_median_leaf"])
    same, _ = compare.numbers(reference, reference, lm_task.extra_numbers)
    assert same["first_grad_direction_worst_leaf"] == 0.0
    # the same sign patterns on the device and from a host array
    import jax.numpy as jnp

    on_device = lm_direction.project({"a": jnp.asarray(mu["a/kernel"])})
    again = lm_direction.project({"a": mu["a/kernel"]})
    assert set(on_device) == {f"{lm_direction.PREFIX}{k}"
                              for k in range(lm_direction.K)}
    for key in on_device:
        assert float(on_device[key]["a"]) == float(again[key]["a"])
    bare = dict(program, first_trace_norm={
        k: v for k, v in program["first_trace_norm"].items()
        if not k.startswith(lm_direction.PREFIX)})
    values, _ = compare.numbers(bare, reference, lm_task.extra_numbers)
    assert "first_grad_direction_median_leaf" not in values
    ok, _ = compare.judge(values, {"first_grad_direction_median_leaf": 1.0})
    assert not ok


def test_the_cells_limits_pass_the_sound_readings_and_fail_the_control():
    """The readings of PERF.md section 4 (chip runs of PR 28) against
    the cell's file: every sound run passes, the int8 control and the
    half batch do not, and it is the direction number that tells the
    control from a sound run, with room on both sides."""
    from benchmark import compare

    limits = dict(harness.load_cell(
        bench_smoke.ROOT, lm_smoke.CELL).workload["limits"])
    sound = {"loss_step1": 7.4e-6, "loss_step2": 7.4e-6, "loss_step3": 7.4e-6,
             "ce_loss_step1": 8.6e-6, "mtp_loss_step1": 6.3e-6,
             "first_grad_worst_leaf": 0.229, "first_grad_median_leaf": 4.3e-4,
             "first_grad_direction_median_leaf": 0.00859,
             "delta3_worst_leaf": 0.0048, "delta3_median_leaf": 1.0e-4,
             "frozen_moved": 0.0}
    control = dict(sound, first_grad_worst_leaf=0.0024,
                   first_grad_median_leaf=3.1e-4,
                   first_grad_direction_median_leaf=0.0270)
    half = dict(sound, loss_step1=9.7e-5, first_grad_median_leaf=0.032,
                first_grad_direction_median_leaf=0.44)
    assert compare.judge(sound, limits)[0]
    assert not compare.judge(control, limits)[0]
    assert not compare.judge(half, limits)[0]
    limit = limits["first_grad_direction_median_leaf"]
    assert 1.5 * 0.00859 < limit < 0.0270 / 1.5
    over = [k for k, v in control.items() if v > limits[k]]
    assert over == ["first_grad_direction_median_leaf"]


def test_the_mix_must_be_the_configurations_shape():
    cell = lm_smoke.smoke_cell()
    cell.workload["traffic"]["seq_len"] = 128
    with pytest.raises(RuntimeError, match="rows of"):
        harness.first_batches(cell, 3, 1)


def test_first_moment_is_adams_mu():
    import jax.numpy as jnp
    import optax

    params = {"w": jnp.ones((3,))}
    tx = optax.chain(optax.clip_by_global_norm(10.0),
                     optax.scale_by_adam(b1=0.9, b2=0.95),
                     optax.add_decayed_weights(0.1),
                     optax.scale_by_learning_rate(1e-3))
    _, state = tx.update({"w": jnp.full((3,), 2.0)}, tx.init(params), params)
    got = lm_task.first_moment(state)
    assert got["w"] == pytest.approx(0.2)
    # beside mu, its projections on the fixed sign patterns
    from benchmark import lm_direction

    assert set(got) == {"w"} | {f"{lm_direction.PREFIX}{k}"
                                for k in range(lm_direction.K)}
    assert abs(float(got[f"{lm_direction.PREFIX}0"]["w"])) in (
        pytest.approx(0.2), pytest.approx(0.6))
    with pytest.raises(RuntimeError, match="found 0"):
        lm_task.first_moment(optax.sgd(0.1, momentum=0.9).init(params))
