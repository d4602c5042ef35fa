"""The hyper-connected task (benchmark/tasks/mhc_lm.py) on the CPU at
the tiny preset: program and reference agree through
``harness.run_cell`` over three AdamW steps (the losses, Adam's mu and
the parameters' change per leaf), planted faults read not ``correct``
(the row-only Sinkhorn among them), the file's spec is held against the
program's config, and the operation counts and readers give what
the paper's equations reckon.  Never a device number.

Tolerances (``mhc_lm_smoke.TINY_LIMITS``): float32 on both sides from
equal weights, so the gaps are summation order (seen: 2e-7 on losses,
6e-6 on the worst leaf of the change).
"""

import inspect
import json
import math
import os

import pytest

import bench_smoke
import mhc_lm_smoke
from bench_smoke import program_config_put_back  # noqa: F401
from benchmark import harness, lm_flops, mhc_flops, tasks
from benchmark.tasks import lm as lm_task, mhc_lm as mhc_task

INTERFACE = {"spec_mismatches", "build_loader", "first_moment",
             "reference_steps", "extra_numbers", "train_ops_per_row"}
READERS = {"mhc_sinkhorn_err", "mhc_res_diag_pct", "mhc_step_mfu_pct",
           "mhc_tokens_per_sec_per_chip", "mhc_step_ms_p50",
           "mhc_step_ms_p75", "mhc_input_wait_ms", "mhc_device_idle_pct",
           "mhc_device_peak_hbm_gb", "mhc_moe_load_max_over_mean",
           "mhc_moe_pairs_per_held_expert",
           "mhc_moe_ragged_dot_roofline_pct", "mhc_splash_fwd_roofline_pct",
           "mhc_splash_bwd_roofline_pct"}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def run(cell, seed, on_trainer=None):
    import jax

    return harness.run_cell(cell, seed=seed, seconds=0.5, trace=False,
                            t_start=0.0, devices=jax.devices()[:1],
                            peaks=bench_smoke.CPU_PEAK,
                            on_trainer=on_trainer)


@pytest.mark.parametrize("seed", [2147483999, 39])
def test_program_and_reference_agree_through_the_harness(seed):
    cell = mhc_lm_smoke.smoke_cell()
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
    # the held routing bias moves on neither side
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


def _rows_only(logits, iters, eps):
    """The planted fault: Sinkhorn with the column step left
    out (every row sums to one, the columns do not)."""
    import jax.numpy as jnp

    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return m


def test_the_row_only_projection_reads_not_correct(monkeypatch):
    """With the column step gone the streams' gradients are scaled by
    columns that no longer sum to one: at a fresh init the forward
    hardly sees it (the streams are near-copies), the first gradient
    does (seen: median leaf 3.8e-3, direction 0.018)."""
    from eksml_tpu.models.lm import mhc

    monkeypatch.setattr(mhc, "sinkhorn", _rows_only)
    out = run(mhc_lm_smoke.smoke_cell(), seed=11)
    assert not out["correct"]
    over = {k for k, row in out["compared"].items()
            if not row["value"] <= row["limit"]}
    assert {"first_grad_median_leaf", "first_grad_direction_median_leaf",
            "first_grad_worst_leaf", "delta3_worst_leaf"} <= over, over


@pytest.mark.parametrize("fault", [
    {"HC_SINKHORN_ITERS": 1},                   # the projection cut short
    {"ROPE_SCALING__MSCALE_ALL_DIM": 0.0},      # the softmax factor gone
    {"ROUTED_SCALING_FACTOR": 1.0},             # gates not scaled
], ids=lambda f: next(iter(f)).lower())
def test_a_planted_fault_reads_not_correct(fault):
    out = run(mhc_lm_smoke.smoke_cell(), seed=11,
              on_trainer=lambda t: _clone_with(t, **fault))
    assert not out["correct"]
    over = {k for k, row in out["compared"].items()
            if not row["value"] <= row["limit"]}
    assert "first_grad_worst_leaf" in over or "delta3_worst_leaf" in over
    assert "compiles_in_window" not in over


def test_joyais_rotary_table_for_yarns_reads_not_correct(monkeypatch):
    """MLA over the plain table theta^(-2j/d) with YaRN's softmax factor
    kept: the rotary alone is wrong."""
    from eksml_tpu.models.lm import xing

    yarn = xing.rotary
    monkeypatch.setattr(xing, "rotary", lambda lm: {
        "softmax_factor": yarn(lm)["softmax_factor"]})
    out = run(mhc_lm_smoke.smoke_cell(), seed=11)
    assert not out["correct"]
    over = {k for k, row in out["compared"].items()
            if not row["value"] <= row["limit"]}
    assert "first_grad_worst_leaf" in over or "delta3_worst_leaf" in over
    assert "compiles_in_window" not in over


def test_the_interface_is_the_six_functions():
    own = {n for n, f in vars(mhc_task).items()
           if inspect.isfunction(f) and f.__module__ == mhc_task.__name__
           and not n.startswith("_")}
    # the loader's wiring and Adam's first moment are the sequence
    # task's, imported: one TokenLoader, one optimizer
    assert own - {"rope_scaling", "delta3_past_copies"} == (
        INTERFACE - {"build_loader", "first_moment"})
    assert mhc_task.build_loader is lm_task.build_loader
    assert mhc_task.first_moment is lm_task.first_moment
    for name in INTERFACE:
        assert callable(getattr(mhc_task, name))
        assert name in (tasks.__doc__ or "")
    cell = harness.load_cell(bench_smoke.ROOT, mhc_lm_smoke.CELL)
    assert cell.task is mhc_task and cell.config["task"] == "mhc_lm"
    # one loss term: the direction numbers are all the task adds
    assert mhc_task.extra_numbers({"terms": [{"ce_loss": 1.0}]},
                                  {"terms": [{"ce_loss": 2.0}]}) == {}


def test_the_number_past_copies_leaves_out_block0s_attention_maps_alone():
    """``delta3_worst_leaf_past_copies`` is ``delta3_worst_leaf`` over
    every live leaf but block 0's attention maps (the map that reads four
    exact copies of the embedding): the same scale, the same floor on the
    reference's gradient."""
    from benchmark import compare

    leaves = {f"block{b}/{m}/{p}": 1.0 for b in range(5)
              for m in ("attn_hc", "mlp_hc") for p in ("phi/kernel",
                                                       "bias/bias")}
    leaves.update({"embed/kernel": 1.0, "block2/attn/q_a/kernel": 2.0,
                   "block3/moe/router/kernel": 1e-6})      # under the floor
    reference = {"loss": [1.0] * 3, "grad_norm": dict(leaves),
                 "first_trace_norm": dict(leaves), "delta_norm": dict(leaves)}
    program = {k: dict(v) if isinstance(v, dict) else v
               for k, v in reference.items()}
    delta = program["delta_norm"]
    delta["block0/attn_hc/phi/kernel"] = 1.3        # the copies' map: left out
    delta["block0/attn_hc/bias/bias"] = 0.6
    delta["block3/moe/router/kernel"] = 5.0         # under the floor
    assert mhc_task.delta3_past_copies(program, reference) == 0.0
    delta["block2/attn/q_a/kernel"] = 2.1           # 0.1 over its own 2
    delta["block4/mlp_hc/phi/kernel"] = 1.04        # 0.04 over the median 1
    got = mhc_task.extra_numbers(program, reference)
    assert got["delta3_worst_leaf_past_copies"] == pytest.approx(0.05)
    values, where = compare.numbers(program, reference)
    assert values["delta3_worst_leaf"] == pytest.approx(0.4)
    assert where["delta3_worst_leaf"] == "block0/attn_hc/bias/bias"
    delta["block1/mlp_hc/phi/kernel"] = float("nan")
    assert mhc_task.delta3_past_copies(program, reference) == math.inf


def test_the_file_holds_the_published_config_and_is_held_to_the_program():
    """Every key of the catalog's ``config`` unchanged (in ``model`` and
    at the top level), the held counts in keys of their own beside them,
    and ``spec_mismatches`` empty against the program's config; one
    changed key of each kind is named."""
    cell = harness.load_cell(bench_smoke.ROOT, mhc_lm_smoke.CELL)
    published = {
        "model_type": "xing4_0", "hidden_size": 3584,
        "intermediate_size": 9216, "num_hidden_layers": 40,
        "num_attention_heads": 32, "num_key_value_heads": 32,
        "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "vocab_size": 131072,
        "max_position_embeddings": 262144, "first_k_dense_replace": 2,
        "num_nextn_predict_layers": 1, "n_routed_experts": 64,
        "num_experts_per_tok": 4, "moe_intermediate_size": 1024,
        "n_shared_experts": 1, "routed_scaling_factor": 2, "hc_mult": 4,
        "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
        "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
        "rope_theta": 10000, "rms_norm_eps": 1e-06,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc"}
    for key, value in published.items():
        assert cell.spec[key] == value, key
        assert cell.config[key] == value, key
    assert cell.spec["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    catalog = os.environ.get("MODEL_CATALOG_JSONL", "")
    if catalog and os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Xing4.0-29B-A4B")
        assert cell.config["source"].startswith(row["source_url"])
        for key, value in row["config"].items():
            assert cell.spec[key] == value and cell.config[key] == value
    assert len(cell.config["source"]) <= 200
    assert "arXiv:2512.24880" in cell.config["source"]
    assert (cell.spec["layers_held"], cell.spec["dense_layers_held"],
            cell.spec["mtp_layers_held"], cell.spec["experts_held"],
            cell.spec["vocab_rows"], cell.spec["seq_len"],
            cell.spec["init_std"], cell.spec["embed_init_std"]) == (
                5, 1, 0, [0, 8], 16384, 4096, 0.006, 1.0)
    assert set(cell.config["reduced"]) == {
        "depth", "experts_held", "vocabulary", "schedule", "weights",
        "data", "router_bias_update"}
    assert set(cell.config["assumed"]) >= {
        "hc_equations", "hc_sinkhorn", "hc_expansion", "hc_init", "rope",
        "recipe", "init", "seq_len", "document_mix", "batch"}
    assert "8 of 64" in cell.config["deployment"]
    assert cell.hyper["global_batch"] == 1
    # peak 2.2e-4 at a global batch of 1, in the program's per-8-rows terms
    assert cell.hyper["base_lr"] * 1 / 8 == pytest.approx(2.2e-4)
    assert cell.workload["traffic"]["seq_len"] == 4096
    assert cell.workload["traffic"]["rows_per_chip"] == 1

    cfg = harness.program_config(cell, 1, "/tmp/none", False)
    assert cfg.MODEL.NAME == "xing4" and cfg.LM.NUM_MTP == 0
    assert mhc_task.spec_mismatches(cfg, cell.spec, cell.hyper) == []
    # the program's count of what the file's reduced.depth states
    assert "759,489,806" in cell.config["reduced"]["depth"]
    # a width, a hard-wired choice, a rotary number, the maps'
    # iterations and clamp, a held count, the init, the optimizer, a
    # published count under what runs
    wrong = mhc_task.spec_mismatches(
        cfg, dict(
            cell.spec, q_lora_rank=1536, topk_method="greedy",
            rope_scaling=dict(cell.spec["rope_scaling"], mscale_all_dim=0),
            hc_sinkhorn_iters=10, mhc_h_res_clamp_max=20,
            dense_layers_held=2, hc_phi_std=0.006,
            first_k_dense_replace=0, vocab_size=8192),
        dict(cell.hyper, adam_b2=0.999))
    assert [w.split(":")[0] for w in wrong] == [
        "topk_method", "q_lora_rank", "rope_scaling", "hc_sinkhorn_iters",
        "mhc_h_res_clamp_max", "dense_layers_held", "hc_phi_std", "adam_b2",
        "first_k_dense_replace", "vocab_size"]


def test_required_operations_are_the_issues_arithmetic():
    spec = harness.load_cell(bench_smoke.ROOT, mhc_lm_smoke.CELL).spec
    f = mhc_flops
    # MLA's five projections at hidden 3,584: 28.41 M a token
    attention = lm_flops.attention_macs_per_token(spec)
    assert attention == (3584 * 768 + 768 * 32 * 192 + 3584 * 576
                         + 512 * 32 * 256 + 32 * 128 * 3584) == 28_409_856
    # one held pair in two a token under uniform routing (4 x 8 / 64)
    assert lm_flops.held_pairs_per_token(spec) == 0.5
    assert lm_flops.expert_layer_macs_per_token(spec) == (
        attention + 3584 * 64 + 3 * 3584 * 1024 + 0.5 * 3 * 3584 * 1024)
    assert f.expert_layers(spec) == 4
    # a sublayer's maps: the [14,336, 24] projection and 24 channels of
    # mixes
    assert f.maps_macs_per_token(spec) == 14336 * 24 + 24 * 3584 == 430_080
    # 370 M multiply-adds a token in the products (the paper's count)
    macs = f.forward_macs_per_token(spec)
    assert macs == (attention + 3 * 3584 * 9216
                    + 4 * lm_flops.expert_layer_macs_per_token(spec)
                    + 10 * 430_080 + 3584 * 16384)
    assert macs == pytest.approx(370.9e6, rel=1e-3)
    core = lm_flops.attention_core_forward_ops(spec, 4096)
    assert core == 2 * (4096 * 4096 / 2) * 32 * (192 + 128)
    row = f.train_ops_per_row(spec)
    assert row == 3 * (2 * macs * 4096 + 5 * core)
    # 11.7 TFLOP a row; 5 cores are 2.58 T of it; the maps 1.2%
    assert row == pytest.approx(11.69e12, rel=2e-3)
    assert 3 * 5 * core == pytest.approx(2.577e12, rel=1e-3)
    assert 3 * 2 * 10 * 430_080 * 4096 / row == pytest.approx(0.009, abs=2e-3)
    assert mhc_task.train_ops_per_row(spec) == row
    # at JoyAI's MFU of 27.6%: a 215 ms step, 4.65 rows/s
    assert row / (0.276 * 197e12) == pytest.approx(0.215, rel=0.01)
    # one MLA core: operations bound
    assert f.attention_core_seconds(spec, 4096, 2, PEAK) == pytest.approx(
        core / 197e12)
    # one grouped product over 8 held experts at 256 pairs each
    call = lm_flops.grouped_product_call(spec, 8 * 256, 2)
    assert call == {"ops": 2 * 2048 * 3584 * 1024,
                    "bytes": (8 * 3584 * 1024 + 2048 * 4608) * 2}


def _ctx(cell, spans=(), trace=None, traced_steps=0, rows_per_s=4.6):
    return harness.TraceContext(
        spec=cell.spec, task=cell.task, chips=1, images_per_step=1,
        images_per_sec_per_chip=rows_per_s, window_s=20.0, window_steps=90,
        traced_steps=traced_steps, feature_itemsize=2, peak=dict(PEAK),
        spans=list(spans), trace=trace)


def test_the_cells_readers():
    from benchmark import trace_reduce

    cell = harness.load_cell(bench_smoke.ROOT, mhc_lm_smoke.CELL)
    # ">=": a later benchmark PR may append a reader to this cell
    assert {m["name"] for m in cell.per_layer} >= READERS
    with open(os.path.join(bench_smoke.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    # the readers that exist under another cell's name are those readers
    for alias, original in (
            ("mhc_step_mfu_pct", "step_mfu_pct"),
            ("mhc_step_ms_p50", "step_ms_p50"),
            ("mhc_step_ms_p75", "loop_step_ms_p75"),
            ("mhc_input_wait_ms", "input_wait_ms"),
            ("mhc_device_idle_pct", "device_idle_pct"),
            ("mhc_device_peak_hbm_gb", "lm_device_peak_hbm_gb"),
            ("mhc_tokens_per_sec_per_chip", "lm_tokens_per_sec_per_chip"),
            ("mhc_moe_load_max_over_mean", "moe_load_max_over_mean"),
            ("mhc_batch_build_ms", "batch_build_ms"),
            ("mhc_h2d_prefetch_ms", "h2d_prefetch_ms"),
            ("mhc_moe_rows_moved_pct", "moe_rows_moved_pct"),
            ("mhc_log_drain_ms", "log_drain_ms"),
            ("mhc_log_row_ms", "log_row_ms"),
            ("mhc_fit_edges_ms", "fit_edges_ms"),
            ("mhc_window_idle_pct", "window_idle_pct"),
            ("mhc_gc_pause_ms", "gc_pause_ms")):
        assert (harness._module("metrics", alias).read
                is harness._module("metrics", original).read)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert by_name[alias][key] == by_name[original][key], alias
        assert by_name[alias]["workloads"] == [mhc_lm_smoke.CELL], alias
    # readers of their own under the accepted ones' layer and unit: the
    # accepted ones count layers from keys this file keeps as published
    for own, like in (
            ("mhc_moe_pairs_per_held_expert", "moe_pairs_per_held_expert"),
            ("mhc_moe_ragged_dot_roofline_pct",
             "moe_ragged_dot_roofline_pct"),
            ("mhc_splash_fwd_roofline_pct",
             "loop_splash_mha_fwd_roofline_pct"),
            ("mhc_splash_bwd_roofline_pct",
             "loop_splash_mha_bwd_roofline_pct")):
        assert (harness._module("metrics", own).read
                is not harness._module("metrics", like).read)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert by_name[own][key] == by_name[like][key], own
    for name in READERS:
        assert by_name[name]["workloads"] == [mhc_lm_smoke.CELL]
    for name in ("mhc_sinkhorn_err", "mhc_res_diag_pct"):
        assert by_name[name]["source"] == "program_counter"
        assert by_name[name]["layer"] == "model - eksml_tpu/models/lm"
    # nothing to read: no rate, no span, no trace
    assert harness.read_per_layer(cell, _ctx(cell, rows_per_s=0.0)) == {}

    spans = [{"name": "moe_route", "dur": 0.0, "args": {
        "step": s, "moe_pairs_held": p, "moe_load_max_over_mean": m,
        "moe_pairs_dropped": 0.0}}
        for s, p, m in ((20, 8192.0, 1.4), (40, 9216.0, 1.8))]
    spans += [{"name": "mhc", "dur": 0.0, "args": {
        "step": s, "mhc_sinkhorn_err": e, "mhc_res_diag": d}}
        for s, e, d in ((20, 1.2e-6, 0.26), (40, 2.0e-6, 0.28))]
    spans += [{"name": "data_wait", "dur": 60.0, "args": {}}] * 3
    # 18 completions: twelve differences of 215 ms, five of 230 ms
    ends = [1e6 + 215e3 * i for i in range(13)]
    ends += [ends[-1] + 230e3 * i for i in range(1, 6)]
    spans += [{"name": "device_step", "ts": end - 100.0, "dur": 100.0,
               "args": {"step": i}} for i, end in enumerate(ends)]
    # two traced steps: 10 forward sites (5 layers, twice under remat)
    # of 1.4 ms a run, 5 fused backward sites of 4 ms; 36 grouped
    # products of 0.1 ms (4 layers x (2 forward + 1, recomputed, + 3 + 3))
    ops = {}
    for i in range(5):
        ops[f"splash_mha_fwd_residuals.{i}"] = 2 * 1.4e-3
        ops[f"splash_mha_fwd_no_residuals.{i}"] = 2 * 1.4e-3
        ops[f"splash_mha_dkv_no_residuals.{i}"] = 2 * 4e-3
    ops.update({f"ragged-dot-none.{i}": 2 * 0.1e-3 for i in range(36)})
    ops["fusion.7"] = 0.4
    trace = trace_reduce.TraceSummary(devices=1, steps=2, window_s=0.5,
                                      busy_s=0.498, op_seconds=ops)
    ctx = _ctx(cell, spans, trace, traced_steps=2)
    ctx.memory_stats = [{"peak_bytes_reserved": 5_000_000_000,
                         "peak_bytes_in_use": 13_500_000_000}]
    got = harness.read_per_layer(cell, ctx)
    value = {k: v["value"] for k, v in got.items()}
    assert set(value) >= READERS
    row = mhc_flops.train_ops_per_row(cell.spec)
    assert value["mhc_step_mfu_pct"] == pytest.approx(
        100 * row * 4.6 / 197e12)
    assert value["mhc_tokens_per_sec_per_chip"] == 4.6 * 4096
    assert value["mhc_step_ms_p50"] == pytest.approx(215.0)
    assert value["mhc_step_ms_p75"] == pytest.approx(230.0)
    assert value["mhc_input_wait_ms"] == pytest.approx(0.06)
    assert value["mhc_device_idle_pct"] == pytest.approx(0.4)
    assert value["mhc_device_peak_hbm_gb"] == pytest.approx(13.5)
    assert value["mhc_moe_load_max_over_mean"] == pytest.approx(1.6)
    assert value["mhc_sinkhorn_err"] == 2.0e-6
    assert value["mhc_res_diag_pct"] == pytest.approx(27.0)
    # 8,704 pairs a step over 4 expert layers x 8 held experts
    assert value["mhc_moe_pairs_per_held_expert"] == pytest.approx(272.0)
    # required work from the spec: 5 cores x 1 row a step whatever the
    # number of call sites; the forward ran twice
    core = mhc_flops.attention_core_seconds(cell.spec, 4096, 2, PEAK)
    assert value["mhc_splash_fwd_roofline_pct"] == pytest.approx(
        100 * 5 * core * 2 / (10 * 2 * 1.4e-3))
    assert value["mhc_splash_bwd_roofline_pct"] == pytest.approx(
        100 * 2 * 5 * core * 2 / (5 * 2 * 4e-3))
    assert value["mhc_splash_fwd_roofline_pct"] < 50
    call = lm_flops.grouped_product_call(cell.spec, 8704.0 / 4, 2)
    need = max(call["ops"] / 197e12, call["bytes"] / 819e9)
    assert value["mhc_moe_ragged_dot_roofline_pct"] == pytest.approx(
        100 * need * 36 / (36 * 0.1e-3))
    assert all(v <= 100 for k, v in value.items() if k.endswith("_pct"))
    # a program without the spans or the kernels (the parent commit
    # under this cell's files): those fall silent, nothing raises
    bare = harness.read_per_layer(cell, _ctx(
        cell, [], trace_reduce.TraceSummary(
            devices=1, steps=2, window_s=1.0, busy_s=0.9,
            op_seconds={"fusion.7": 1.0}), traced_steps=2))
    assert set(bare) == {"mhc_step_mfu_pct", "mhc_tokens_per_sec_per_chip",
                         "mhc_device_idle_pct"}


# the sound readings of the cell (one v5e chip, PERF.md section 4):
# the largest of each number over 11 seeds (``delta3_worst_leaf_past_copies``
# over the 3 read since the task computes it)
SOUND = {"loss_step1": 5.07e-6, "loss_step2": 6.83e-6, "loss_step3": 5.86e-6,
         "first_grad_worst_leaf": 0.0236, "first_grad_median_leaf": 3.83e-4,
         "first_grad_direction_median_leaf": 0.00884,
         "delta3_worst_leaf": 0.286, "delta3_median_leaf": 1.04e-4,
         "delta3_worst_leaf_past_copies": 0.0415, "frozen_moved": 0.0}
# the faults, each in the reference against the exact reference on the
# same batches, by seed; a number a reading lacks (seed 39201 was read
# before the task computed the last) stands at its largest sound reading
CONTROL = {
    39201: dict(loss_step1=3.32e-6, loss_step2=1.42e-5, loss_step3=8.69e-6,
                first_grad_worst_leaf=0.0211, first_grad_median_leaf=7.79e-4,
                first_grad_direction_median_leaf=0.0333,
                delta3_worst_leaf=7.88e-3, delta3_median_leaf=1.70e-4),
    3921: dict(loss_step1=1.31e-5, loss_step2=8.48e-6, loss_step3=1.15e-5,
               first_grad_worst_leaf=0.0382, first_grad_median_leaf=7.74e-4,
               first_grad_direction_median_leaf=0.0362,
               delta3_worst_leaf=0.0115, delta3_median_leaf=2.32e-4,
               delta3_worst_leaf_past_copies=4.82e-3)}
HALF = {
    39201: dict(loss_step1=6.20e-4, loss_step2=7.04e-5, loss_step3=3.45e-4,
                first_grad_worst_leaf=0.367, first_grad_median_leaf=0.0297,
                first_grad_direction_median_leaf=0.460,
                delta3_worst_leaf=0.207, delta3_median_leaf=0.0156),
    3921: dict(loss_step1=1.89e-4, loss_step2=1.84e-4, loss_step3=4.57e-4,
               first_grad_worst_leaf=0.324, first_grad_median_leaf=0.0490,
               first_grad_direction_median_leaf=0.469,
               delta3_worst_leaf=0.211, delta3_median_leaf=0.0186,
               delta3_worst_leaf_past_copies=0.211)}
ROWS_ONLY = {
    39201: dict(loss_step1=1.07e-6, loss_step2=5.85e-7, loss_step3=1.46e-6,
                first_grad_worst_leaf=1.058, first_grad_median_leaf=1.71e-3,
                first_grad_direction_median_leaf=0.0237,
                delta3_worst_leaf=0.668, delta3_median_leaf=2.83e-4),
    3921: dict(loss_step1=1.27e-6, loss_step2=1.17e-6, loss_step3=1.17e-6,
               first_grad_worst_leaf=0.312, first_grad_median_leaf=2.30e-3,
               first_grad_direction_median_leaf=0.0293,
               delta3_worst_leaf=0.730, delta3_median_leaf=5.11e-4,
               delta3_worst_leaf_past_copies=0.617)}


def test_the_cells_limits_pass_the_sound_readings_and_fail_the_faults():
    """The readings of PERF.md section 4 against the cell's file: every
    sound run passes; the int8 control, half the row's tokens and the
    row-only Sinkhorn (each in the reference, against the exact
    reference, on seeds 39201 and 3921) do not.  The control is told
    apart by ``first_grad_direction_median_leaf`` (3.8 and 4.1 times the
    largest sound reading); its other numbers stand inside or beside the
    sound range.  ``delta3_worst_leaf`` is block 0's attention maps on
    every sound seed: they mix four copies of the embedding, so the
    ``H_res`` columns of ``phi`` get a gradient of rounding, which Adam
    turns into full-size steps; ``delta3_worst_leaf_past_copies`` leaves
    that map out and tells half the tokens apart where it cannot."""
    from benchmark import compare

    limits = dict(harness.load_cell(
        bench_smoke.ROOT, mhc_lm_smoke.CELL).workload["limits"])
    assert set(limits) == set(SOUND)
    assert compare.judge(SOUND, limits)[0]
    over = lambda faulty: {k for k, v in faulty.items() if v > limits[k]}
    for seed in (39201, 3921):
        control = dict(SOUND, **CONTROL[seed])
        half = dict(SOUND, **HALF[seed])
        rows_only = dict(SOUND, **ROWS_ONLY[seed])
        assert "first_grad_direction_median_leaf" in over(control)
        assert over(control) <= {"first_grad_direction_median_leaf",
                                 "delta3_median_leaf"}
        assert over(half) >= set(HALF[seed]) - {"delta3_worst_leaf"}
        assert over(rows_only) >= {
            "first_grad_worst_leaf", "first_grad_median_leaf",
            "first_grad_direction_median_leaf", "delta3_worst_leaf",
            "delta3_median_leaf"}
        for faulty in (control, half, rows_only):
            assert not compare.judge(faulty, limits)[0]
    assert over(dict(SOUND, **ROWS_ONLY[3921])) >= {
        "delta3_worst_leaf_past_copies"}
    unchanged = dict(SOUND, first_grad_worst_leaf=1.0, delta3_worst_leaf=1.0,
                     first_grad_median_leaf=1.0, delta3_median_leaf=1.0,
                     delta3_worst_leaf_past_copies=1.0)
    assert over(unchanged) >= {"first_grad_worst_leaf", "delta3_worst_leaf",
                               "delta3_worst_leaf_past_copies"}
    assert not compare.judge(unchanged, limits)[0]
    # room on the sound side: every limit at least 1.8 times its largest
    # sound reading
    for name, value in SOUND.items():
        if value:
            assert limits[name] >= 1.8 * value, name
