"""BENCHMARK.json against the files it names, a ``chips: 4`` cell
accepted as data (four virtual CPU devices, smoke widths; never a
device number), and the proof that a later PR adds a cell and its
readers with new files and appended entries alone: the checks take the
root they check, and ``test_the_manifest_takes_an_appended_cell`` runs
them on a copy of the benchmark's data that has grown."""

import filecmp
import importlib
import json
import os
import re
import shutil
import sys

import pytest

import bench_smoke
import benchmark.metrics
from bench_smoke import program_config_put_back  # noqa: F401
from benchmark import harness

ROOT = bench_smoke.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def check_keys_and_names(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in manifest["configs"]]
             + [w["name"] for w in manifest["workloads"]]
             + [m["name"] for m in manifest["end_to_end"]
                + manifest["per_layer"]])
    for n in names + [w["traffic"] for w in manifest["workloads"]]:
        assert NAME.match(n), n
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    metric_names = [m["name"] for m in manifest["end_to_end"]
                    + manifest["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    assert "setup_s" in [m["name"] for m in manifest["end_to_end"]]
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    assert 1 <= manifest["run_seconds"] <= 51
    cells = len(manifest["workloads"])
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, cells // 4)


def test_keys_and_names(manifest):
    check_keys_and_names(manifest)


def check_every_workload_has_its_files(root, manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    pairs = set()
    for w in manifest["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        pair = (w["config"], w["traffic"])
        assert pair not in pairs
        pairs.add(pair)
        cfg = configs[w["config"]]
        assert cfg["file"].startswith(tuple(manifest["paths"]))
        cell = harness.load_cell(root, w["name"], manifest)
        assert cell.workload["config"] == w["config"]
        assert cell.workload["chips"] == w["chips"]
        assert cell.workload["why"] == w["why"]
        # the mix is a data file of its own, found by the traffic's
        # name, and the task's loader yields a first batch from it
        assert cell.workload["traffic"]["name"] == w["traffic"]
        (batch,) = harness.first_batches(cell, 7, 1)
        assert batch and all(v.shape[0] == cell.hyper["global_batch"]
                             for v in batch.values())
        assert cell.config["source"] == cfg["source"]
        assert sorted(cell.config["reduced"]) == sorted(cfg["reduced"])
        assert set(cell.workload["limits"]) >= {
            "loss_step2", "first_grad_worst_leaf", "delta3_worst_leaf",
            "frozen_moved"}
    assert {c["name"] for c in manifest["configs"]} == {
        w["config"] for w in manifest["workloads"]}


def test_every_workload_has_its_files(manifest):
    check_every_workload_has_its_files(ROOT, manifest)


def check_every_per_layer_metric_has_a_reader_and_moves_something(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = [w["name"] for w in manifest["workloads"]]
    layers = {}
    for m in manifest["per_layer"]:
        mod = importlib.import_module(
            "benchmark.metrics." + m["name"].replace(".", "_")
            .replace("-", "_"))
        assert callable(mod.read)
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in moved.get("workloads", cells)
        layers.setdefault(m["layer"].split(" - ")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers
    assert any("mfu" in re.split(r"[_.\-]", m["name"])
               for m in manifest["per_layer"])


def test_every_per_layer_metric_has_a_reader_and_moves_something(manifest):
    check_every_per_layer_metric_has_a_reader_and_moves_something(manifest)


def test_readers_return_nothing_when_there_is_nothing(manifest):
    cell = bench_smoke.smoke_cell(mask=True)
    ctx = harness.TraceContext(
        spec=cell.spec, task=cell.task, chips=1, images_per_step=2,
        images_per_sec_per_chip=0.0, window_s=1.0, window_steps=0,
        traced_steps=0, feature_itemsize=4, peak=bench_smoke.CPU_PEAK)
    assert harness.read_per_layer(cell, ctx) == {}


def test_a_four_chip_cell_is_data():
    """mesh (4,1), TRAIN.NUM_CHIPS=4, rows per step = 4 x batch: the
    same harness path, on four virtual CPU devices."""
    import jax

    cell = bench_smoke.smoke_cell(mask=False, chips=4, batch_per_chip=1)
    out = harness.run_cell(
        cell, seed=5, seconds=0.5, trace=False, t_start=0.0,
        devices=jax.devices()[:4], peaks=bench_smoke.CPU_PEAK)
    assert out["device"]["count"] == 4 and out["device"]["platform"] == "cpu"
    assert out["attempted"] > 0
    assert out["correct"], out["compared"]


DATA = ("configs", "workloads", "mixes", "metrics")


def test_the_manifest_takes_an_appended_cell(tmp_path, monkeypatch):
    """What a later PR does, on a copy of the benchmark's data under
    ``tmp_path``: one more configuration, one more cell on a mix that
    is there, two more per-layer metrics, each with its reader and its
    example, as new files and appended entries.  Every check of the
    accepted tests that reads the manifest passes on the grown copy,
    and no file that was there differs but ``BENCHMARK.json``."""
    import test_examples
    import test_roi_bwd_tile_share
    import test_span_metrics

    root = str(tmp_path)
    bench = os.path.join(root, "benchmark")
    for d in DATA:
        shutil.copytree(os.path.join(ROOT, "benchmark", d),
                        os.path.join(bench, d),
                        ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)

    def rewrite(kind, old, new, **changes):
        with open(os.path.join(bench, kind, old + ".json")) as f:
            data = dict(json.load(f), **changes)
        with open(os.path.join(bench, kind, new + ".json"), "w") as f:
            json.dump(data, f, indent=1)

    def entry_of(key, name):
        return next(e for e in manifest[key] if e["name"] == name)

    # --- the addition: new files ...
    rewrite("configs", "joyai-llm-flash-ep16", "appended-config",
            name="appended-config")
    rewrite("workloads", "joyai-flash-train-4k-ep16", "appended-cell",
            name="appended-cell", config="appended-config")
    readers = {"appended_step_mfu_pct": "lm_step_mfu_pct",
               "appended_step_ms_p50": "lm_step_ms_p50"}
    for new, old in readers.items():
        with open(os.path.join(bench, "metrics", new + ".py"), "w") as f:
            f.write(f"from benchmark.metrics.{old} import read  # noqa\n")
        rewrite(os.path.join("metrics", "examples"), old, new,
                workload="appended-cell")
    # --- ... and entries appended to the manifest's lists
    manifest["configs"].append(dict(
        entry_of("configs", "joyai-llm-flash-ep16"), name="appended-config",
        file="benchmark/configs/appended-config.json"))
    manifest["workloads"].append(dict(
        entry_of("workloads", "joyai-flash-train-4k-ep16"),
        name="appended-cell", config="appended-config"))
    manifest["per_layer"] += [
        dict(entry_of("per_layer", old), name=new,
             workloads=["appended-cell"]) for new, old in readers.items()]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)

    # the harness finds readers as modules of ``benchmark.metrics``:
    # the copy's directory joins the package's path for this test
    monkeypatch.setattr(benchmark.metrics, "__path__",
                        [os.path.join(bench, "metrics"),
                         *benchmark.metrics.__path__])
    for new in readers:
        monkeypatch.delitem(sys.modules, f"benchmark.metrics.{new}",
                            raising=False)
    try:
        check_keys_and_names(manifest)
        check_every_workload_has_its_files(root, manifest)
        check_every_per_layer_metric_has_a_reader_and_moves_something(
            manifest)
        test_roi_bwd_tile_share.check_the_entry_in_the_manifest(root)
        test_span_metrics.check_entries_are_registered_or_not_in_the_tree(
            root, manifest)
        for m in manifest["per_layer"]:
            test_examples.check_example(root, manifest, m["name"])
        test_examples.check_every_cell_reports_all_its_metrics_on_their_examples(
            root, manifest)
        cell = harness.load_cell(root, "appended-cell", manifest)
        assert [m["name"] for m in cell.per_layer] == list(readers)
        assert cell.config["name"] == "appended-config"
    finally:
        for new in readers:
            sys.modules.pop(f"benchmark.metrics.{new}", None)

    # replayed on the real tree, the addition is `BENCHMARK.json` and
    # new files: every file that was there is there unchanged
    added = []
    for d in DATA:
        cmp = filecmp.dircmp(os.path.join(ROOT, "benchmark", d),
                             os.path.join(bench, d),
                             ignore=["__pycache__"])
        stack = [cmp]
        while stack:
            c = stack.pop()
            assert not c.left_only and not c.diff_files and not c.funny_files
            added += [os.path.relpath(os.path.join(c.right, f), bench)
                      for f in c.right_only]
            stack += c.subdirs.values()
    assert sorted(added) == sorted(
        ["configs/appended-config.json", "workloads/appended-cell.json"]
        + [f"metrics/{n}.py" for n in readers]
        + [f"metrics/examples/{n}.json" for n in readers])


def test_a_traced_run_traces_the_device_alone(monkeypatch):
    """``--trace 1`` on the CPU at smoke widths: the harness's own
    traced path end to end.  The capture starts with the host tracer
    and the Python tracer off (PR 32: under the host tracer XLA's
    re-tiling of a batch records an event for every block it moves and
    the chip starves behind it), around a fit of ``trace_steps`` steps
    after the window; the spans' readers report, and with no TPU plane
    in the trace the line carries no ``busy_s``: never a CPU's."""
    import jax

    started = []
    start_trace = jax.profiler.start_trace

    def spy(log_dir, **kw):
        started.append(kw["profiler_options"])
        return start_trace(log_dir, **kw)

    monkeypatch.setattr(jax.profiler, "start_trace", spy)
    cell = bench_smoke.smoke_cell(mask=False)
    out = harness.run_cell(
        cell, seed=11, seconds=0.5, trace=True, t_start=0.0,
        devices=jax.devices()[:1], peaks=bench_smoke.CPU_PEAK)
    (options,) = started
    assert options.host_tracer_level == 0
    assert options.python_tracer_level == 0
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0
    assert {"step_mfu_pct", "batch_build_ms", "h2d_prefetch_ms"} <= set(
        out["metrics"])
    assert "device_idle_pct" not in out["metrics"]
    assert "busy_s" not in out["device"] and "breakdown" not in out
