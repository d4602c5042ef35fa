"""BENCHMARK.json against the files it names, and a ``chips: 4`` cell
accepted as data (four virtual CPU devices, smoke widths; never a
device number)."""

import importlib
import json
import os
import re

import pytest

import bench_smoke
from bench_smoke import program_config_put_back  # noqa: F401
from benchmark import harness

ROOT = bench_smoke.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_names(manifest):
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


def test_every_workload_has_its_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    pairs = set()
    for w in manifest["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        pair = (w["config"], w["traffic"])
        assert pair not in pairs
        pairs.add(pair)
        cfg = configs[w["config"]]
        assert cfg["file"].startswith(tuple(manifest["paths"]))
        cell = harness.load_cell(ROOT, w["name"], manifest)
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


def test_every_per_layer_metric_has_a_reader_and_moves_something(manifest):
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
