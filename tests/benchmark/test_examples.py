"""Every per-layer entry of ``BENCHMARK.json`` ships with an example
beside its reader (``benchmark/metrics/examples/<name>.json``): what
the reader reads, and the value it must then return.  The tests that
pin the manifest's shape build their contexts from these
(``bench_smoke.example_context``), so a PR adds a reader with three new
things and no edit: the reader, its example, its appended entry."""

import dataclasses
import json
import os

import pytest

import bench_smoke
from benchmark import harness

with open(os.path.join(bench_smoke.ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)


def check_example(root, manifest, name):
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    example = bench_smoke.load_example(name, root)
    assert set(example) <= {"reads", "workload", "context", "peak", "spans",
                            "trace", "memory_stats", "value",
                            "rel_tolerance"}
    # the example is of a cell that reports the metric: its spec, task
    # and chips are that cell's
    assert example["workload"] in entry.get(
        "workloads", [w["name"] for w in manifest["workloads"]])
    cell = harness.load_cell(root, example["workload"], manifest)
    reader = harness._module("metrics", name)
    ctx = bench_smoke.example_context(cell, [name], root)
    want = pytest.approx(example["value"], rel=example["rel_tolerance"])
    assert reader.read(ctx) == want
    assert 0 < example["rel_tolerance"] <= 1e-3
    # through the harness, under the entry's unit
    alone = dataclasses.replace(cell, per_layer=[entry])
    assert harness.read_per_layer(alone, ctx) == {
        name: {"value": want, "unit": entry["unit"]}}
    # and nothing from a context that holds nothing: never 0
    nothing = bench_smoke.example_context(cell, (), root)
    assert reader.read(nothing) is None
    assert harness.read_per_layer(alone, nothing) == {}


@pytest.mark.parametrize("name", [m["name"] for m in MANIFEST["per_layer"]])
def test_the_reader_returns_its_examples_value_and_nothing_from_nothing(name):
    check_example(bench_smoke.ROOT, MANIFEST, name)


def check_every_cell_reports_all_its_metrics_on_their_examples(root,
                                                               manifest):
    for w in manifest["workloads"]:
        cell = harness.load_cell(root, w["name"], manifest)
        ctx = bench_smoke.example_context(cell, root=root)
        assert set(harness.read_per_layer(cell, ctx)) == {
            m["name"] for m in cell.per_layer}, w["name"]
        assert cell.per_layer, w["name"]


def test_every_cell_reports_all_its_metrics_on_their_examples():
    """The real cells (the two accepted tests do this for the mask
    cell at smoke widths): the examples of one cell's metrics do not
    stand in one another's way."""
    check_every_cell_reports_all_its_metrics_on_their_examples(
        bench_smoke.ROOT, MANIFEST)
