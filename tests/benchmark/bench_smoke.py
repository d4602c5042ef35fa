"""Smoke-width cells for the CPU tests of the benchmark: the harness's
own path (``harness.run_cell``) at ``config.SMOKE_OVERRIDES`` widths in
float32.  Never a device number."""

import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CPU_PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
            "hbm_bytes": 16e9}

@pytest.fixture(autouse=True)
def program_config_put_back():
    """A test module that calls ``harness.program_config`` (through
    ``run_cell`` or ``first_batches``) imports this beside the module:
    the call writes a cell's overrides into the program's one global
    config tree (``TRAIN.BATCH_SIZE_PER_CHIP=4`` rescales
    ``TRAIN.STEPS_PER_EPOCH``), and tests elsewhere read the tree bare
    (``tests/test_config.py``); which files share a worker under
    ``--dist loadfile`` changes with their run times.  No conftest.py
    here: ``tests/`` modules import theirs by that name."""
    from eksml_tpu.config import config

    saved, frozen = config.to_dict(), config._frozen
    yield
    config.freeze(False)
    config.from_dict(saved)
    config.freeze(frozen)


def smoke_overrides(batch_per_chip):
    from eksml_tpu.config import SMOKE_OVERRIDES

    return list(SMOKE_OVERRIDES) + [
        "TRAIN.PRECISION=float32", "DATA.NUM_WORKERS=0",
        f"TRAIN.BATCH_SIZE_PER_CHIP={batch_per_chip}"]


SMOKE_TRAFFIC = {
    "records": 16, "sizes": [[96, 128, 0.70], [128, 96, 0.25],
                             [128, 128, 0.05]],
    "instances": [1, 5], "polygon_vertices": 12, "box_side_px": [8, 60],
    "num_classes": 5}

# what decides `correct` at smoke widths in float32 on the CPU: both
# sides compute in float32, so the gaps are reduction order and the
# rare discrete flip of a threshold; see test_reference_parity.py
SMOKE_LIMITS = {"rpn_loss_step1": 1e-5, "rpn_box_loss_step1": 1e-5,
                "loss_step1": 1e-4,
                "loss_step2": 1e-3, "loss_step3": 1e-3,
                "first_grad_worst_leaf": 1e-3, "first_grad_median_leaf": 1e-4,
                "delta3_worst_leaf": 1e-2, "delta3_median_leaf": 1e-3,
                "frozen_moved": 0.0}


def smoke_cell(mask: bool, chips: int = 1, limits=None,
               batch_per_chip: int = 2):
    from benchmark import harness

    # the real cell's configuration, task and metrics, cut to smoke size
    real = harness.load_cell(ROOT, "mask-r50-train-1344-b4" if mask
                             else "frcnn-r50-train-1344-b4")
    config, name = real.config, real.config["name"]
    model = dict(config["model"], canvas=[128, 128], resnet_blocks=[1, 1, 1, 1],
                 fpn_channels=32, rpn_pre_nms_topk=64, rpn_post_nms_topk=32,
                 frcnn_batch_per_im=16, fc_head_dim=64, num_classes=5,
                 mask_head_dim=16, max_gt_boxes=8,
                 # the CPU runs the program's XLA ROIAlign, which keeps
                 # the plain FPN level rule: no tile to fit
                 roi_tile_usable=1e9)
    config = dict(config, model=model, precision="float32",
                  batch_per_chip=batch_per_chip,
                  overrides=[o for o in config["overrides"]
                             if not o.startswith(("TRAIN.PRECISION",
                                                  "TRAIN.BATCH_SIZE",
                                                  "PREPROC.MAX_SIZE"))]
                  + smoke_overrides(batch_per_chip))
    workload = {"name": "smoke", "config": name, "chips": chips,
                "traffic": SMOKE_TRAFFIC, "warmup_steps": 4,
                "follow_steps": 3, "trace_steps": 3,
                "limits": dict(SMOKE_LIMITS if limits is None else limits)}
    return harness.Cell(name="smoke", chips=chips, config=config,
                        workload=workload, task=real.task,
                        end_to_end=real.end_to_end,
                        per_layer=real.per_layer)


# ------------------------------------------------- examples of the readers

# what a context holds when no example says otherwise: nothing to read
EMPTY_CONTEXT = {"images_per_sec_per_chip": 0.0, "window_s": 0.0,
                 "window_steps": 0, "traced_steps": 0}


def load_example(name, root=ROOT):
    """``benchmark/metrics/examples/<name>.json``: what the reader of
    the per-layer metric ``name`` reads (``spans``, ``trace`` with its
    ``op_seconds``, ``memory_stats``, fields of the ``context``, a
    ``peak`` row) and the ``value`` it must then return."""
    import json

    with open(os.path.join(root, "benchmark", "metrics", "examples",
                           f"{name}.json")) as f:
        return json.load(f)


def example_context(cell, names=None, root=ROOT, peak=None):
    """A ``harness.TraceContext`` for ``cell`` that holds what the
    examples of ``names`` hold (default: every per-layer metric the
    cell reports), so a manifest that has grown brings its own data.
    Spans and memory rows are put together (one that two examples share
    stays once); of a context field, a trace field or an instruction's
    seconds that two examples give, the first in ``names``' order
    stands.  ``peak`` takes the place of the examples' own rows.  With
    no names the context is empty: every reader returns None from it."""
    from benchmark import harness, trace_reduce

    if names is None:
        names = [m["name"] for m in cell.per_layer]
    fields = {"images_per_step": cell.hyper["global_batch"],
              "feature_itemsize": cell.feature_itemsize}
    trace, op_seconds, spans, memory = {}, {}, [], []
    peaks = {} if peak is None else dict(peak)
    for name in names:
        example = load_example(name, root)
        for key, value in example.get("context", {}).items():
            fields.setdefault(key, value)
        found = dict(example.get("trace", {}))
        for instruction, seconds in found.pop("op_seconds", {}).items():
            op_seconds.setdefault(instruction, seconds)
        for key, value in found.items():
            trace.setdefault(key, value)
        spans += [s for s in example.get("spans", []) if s not in spans]
        memory += [m for m in example.get("memory_stats", [])
                   if m not in memory]
        if peak is None:
            for key, value in example.get("peak", {}).items():
                peaks.setdefault(key, value)
    summary = None
    if trace or op_seconds:
        summary = trace_reduce.TraceSummary(**trace, op_seconds=op_seconds)
    return harness.TraceContext(
        **dict(EMPTY_CONTEXT, **fields), spec=cell.spec, task=cell.task,
        chips=cell.chips, peak=peaks, spans=spans, trace=summary,
        memory_stats=memory)
