"""The reduction from trace to numbers: hand-checked on hand-made
events, and on a small trace recorded on the chip
(``benchmark/fixtures/tiny.xplane.pb``: three calls of a jitted step
holding one Pallas custom call, with host sleeps between them)."""

import os

import pytest

import bench_smoke
from benchmark import harness, trace_reduce
from benchmark.metrics import (device_idle_pct, device_peak_hbm_gb,
                               input_wait_ms, roi_align_kernels_roofline_pct,
                               step_mfu_pct)

FIXTURES = os.path.join(bench_smoke.ROOT, "benchmark", "fixtures")

HLO = '''
HloModule jit_step
fused_computation {
  %p = f32[8]{0} parameter(0)
  ROOT %t = f32[8]{0} tanh(%p), metadata={op_name="jit(step)/tanh"}
}
ENTRY main {
  %x = f32[8]{0} parameter(0)
  %custom-call.3 = f32[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/roi_align/pallas_call"}
  %custom-call.9 = f32[8]{0} custom-call(%x), custom_call_target="Sharding"
  ROOT %fusion.1 = f32[8]{0} fusion(%custom-call.3), kind=kLoop, calls=fused_computation, metadata={op_name="jit(step)/mask_head/tanh"}
}
'''


def test_hlo_instructions_finds_kernels_and_scopes():
    scopes, custom = trace_reduce.hlo_instructions(HLO)
    assert custom == {"custom-call.3"}
    assert scopes["fusion.1"] == "jit(step)/mask_head/tanh"
    assert scopes["custom-call.3"].endswith("roi_align/pallas_call")


def test_union_and_gaps_by_hand():
    ivals = [(0, 10), (5, 20), (30, 40), (32, 35)]
    assert trace_reduce.union_seconds(ivals) == pytest.approx(30e-9)
    assert trace_reduce.gaps(ivals, 0, 50) == [(20, 30), (40, 50)]
    assert trace_reduce.gaps([], 0, 5) == [(0, 5)]


def test_summarize_by_hand():
    # device 0: busy 0-100, 150-250 (two ops overlap 200-250), 400-500 us
    us = 1000
    d0 = [("fusion.1", 0, 100 * us), ("custom-call.3", 150 * us, 100 * us),
          ("fusion.1", 200 * us, 50 * us), ("fusion.2", 400 * us, 100 * us)]
    d1 = [("fusion.1", 0, 500 * us)]
    feed = [(255 * us, 395 * us)]
    s = trace_reduce.summarize({"/device:TPU:0": d0, "/device:TPU:1": d1},
                               feed, {"custom-call.3"})
    assert s.devices == 2 and s.steps == 0
    assert s.window_s == pytest.approx(500e-6)
    assert s.busy_s == pytest.approx((300e-6 + 500e-6) / 2)
    assert s.idle_share == pytest.approx(0.2)
    assert s.custom_call_s == pytest.approx(100e-6 / 2)
    assert s.custom_call_events == 1
    assert s.op_seconds["fusion.1"] == pytest.approx((150e-6 + 500e-6) / 2)
    labels = dict((k.split(" (")[0], v) for k, v in s.idle_gaps)
    assert labels["feed waits in the loader's next"] == pytest.approx(150e-6)
    assert labels["host not in the loader's next"] == pytest.approx(50e-6)
    top = s.top_ops(2, {"fusion.1": "jit(step)/x"})
    assert top[0][0] == "fusion.1 [jit(step)/x]"


def test_idle_gaps_are_labelled_by_what_the_host_was_doing():
    us = 1000
    ops = {"d": [("a", 0, 100 * us), ("a", 3100 * us, 100 * us),
                 ("a", 3300 * us, 100 * us)]}
    host = [("python:worker loop", 0, 3400 * us),          # covers all
            ("main:device_get", 200 * us, 2900 * us),      # most specific
            ("main:short", 3210 * us, 3220 * us)]          # under half
    s = trace_reduce.summarize(ops, (), host_events=host)
    assert s.idle_gaps[0][0].startswith(
        "host not in the loader's next, host in main:device_get (")
    assert s.idle_gaps[0][1] == pytest.approx(3000e-6)
    assert s.idle_gaps[1][0].startswith(
        "host not in the loader's next, host in python:worker loop (")
    assert trace_reduce.host_label((0, 10), []) is None


def test_with_the_host_untraced_a_gap_is_labelled_by_its_place():
    """``harness.Capture`` traces the device alone (PR 32): nothing is
    said of the loader or the host; a gap lies between two executions
    of the step (the host's to fill) or inside one (the program's)."""
    us = 1000
    ops = {"d": [("a", 0, 100 * us), ("b", 130 * us, 70 * us),    # step 1
                 ("a", 3100 * us, 100 * us), ("b", 3200 * us, 100 * us),
                 ("a", 3310 * us, 90 * us)]}
    mods = {"d": [("jit_step(1)", 0, 200 * us),
                  ("jit_step(1)", 3100 * us, 200 * us),
                  ("jit_step(1)", 3310 * us, 90 * us)]}
    s = trace_reduce.summarize(ops, (), modules=mods, step_module="jit_step",
                               host_traced=False)
    assert s.steps == 3 and s.window_s == pytest.approx(3400e-6)
    assert s.idle_gaps == [
        ["between two steps' executions (longest of 2)",
         pytest.approx(2900e-6)],
        ["inside a step's execution (longest of 1)", pytest.approx(30e-6)]]
    assert not any("loader" in label for label, _ in s.idle_gaps)
    # no executions recorded: a gap is idle time and no more is said
    bare = trace_reduce.summarize(ops, (), host_traced=False)
    assert [label for label, _ in bare.idle_gaps] == [
        "idle, host not traced (longest of 3)"]
    # the same events with the host traced read as before
    s = trace_reduce.summarize(ops, [(250 * us, 3050 * us)], modules=mods,
                               step_module="jit_step")
    assert s.idle_gaps[0][0].startswith("feed waits in the loader's next")
    assert trace_reduce.place_label((5, 6), [(0, 10)]).startswith("inside")
    assert trace_reduce.place_label((5, 12), [(0, 10), (12, 20)]).startswith(
        "between")


def test_window_is_the_whole_executions_where_they_are_recorded():
    us = 1000
    ops = {"d": [("a", 0, 10 * us), ("b", 20 * us, 10 * us),
                 ("b", 40 * us, 10 * us), ("a", 60 * us, 5 * us)]}
    mods = {"d": [("jit_step(1)", 20 * us, 10 * us),
                  ("jit_step(1)", 40 * us, 10 * us)]}
    s = trace_reduce.summarize(ops, (), {"b"}, mods)
    assert s.steps == 2 and s.window_s == pytest.approx(30e-6)
    assert s.busy_s == pytest.approx(20e-6) and "a" not in s.op_seconds
    assert s.custom_call_events == 2


def test_only_the_step_programs_executions_are_steps():
    """A log step's small program inside the stretch is no step: it
    neither counts nor sets the window."""
    us = 1000
    ops = {"d": [("a", 0, 4 * us), ("b", 20 * us, 10 * us),
                 ("b", 40 * us, 10 * us), ("a", 60 * us, 5 * us)]}
    mods = {"d": [("jit_mean(7)", 0, 4 * us),
                  ("jit__train_step(1)", 20 * us, 10 * us),
                  ("jit__train_step(1)", 40 * us, 10 * us),
                  ("jit_mean(7)", 60 * us, 5 * us)]}
    every = trace_reduce.summarize(ops, (), {"b"}, mods)
    assert every.steps == 4 and every.window_s == pytest.approx(65e-6)
    s = trace_reduce.summarize(ops, (), {"b"}, mods,
                               step_module="jit__train_step")
    assert s.steps == 2 and s.window_s == pytest.approx(30e-6)
    assert s.busy_s == pytest.approx(20e-6) and "a" not in s.op_seconds
    assert trace_reduce.hlo_module_name(
        "HloModule jit__train_step, is_scheduled=true\n") == "jit__train_step"
    assert trace_reduce.hlo_module_name(HLO) == "jit_step"


def test_fixture_recorded_on_the_chip():
    """tiny.xplane.pb, read by hand (PR 24): three executions of
    ``jit_step`` starting at 45303115, 49718578, 54117327 ns and
    lasting 5311, 5200, 5257 ns; each holds copy-start (13), the
    Pallas custom call ``step.1`` (2161, 2032, 2108), copy-done
    (3, 3, 2) and a fused matmul+tanh (3123, 3140, 3122)."""
    with open(os.path.join(FIXTURES, "tiny.hlo.txt")) as f:
        scopes, custom = trace_reduce.hlo_instructions(f.read())
    assert custom == {"step.1"}
    assert scopes["step.1"] == "jit(step)/pallas_call"
    with open(os.path.join(FIXTURES, "tiny.hlo.txt")) as f:
        module = trace_reduce.hlo_module_name(f.read())
    s = trace_reduce.summarize_file(
        os.path.join(FIXTURES, "tiny.xplane.pb"), custom, module)
    assert module == "jit_step" and s.devices == 1 and s.steps == 3
    assert s.window_s == pytest.approx((54117327 + 5257 - 45303115) / 1e9)
    assert s.busy_s == pytest.approx((5300 + 5188 + 5245) / 1e9)
    assert s.custom_call_s == pytest.approx((2161 + 2032 + 2108) / 1e9)
    assert s.custom_call_events == 3
    assert s.idle_share == pytest.approx(1 - 15733 / 8819469)
    assert s.top_ops(1, scopes)[0][0] == \
        "convolution_tanh_fusion [jit(step)/dot_general]"
    # the host slept inside the feed's annotation between the steps
    assert s.idle_gaps[0][0].startswith("feed waits in the loader's next")
    assert s.idle_gaps[0][1] == pytest.approx(4.41e-3, rel=0.01)
    assert trace_reduce.instruction_name(
        "%fusion.37 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop") == \
        "fusion.37"


FIVE = ("input_wait_ms", "step_mfu_pct", "roi_align_kernels_roofline_pct",
        "device_idle_pct", "device_peak_hbm_gb")      # PR 24's readers


def test_readers_on_hand_made_context():
    """The context is made from the examples that ship with the readers
    (``benchmark/metrics/examples/<name>.json``, put together by
    ``bench_smoke.example_context``), so a manifest that has grown
    brings what its new readers read."""
    cell = bench_smoke.smoke_cell(mask=True)
    # PR 24's five and the two of PR 25 this test names, on their own
    # examples: 3 data_wait spans (the last ends the iterator), busy
    # 1.5 of 2.0 s, 8.1 GB reserved over 1.7 in use, 10 rows/s, custom
    # calls 0.02 s in 5 traced steps, 44 completions 250 ms apart
    ctx = bench_smoke.example_context(
        cell, FIVE + ("step_ms_p50", "batch_build_ms"),
        peak=bench_smoke.CPU_PEAK)
    assert device_idle_pct.read(ctx) == pytest.approx(25.0)
    assert input_wait_ms.read(ctx) == pytest.approx(3.0)
    assert device_peak_hbm_gb.read(ctx) == pytest.approx(8.1)
    from benchmark import flops
    ops = flops.train_ops_per_image(cell.spec, 128, 128)
    assert cell.task.train_ops_per_row(cell.spec) == ops
    assert step_mfu_pct.read(ctx) == pytest.approx(100 * ops * 10 / 1e12)
    need, by = roi_align_kernels_roofline_pct.bound_seconds(ctx)
    assert by["ops"] == 0.0 and need == pytest.approx(by["bytes"])
    assert roi_align_kernels_roofline_pct.read(ctx) == pytest.approx(
        100 * need * 5 / 0.02)
    out = harness.read_per_layer(cell, ctx)
    assert out["step_ms_p50"]["value"] == pytest.approx(250.0)
    assert out["batch_build_ms"]["value"] == pytest.approx(30.0)
    # every metric the cell reports, on the examples of all of them
    every = bench_smoke.example_context(cell, peak=bench_smoke.CPU_PEAK)
    out = harness.read_per_layer(cell, every)
    assert set(out) == {m["name"] for m in cell.per_layer}
    assert len(out) >= 11
    # nothing traced: the trace's readers return nothing, never 0
    ctx.trace = None
    assert device_idle_pct.read(ctx) is None
    assert roi_align_kernels_roofline_pct.read(ctx) is None
