"""The perf-evidence machinery itself: bench.py's exit status and
diagnostic line, the HBM-OOM → remat retry, the rung ladder, and the
rule that nothing in the repo edits ``LIBTPU_INIT_ARGS`` or starts a
child process that would need the chip.
"""

import os

import pytest

import bench as bench_mod
from eksml_tpu.parallel import collectives


def test_main_emits_diagnostic_json_on_failure(monkeypatch, capsys):
    """Any failure inside run() must still land one parseable JSON
    line (the driver records stdout; a stack trace is not evidence)
    AND a non-zero exit status."""
    import json

    monkeypatch.setattr(bench_mod, "run",
                        lambda args, diag: (_ for _ in ()).throw(
                            RuntimeError("boom")))
    rc = bench_mod.main(["--single", "--steps", "1"])
    line = capsys.readouterr().out.strip().splitlines()[-1]
    diag = json.loads(line)
    assert rc != 0  # a caught failure never exits 0
    assert diag["status"] == "error"
    assert diag["value"] == 0.0
    assert "boom" in diag["error"]
    assert "last_good" not in diag  # no stale number rides along


def test_main_retries_hbm_oom_with_remat(monkeypatch, capsys):
    """An XLA 'Ran out of memory in memory space hbm' compile failure
    is an operating-point problem — bench reruns once with
    TRAIN.REMAT=True, RECORDS that it did (``remat_fallback``), and
    still emits exactly ONE JSON line."""
    import json

    calls = []

    def fake_run(args, diag):
        calls.append(args.remat)
        if not args.remat:
            raise RuntimeError(
                "XLA:TPU compile permanent error. Ran out of memory in "
                "memory space hbm. Used 16.22G of 15.75G hbm.")
        diag["value"] = 7.5

    monkeypatch.setattr(bench_mod, "run", fake_run)
    assert bench_mod.main(["--single", "--steps", "1"]) == 0
    out_lines = [l for l in capsys.readouterr().out.splitlines()
                 if l.strip().startswith("{")]
    assert calls == [False, True]
    assert len(out_lines) == 1, out_lines
    diag = json.loads(out_lines[0])
    assert diag["value"] == 7.5
    assert diag["remat_fallback"] is True
    assert "error" not in diag


def test_main_oom_retry_failure_reports_second_error(monkeypatch,
                                                     capsys):
    """If the remat rerun ALSO fails, the diagnostic line must carry
    the second (post-remat) error, marked with remat_fallback."""
    import json

    def fake_run(args, diag):
        if not args.remat:
            raise RuntimeError("Ran out of memory in memory space hbm.")
        raise RuntimeError("still too big even with remat")

    monkeypatch.setattr(bench_mod, "run", fake_run)
    assert bench_mod.main(["--single", "--steps", "1"]) != 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    diag = json.loads(line)
    assert diag["value"] == 0.0
    assert "still too big" in diag["error"]
    assert diag["remat_fallback"] is True


def test_grpc_allocation_failure_is_not_hbm_oom():
    """A gRPC 'RESOURCE_EXHAUSTED ... Failed to allocate request
    buffer' (a transport problem) must NOT trigger the remat retry —
    only an HBM-marked failure is an operating-point OOM."""
    transport = RuntimeError(
        "RESOURCE_EXHAUSTED: Failed to allocate request buffer")
    assert not bench_mod._is_hbm_oom(transport)
    real = RuntimeError(
        "RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm.")
    assert bench_mod._is_hbm_oom(real)
    real2 = RuntimeError("RESOURCE_EXHAUSTED: exceeded HBM capacity")
    assert bench_mod._is_hbm_oom(real2)


def test_ladder_banks_each_rung_and_promotes_headline(monkeypatch,
                                                      tmp_path, capsys):
    """Default (no --single) mode runs the cheap-first ladder: every
    rung banks its own artifact BEFORE the next is attempted, and the
    single emitted line carries the most expensive successful point."""
    import json

    monkeypatch.setattr(bench_mod, "LAST_GOOD",
                        str(tmp_path / "bench_last_good.json"))
    seen = []

    def fake_run(args, diag):
        seen.append((args.image_size, tuple(args.pad_hw or ()),
                     args.batch_size))
        diag["value"] = 10.0 * len(seen)
        diag["mfu"] = 0.1 * len(seen)
        diag["device_kind"] = "TPU v5 lite"

    monkeypatch.setattr(bench_mod, "run", fake_run)
    assert bench_mod.main(["--steps", "1"]) == 0
    assert seen == [(256, (), 1), (512, (), 1), (1344, (832, 1344), 4),
                    (1344, (), 4), (1344, (), 8)]
    for rung in ("micro_256_b1_fwd", "512_b1", "832x1344_b4",
                 "1344_b4", "1344_b8_remat"):
        banked = json.load(open(tmp_path / f"bench_rung_{rung}.json"))
        assert banked["value"] > 0 and "banked_at" in banked
    out_lines = [l for l in capsys.readouterr().out.splitlines()
                 if l.strip().startswith("{")]
    assert len(out_lines) == 1, out_lines
    diag = json.loads(out_lines[0])
    assert diag["operating_point"] == "1344_b8_remat"
    assert diag["headline_point"] is True
    assert diag["value"] == 50.0
    assert [r["rung"] for r in diag["rungs"]] == [
        "micro_256_b1_fwd", "512_b1", "832x1344_b4", "1344_b4",
        "1344_b8_remat"]


def test_ladder_partial_failure_keeps_cheap_rung(monkeypatch,
                                                 tmp_path, capsys):
    """A ladder that fails after the cheap rung still leaves that rung
    banked and reported as the headline value — and exits non-zero,
    because a rung failed."""
    import json

    monkeypatch.setattr(bench_mod, "LAST_GOOD",
                        str(tmp_path / "bench_last_good.json"))

    def fake_run(args, diag):
        if args.pad_hw or args.image_size > 512:
            raise TimeoutError("compile refused")
        diag["value"] = 11.5
        diag["mfu"] = 0.21
        diag["device_kind"] = "TPU v5 lite"

    monkeypatch.setattr(bench_mod, "run", fake_run)
    assert bench_mod.main(["--steps", "1"]) != 0
    assert (tmp_path / "bench_rung_512_b1.json").exists()
    assert not (tmp_path / "bench_rung_1344_b4.json").exists()
    diag = json.loads(
        [l for l in capsys.readouterr().out.splitlines()
         if l.strip().startswith("{")][-1])
    assert diag["value"] == 11.5
    assert diag["operating_point"] == "512_b1"
    assert diag["headline_point"] is False
    assert diag["ladder_abort"]["rung"] == "832x1344_b4"
    assert "error" not in diag  # the banked rung's numbers stand


def test_ladder_cpu_run_does_not_clobber_tpu_rung_banks(monkeypatch,
                                                        tmp_path,
                                                        capsys):
    """A run whose device field says CPU must leave banked TPU rung
    artifacts untouched (same hardware-only rule as
    bench_last_good.json)."""
    import json

    monkeypatch.setattr(bench_mod, "LAST_GOOD",
                        str(tmp_path / "bench_last_good.json"))
    tpu_rec = {"value": 99.0, "device_kind": "TPU v5 lite"}
    (tmp_path / "bench_rung_512_b1.json").write_text(
        json.dumps(tpu_rec))

    def fake_run(args, diag):
        diag["value"] = 1.0
        diag["device_kind"] = "cpu"

    monkeypatch.setattr(bench_mod, "run", fake_run)
    bench_mod.main(["--steps", "1"])
    banked = json.loads(
        (tmp_path / "bench_rung_512_b1.json").read_text())
    assert banked["value"] == 99.0  # untouched
    capsys.readouterr()


def test_ladder_carries_remat_to_larger_rungs(monkeypatch, tmp_path,
                                              capsys):
    """Once a rung needed the remat fallback, every larger rung must
    start WITH remat instead of re-paying a doomed non-remat compile
    (each compile is minutes)."""
    calls = []

    def fake_run(args, diag):
        calls.append((args.image_size, bool(args.pad_hw), args.remat))
        if args.pad_hw and not args.remat:  # 832x1344 OOMs w/o remat
            raise RuntimeError("Ran out of memory in memory space hbm")
        diag["value"] = 5.0
        diag["device_kind"] = "TPU v5 lite"

    monkeypatch.setattr(bench_mod, "LAST_GOOD",
                        str(tmp_path / "bench_last_good.json"))
    monkeypatch.setattr(bench_mod, "run", fake_run)
    bench_mod.main(["--steps", "1"])
    assert calls == [
        (256, False, False),    # micro rung: no remat needed
        (512, False, False),    # cheap rung: no remat needed
        (1344, True, False),    # bucket rung: OOM ...
        (1344, True, True),     # ... retried with remat
        (1344, False, True),    # headline STARTS with remat
        (1344, False, True),    # b8 memory-plan rung forces remat
    ]
    capsys.readouterr()


def test_ladder_rung_subset_env(monkeypatch, tmp_path, capsys):
    """EKSML_BENCH_RUNGS subsets the ladder; an unknown name fails
    loudly instead of silently benching nothing."""
    import json

    monkeypatch.setattr(bench_mod, "LAST_GOOD",
                        str(tmp_path / "bench_last_good.json"))
    seen = []

    def fake_run(args, diag):
        seen.append(args.batch_size)
        diag["value"] = 1.0
        diag["device_kind"] = "TPU v5 lite"

    monkeypatch.setattr(bench_mod, "run", fake_run)
    monkeypatch.setenv("EKSML_BENCH_RUNGS", "512_b1")
    bench_mod.main(["--steps", "1"])
    assert seen == [1]  # only the cheap rung ran
    diag = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert diag["operating_point"] == "512_b1"

    # a typo must fail loudly even when OTHER names matched — silently
    # dropping the headline rung would mask a mis-set env
    for bad in ("nope", "512_b1, 1344b4"):
        monkeypatch.setenv("EKSML_BENCH_RUNGS", bad)
        assert bench_mod.main(["--steps", "1"]) != 0
        diag = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1])
        assert "unknown rung" in diag["error"], diag
    # whitespace-padded VALID names still work
    seen.clear()
    monkeypatch.setenv("EKSML_BENCH_RUNGS", " 512_b1 , 1344_b4 ")
    bench_mod.main(["--steps", "1"])
    assert seen == [1, 4]
    capsys.readouterr()


def test_point_flags_require_single():
    """Explicit operating-point flags without --single must fail fast
    (the ladder would silently override them — benching a point the
    caller did not ask for)."""
    import pytest as _pytest

    for argv in (["--image-size", "512"], ["--batch-size", "1"],
                 ["--pad-hw", "832", "1344"], ["--profile", "4"]):
        with _pytest.raises(SystemExit):
            bench_mod.main(argv)


def test_ladder_total_failure_surfaces_error(monkeypatch, tmp_path,
                                             capsys):
    import json

    monkeypatch.setattr(bench_mod, "LAST_GOOD",
                        str(tmp_path / "bench_last_good.json"))
    monkeypatch.setattr(bench_mod, "run",
                        lambda args, diag: (_ for _ in ()).throw(
                            RuntimeError("no device")))
    rc = bench_mod.main(["--steps", "1"])
    diag = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc != 0
    assert diag["status"] == "error"
    assert diag["value"] == 0.0
    assert "no device" in diag["error"]
    assert "last_good" not in diag
    assert diag["ladder_abort"]["rung"] == "micro_256_b1_fwd"


def _repo_sources():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = [os.path.join(root, "bench.py"),
             os.path.join(root, "__graft_entry__.py")]
    for base in ("eksml_tpu", "tools"):
        for d, _, files in os.walk(os.path.join(root, base)):
            paths += [os.path.join(d, f) for f in files
                      if f.endswith(".py")]
    return paths


def test_collective_flag_absent_from_libtpu_init_args():
    """libtpu 0.0.34 has no ``xla_tpu_all_reduce_combine_threshold_
    bytes`` and EXITS on the unknown flag, and it reads
    ``LIBTPU_INIT_ARGS`` once, at backend init.  So the flag is not set
    at all: no library, tool or bench source writes that
    variable."""
    for path in _repo_sources():
        with open(path) as f:
            src = f.read()
        assert 'environ["LIBTPU_INIT_ARGS"] =' not in src, path
    assert not hasattr(collectives, "set_xla_collective_flags")


def test_collective_flag_layer_starts_no_child():
    """A process that has initialised JAX owns the chip; a child that
    needs it fails or hangs.  The collective layer (and the kernel
    gate) therefore never start one."""
    import inspect

    from eksml_tpu.ops.pallas import roi_align_kernel

    for mod in (collectives, roi_align_kernel):
        src = inspect.getsource(mod)
        assert "subprocess" not in src, mod.__name__
        assert "threading" not in src, mod.__name__


def test_collective_flag_operator_value_survives_trainer_init(
        monkeypatch, fresh_config, tmp_path):
    """An operator-set LIBTPU_INIT_ARGS (the charts' pod env — placed
    before the first backend call, where it can take effect) passes
    through ``Trainer.__init__`` untouched."""
    from eksml_tpu.config import SMOKE_OVERRIDES, finalize_configs
    from eksml_tpu.train import Trainer

    monkeypatch.setenv("LIBTPU_INIT_ARGS", "--xla_keep_me=1")
    fresh_config.update_args(list(SMOKE_OVERRIDES)
                             + ["TPU.MESH_SHAPE=(1,1)"])
    cfg = finalize_configs(is_training=True)
    trainer = Trainer(cfg, str(tmp_path), write_metrics=False)
    try:
        assert os.environ["LIBTPU_INIT_ARGS"] == "--xla_keep_me=1"
    finally:
        trainer.ckpt.close()


def test_unknown_device_kind_is_an_error_not_a_default_peak(capsys):
    """No chip (the suite runs on CPU): bench.py must refuse before it
    builds anything — no v5e peak assumed — and exit non-zero."""
    import json

    with pytest.raises(ValueError, match="cpu"):
        bench_mod.peak_flops_for("cpu")
    assert not hasattr(bench_mod, "DEFAULT_PEAK")
    rc = bench_mod.main(["--single", "--steps", "1"])
    diag = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc != 0 and diag["status"] == "error"
    assert "device kind 'cpu'" in diag["error"]
    assert diag["device_kind"] == "cpu" and "mfu" not in diag


def test_micro_rung_is_forward_only_and_tiny(monkeypatch, tmp_path,
                                             capsys):
    """Rung 0 must run forward-only with ~3 steps (the cheapest
    compile on the ladder), carry a distinct metric name, and never
    ratio itself against the train-throughput baseline anchor."""
    import json

    monkeypatch.setattr(bench_mod, "LAST_GOOD",
                        str(tmp_path / "bench_last_good.json"))
    seen = []

    def fake_run(args, diag):
        if not getattr(args, "forward_only", False):
            raise TimeoutError("failed after the micro rung")
        seen.append((args.image_size, args.forward_only,
                     args.steps, args.warmup))
        diag["value"] = 7.0
        diag["device_kind"] = "TPU v5 lite"

    monkeypatch.setattr(bench_mod, "run", fake_run)
    bench_mod.main(["--steps", "20"])
    assert seen == [(256, True, 3, 1)]
    diag = json.loads(
        [l for l in capsys.readouterr().out.splitlines()
         if l.strip().startswith("{")][-1])
    # the ladder failed AFTER the micro rung banked
    banked = json.load(
        open(tmp_path / "bench_rung_micro_256_b1_fwd.json"))
    assert banked["value"] == 7.0
    assert banked["metric"] == "maskrcnn_r50fpn_fwd_microbench"
    assert banked["forward_only"] is True
    assert diag["value"] == 7.0
    assert diag["operating_point"] == "micro_256_b1_fwd"
