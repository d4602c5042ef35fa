"""One run of one cell: ``Trainer.fit`` over the task's loader on the
benchmark's seeded traffic, warm-up fit as set-up, a second fit on the
same ``Trainer`` as the measured window, then the comparison with the
plain reference.  ``harness.py`` and the task modules are the only
files of the benchmark that import the program.

Everything that belongs to one cell, configuration, task or per-layer
metric is found by name from ``BENCHMARK.json``:
``configs/<config>.json``, ``workloads/<cell>.json``,
``mixes/<traffic>.json``, ``metrics/<metric>.py`` and, by the ``"task"``
key of the configuration's file, ``tasks/<task>.py``.
"""

from __future__ import annotations

import contextlib
import gc
import glob
import importlib
import json
import math
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field

from benchmark import compare, traffic, trace_reduce

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
BIG_STEPS = 10 ** 9       # fit's total_steps: never reached, so no
#                           final-step checkpoint; the feed ends the fit
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoDevice(RuntimeError):
    """No accelerator, too few chips, or a device kind with no peaks."""


# ------------------------------------------------------------------ cells


@dataclass
class Cell:
    name: str
    chips: int
    config: dict           # configs/<config>.json
    workload: dict         # workloads/<cell>.json
    task: object           # tasks/<task>.py, the module
    end_to_end: list
    per_layer: list

    @property
    def spec(self):
        return self.config["model"]

    @property
    def feature_itemsize(self):
        return 2 if self.config["precision"] == "bfloat16" else 4

    @property
    def hyper(self):
        return dict(self.config["optimizer"],
                    global_batch=self.config["batch_per_chip"] * self.chips)


def _reports(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def _module(package: str, name: str):
    return importlib.import_module(
        f"benchmark.{package}." + name.replace(".", "_").replace("-", "_"))


def load_task(config: dict, path: str):
    """The module ``tasks/<task>.py`` that the configuration's file (at
    ``path``) names under ``"task"``.  There is no default task."""
    if "task" not in config:
        raise KeyError(f"{path}: no \"task\" key (benchmark/tasks/<task>.py)")
    try:
        return _module("tasks", config["task"])
    except ModuleNotFoundError as e:
        if not (e.name or "").startswith("benchmark.tasks."):
            raise
        raise KeyError(f"{path}: task {config['task']!r} has no module "
                       f"benchmark/tasks/{config['task']}.py") from e


def load_cell(root: str, name: str, manifest: dict | None = None) -> Cell:
    if manifest is None:
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            manifest = json.load(f)
    entry = next((w for w in manifest["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == entry["config"])
    config_path = os.path.join(root, cfg_entry["file"])
    with open(config_path) as f:
        config = json.load(f)
    bench = os.path.join(root, manifest["paths"][0])
    with open(os.path.join(bench, "workloads", f"{name}.json")) as f:
        workload = json.load(f)
    with open(os.path.join(bench, "mixes",
                           f"{entry['traffic']}.json")) as f:
        workload["traffic"] = json.load(f)     # the mix's parameters
    return Cell(
        name=name, chips=int(entry["chips"]), config=config,
        workload=workload, task=load_task(config, config_path),
        end_to_end=[m for m in manifest["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in manifest["per_layer"] if _reports(m, name)])


def load_peaks(device_kind: str) -> dict:
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        kinds = json.load(f)["device_kinds"]
    if device_kind not in kinds:
        raise NoDevice(f"device kind {device_kind!r} is not in "
                       "benchmark/peaks.json")
    return kinds[device_kind]


def check_device(chips: int) -> tuple:
    """(devices used, peaks) or NoDevice: a TPU with at least the
    cell's chips, of a kind the peaks table knows.  Nothing falls back
    to the CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"no TPU: jax sees platform "
                       f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chip(s), jax sees "
                       f"{len(devices)}")
    return devices[:chips], load_peaks(devices[0].device_kind)


# ---------------------------------------------------------------- program


def program_config(cell: Cell, seed: int, logdir: str, trace: bool):
    """The global config at its defaults, then the configuration's
    overrides, then what a run fixes (seed, log dir, chips, mesh,
    telemetry port 0).  A traced run also switches the program's span
    ring on; nothing else differs."""
    from eksml_tpu import config as config_mod

    global _DEFAULTS
    cfg = config_mod.config
    if _DEFAULTS is None:       # the program's config is one global tree
        _DEFAULTS = cfg.to_dict()
    cfg.freeze(False)
    cfg.from_dict(_DEFAULTS)
    overrides = list(cell.config["overrides"]) + [
        f"TRAIN.LOGDIR={logdir}",
        f"TRAIN.SEED={traffic.effective_seed(seed)}",
        f"TRAIN.NUM_CHIPS={cell.chips}",
        f"TPU.MESH_SHAPE=({cell.chips},1)",
        "TELEMETRY.PORT=0",
    ]
    if trace:
        overrides += ["TELEMETRY.TRACING.ENABLED=True",
                      "TELEMETRY.TRACING.ANOMALY_TRIGGER=False"]
    cfg.update_args(overrides)
    return config_mod.finalize_configs(is_training=True)


_DEFAULTS = None


def first_batches(cell: Cell, seed: int, n: int):
    """The first ``n`` host batches the cell's loader yields for
    ``seed`` (no Trainer): what the control and the fault readings
    feed the reference."""
    import numpy as np

    logdir = tempfile.mkdtemp(prefix="bench_feed_")
    try:
        cfg = program_config(cell, seed, logdir, False)
        loader, _ = cell.task.build_loader(cell, cfg, seed, logdir)
        gen = loader.batches(n)
        try:
            return [{k: np.array(v) for k, v in b.items()} for b in gen]
        finally:
            gen.close()
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


class CompileCounter:
    """Counts backend compilations (jax.monitoring duration events)."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event == COMPILE_EVENT:
            self.count += 1


class Capture:
    """The traced run's profiler capture, around a short fit of its
    own after the measured window.  Neither the program's
    ``fit(profile_steps=)`` nor a capture started inside the window
    leaves the step loop alone: with the Python tracer on (the
    default) the first traced step waited 0.75 s for the host, and
    ``start_trace`` itself holds the host for 2.6 s (my chip runs,
    PR 24).  So the profiler starts while the device is drained and
    nothing is timed, a fit of ``trace_steps`` steps runs, and the
    profiler stops once the device has drained again; the reduction's
    window is first whole step to last, which leaves the fit's ramp-up
    out.

    The device's planes alone: the host tracer is off too (PR 32).
    XLA's host-side re-tiling of a batch on its way to the chip
    (``pjrt-tpu-tasks``: ``XlaLinearize`` of the uint8
    ``[4, 1344, 1344, 3]`` pixels) records one host event for every
    inner block it moves, at any host tracer level above 0: ~17 MB of
    events a batch (a 366 MB trace for 20 steps), and some traced
    stretches had two such transfers in flight take 3.0-3.4 s each, the
    chip idle for 2.7-3.0 s behind them, where the plain window's
    never starves (my chip runs, PR 32).  What is read here (busy time, the
    window, seconds by instruction) is the device's; with the host
    untraced an idle gap is labelled by where it lies among the step's
    executions (``trace_reduce.summarize``), not by what the host did."""

    def __init__(self, logdir: str):
        import jax

        self.dir = os.path.join(logdir, "bench_profile")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)

    def stop(self):
        """Path of the ``.xplane.pb``, or None."""
        import jax

        jax.profiler.stop_trace()
        files = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        return files[-1] if files else None


class StepTap:
    """Observes (does not alter) the trainer's step callable during the
    warm-up fit: the parameters before step 1, each followed step's
    loss, what the optimizer holds of the first gradient after step 1
    (``first_moment(opt_state)``, the task's) and the parameters after
    the last followed step, reduced to per-leaf norms.  Removed before
    the window."""

    def __init__(self, trainer, follow: int, first_moment):
        self.trainer, self.follow = trainer, follow
        self.first_moment = first_moment
        self.inner = trainer._step_fn_with_prediction
        self.calls = 0
        self.terms = []
        self.p0 = None
        self.first_trace_norm = None
        self.delta_norm = None
        trainer._step_fn_with_prediction = self

    def remove(self):
        del self.trainer._step_fn_with_prediction

    @staticmethod
    def _host(tree):
        import jax
        import numpy as np

        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        return {"/".join(str(getattr(p, "key", p)) for p in path):
                np.asarray(leaf, np.float32) for path, leaf in flat}

    def __call__(self, jit_step, state, batch):
        import numpy as np

        dispatch = self.inner(jit_step, state, batch)

        def norm(x):
            return float(np.sqrt(np.sum(np.square(x.astype(np.float64)))))

        def tapped(s, b):
            i = self.calls
            self.calls += 1
            if i >= self.follow:
                return dispatch(s, b)
            if i == 0:
                self.p0 = self._host(s.params)   # before donation
            s2, metrics = dispatch(s, b)
            self.terms.append({k: v for k, v in metrics.items()
                               if k.endswith("_loss")})
            if i == 0:
                self.first_trace_norm = {
                    k: norm(v) for k, v in self._host(
                        self.first_moment(s2.opt_state)).items()}
            if i == self.follow - 1:
                pn = self._host(s2.params)
                self.delta_norm = {k: norm(pn[k] - self.p0[k])
                                   for k in self.p0}
                self.p0 = None
            return s2, metrics

        return tapped

    def readings(self):
        terms = [{k: float(v) for k, v in t.items()} for t in self.terms]
        return {"loss": [t["total_loss"] for t in terms], "terms": terms,
                "first_trace_norm": self.first_trace_norm,
                "delta_norm": self.delta_norm}


# ------------------------------------------------------------ trace context


@dataclass
class TraceContext:
    """What a per-layer metric's reader may read."""
    spec: dict
    task: object                     # the cell's task module
    chips: int
    images_per_step: int
    images_per_sec_per_chip: float
    window_s: float
    window_steps: int
    traced_steps: int
    feature_itemsize: int
    peak: dict
    spans: list = field(default_factory=list)
    trace: object = None             # trace_reduce.TraceSummary
    memory_stats: list = field(default_factory=list)


def read_per_layer(cell: Cell, ctx: TraceContext) -> dict:
    out = {}
    for m in cell.per_layer:
        value = _module("metrics", m["name"]).read(ctx)
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# ---------------------------------------------------------------- the run


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devices=None, peaks=None,
             on_trainer=None) -> dict:
    """Drive one run; returns the result line as a dict.  ``devices``
    and ``peaks`` None means: look for the chip (and raise NoDevice
    without one); tests pass CPU devices and a peaks row.
    ``on_trainer(trainer)`` lets a test break the timed path."""
    import jax
    import numpy as np

    from eksml_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    if devices is None:
        devices, peaks = check_device(cell.chips)
    from eksml_tpu.train import Trainer

    phases = {"imports_device": time.perf_counter() - t_start}
    compiles = CompileCounter()
    logdir = tempfile.mkdtemp(prefix="bench_run_")
    gen = None
    trainer = None
    try:
        cfg = program_config(cell, seed, logdir, trace)
        wrong = cell.task.spec_mismatches(cfg, cell.spec, cell.hyper)
        if wrong:
            raise RuntimeError("configuration file and program disagree: "
                               + "; ".join(wrong))
        trainer = Trainer(cfg, logdir)
        if on_trainer is not None:
            on_trainer(trainer)
        loader, rows_per_step = cell.task.build_loader(
            cell, cfg, seed, logdir)
        gen = loader.batches(None)
        phases["records_trainer_loader"] = time.perf_counter() - t_start

        # ---- set-up: the warm-up fit compiles the cell's one shape and
        # takes the first steps, which the reference follows
        follow = int(cell.workload["follow_steps"])
        warm = max(int(cell.workload["warmup_steps"]), follow)
        followed = []

        def remember(batch):
            if len(followed) < follow:
                followed.append({k: np.array(v) for k, v in batch.items()})

        tap = StepTap(trainer, follow, cell.task.first_moment)
        state = trainer.fit(
            traffic.feed(gen, count=warm, on_batch=remember), BIG_STEPS,
            data_health=loader.health)
        tap.remove()
        jax.block_until_ready(state)
        program = tap.readings()
        setup_s = time.perf_counter() - t_start

        # ---- the window: a second fit on the same Trainer
        compiles_before = compiles.count
        step0 = int(np.asarray(state.step))
        spans_before = (len(trainer.tracer.snapshot())
                        if trainer.tracer is not None else 0)
        t0 = time.perf_counter()
        state = trainer.fit(
            traffic.feed(gen, deadline=t0 + seconds,
                         clock=time.perf_counter),
            BIG_STEPS, start_step=step0, state=state,
            data_health=loader.health)
        jax.block_until_ready(state)
        window_s = time.perf_counter() - t0
        steps = int(np.asarray(state.step)) - step0
        window_compiles = compiles.count - compiles_before
        spans = (trainer.tracer.snapshot()[spans_before:]
                 if trainer.tracer is not None else [])
        trace_file = None
        if trace:
            capture = Capture(logdir)
            state = trainer.fit(
                traffic.feed(gen, count=int(cell.workload["trace_steps"])),
                BIG_STEPS, start_step=step0 + steps, state=state,
                data_health=loader.health)
            jax.block_until_ready(state)
            trace_file = capture.stop()
        gen.close()
        gen = None

        ips_chip = steps * rows_per_step / window_s / cell.chips
        mem = [d.memory_stats() or {} for d in devices]
        peak_bytes = max([max(s.get("peak_bytes_in_use", 0),
                              s.get("peak_bytes_reserved", 0))
                          for s in mem] or [0])
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": int(peak_bytes)}
        metrics = {}
        breakdown = None
        if trace:
            hlo = trainer.aot_step[1].as_text()
            scopes, custom = trace_reduce.hlo_instructions(hlo)
            summary = (trace_reduce.summarize_file(
                trace_file, custom, trace_reduce.hlo_module_name(hlo))
                if trace_file else None)
            ctx = TraceContext(
                spec=cell.spec, task=cell.task, chips=cell.chips,
                images_per_step=rows_per_step,
                images_per_sec_per_chip=ips_chip, window_s=window_s,
                window_steps=steps,
                traced_steps=summary.steps if summary else 0,
                feature_itemsize=cell.feature_itemsize,
                peak=peaks, spans=spans, trace=summary, memory_stats=mem)
            metrics = read_per_layer(cell, ctx)
            if summary is not None and summary.window_s:
                device["busy_s"] = summary.busy_s
                device["window_s"] = summary.window_s
                breakdown = {"device_ops": summary.top_ops(10, scopes),
                             "idle_gaps": summary.idle_gaps[:10]}
        else:
            values = {"images_per_sec_per_chip": ips_chip,
                      "setup_s": setup_s}
            metrics = {m["name"]: {"value": values[m["name"]],
                                   "unit": m["unit"]}
                       for m in cell.end_to_end if m["name"] in values}

        # ---- free the program, then the reference follows the steps
        trainer.ckpt.close()
        trainer = None
        del state, tap, loader
        gc.collect()
        jax.clear_caches()
        t_ref = time.perf_counter()
        reference = cell.task.reference_steps(
            cell.spec, cell.hyper, traffic.effective_seed(seed), followed)
        values, where = compare.numbers(program, reference,
                                        cell.task.extra_numbers)
        values["compiles_in_window"] = float(window_compiles)
        limits = dict(cell.workload["limits"], compiles_in_window=0.0)
        correct, rows = compare.judge(values, limits)
        correct = bool(correct and steps > 0)
        reference_s = time.perf_counter() - t_ref
    finally:
        if gen is not None:
            gen.close()
        if trainer is not None:
            with contextlib.suppress(Exception):
                trainer.ckpt.close()
        shutil.rmtree(logdir, ignore_errors=True)

    compared = {name: {"value": v, "limit": lim} for name, v, lim in rows}
    for name, v, lim in rows:
        leaf = f" (worst leaf {where[name]})" if where.get(name) else ""
        print(f"compared {name} = {v:.6g}  limit {lim:.6g}  "
              f"{'ok' if v <= lim else 'OVER'}{leaf}", file=sys.stderr)
    print(f"correct = {correct}; window {steps} steps in {window_s:.3f} s, "
          f"{window_compiles} compile(s) in it; reference "
          f"{reference_s:.1f} s", file=sys.stderr)
    result = {"correct": correct, "attempted": steps, "failed": 0,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window"] = {"steps": steps, "seconds": window_s,
                        "setup_s": setup_s, "setup_phases_s": phases,
                        "reference_s": reference_s,
                        "program_loss": program["loss"],
                        "reference_loss": reference["loss"],
                        "numbers": values, "worst_leaf": where}
    result["compared"] = compared
    return result
