"""Training entry point: SPMD data-parallel Mask-RCNN on a TPU mesh.

Parity target: the command the reference charts render —
``mpirun … python3 train.py --logdir <dir> --config KEY=VALUE …``
(charts/maskrcnn/templates/maskrcnn.yaml:47-72, run.sh:33-45) — with
the Horovod/NCCL machinery replaced by the mesh (SURVEY.md §3.2):

  reference                          here
  ---------                          ----
  mpirun spawns 1 proc/GPU           JobSet runs 1 proc/host, SPMD
  hvd.init() + NCCL communicator     jax.distributed.initialize + Mesh
  sess.run(train_op) per step        one jitted train_step, donated state
  Horovod fused ring allreduce       XLA-inserted allreduce (batch
                                     sharded on 'data', params replicated)
  TF model-<step> ckpts on EFS       Orbax CheckpointManager + auto-resume
  TB summaries to logdir             MetricWriter (TB events + JSONL)
  periodic COCO eval (rank 0)        eval hook on coordinator

Usage (single host)::

    python -m eksml_tpu.train --logdir /tmp/run --synthetic \
        --config TRAIN.STEPS_PER_EPOCH=20 TRAIN.MAX_EPOCHS=1
"""

from __future__ import annotations

import argparse
import logging
import math
import os
import sys
import time
from functools import partial
from typing import Any, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct

from eksml_tpu.config import config as global_config
from eksml_tpu.config import config_from_env, finalize_configs
from eksml_tpu import models
from eksml_tpu.parallel import (build_mesh, current_topology,
                                initialize_from_env,
                                replicated_sharding, validate_topology,
                                warm_mesh_collectives)
from eksml_tpu.parallel.sharding import (ShardingPlan, plan_mesh,
                                         publish_state_byte_gauges)
from eksml_tpu.resilience import (HangWatchdog, PreemptedError,
                                  PreemptionHandler)
from eksml_tpu.resilience.sentinel import ROLLBACK, DivergenceSentinel
from eksml_tpu import telemetry
from eksml_tpu.utils import CheckpointManager, MetricWriter

log = logging.getLogger("eksml_tpu.train")


class TrainState(struct.PyTreeNode):
    step: jnp.ndarray
    params: Any
    opt_state: Any
    rng: jax.Array


def lr_schedule(cfg) -> optax.Schedule:
    """Warmup + piecewise-constant decay.

    Reproduces the reference semantics: linear warmup then ×0.1 drops at
    TRAIN.LR_SCHEDULE boundaries, with the base LR linearly scaled by
    global batch.  Boundary numbers follow the TensorPack convention the
    charts use: steps *at global batch 8*, rescaled here to actual
    steps — this is what makes values.yaml:15's [240000,320000,360000]
    @16 GPUs and run.sh:42's [120000,160000,180000] @8 GPUs land on the
    same image counts.
    """
    global_batch = cfg.TRAIN.NUM_CHIPS * cfg.TRAIN.BATCH_SIZE_PER_CHIP
    base = cfg.TRAIN.BASE_LR * global_batch / 8.0
    # At large global batch two schedule entries can rescale onto the
    # same step; accumulate the ×0.1 factors so no decay is dropped.
    boundaries: Dict[int, float] = {}
    for s in cfg.TRAIN.LR_SCHEDULE:
        b = max(1, int(s * 8 / global_batch))
        boundaries[b] = boundaries.get(b, 1.0) * 0.1
    main = optax.piecewise_constant_schedule(base, boundaries)
    warm = cfg.TRAIN.WARMUP_STEPS
    if warm <= 0:
        return main
    init = base * cfg.TRAIN.WARMUP_INIT_FACTOR

    def sched(step):
        w = init + (base - init) * jnp.minimum(step, warm) / warm
        return jnp.where(step < warm, w, main(step))

    return sched


def make_optimizer(cfg):
    """clip -> [sgd: decay, momentum | adamw: Adam moments, decoupled
    decay] -> schedule.  What decays is the model's own answer
    (``models.decay_mask``)."""
    sched = lr_schedule(cfg)
    chain = []
    if cfg.TRAIN.GRADIENT_CLIP > 0:
        # reference optimized chart: TRAIN.GRADIENT_CLIP=0.36
        # (charts/maskrcnn-optimized/values.yaml:32)
        chain.append(optax.clip_by_global_norm(cfg.TRAIN.GRADIENT_CLIP))
    decay = []
    if cfg.TRAIN.WEIGHT_DECAY > 0:
        decay.append(optax.add_decayed_weights(
            cfg.TRAIN.WEIGHT_DECAY, mask=models.decay_mask(cfg)))
    if cfg.TRAIN.OPTIMIZER == "adamw":
        chain += [optax.scale_by_adam(b1=cfg.TRAIN.ADAM_B1,
                                      b2=cfg.TRAIN.ADAM_B2,
                                      eps=cfg.TRAIN.ADAM_EPS),
                  *decay, optax.scale_by_learning_rate(sched)]
    else:
        chain += [*decay, optax.sgd(sched, momentum=cfg.TRAIN.MOMENTUM)]
    return optax.chain(*chain), sched


def _knobs_with_fallback(node, defaults: Dict[str, Any]) -> Dict[str, Any]:
    """Config-node values over canonical defaults — now the shared
    ``knobs_with_defaults`` merge hoisted to config.py (loader,
    sharding and the serve engine call the same implementation);
    kept as a thin alias for this module's callers."""
    from eksml_tpu.config import knobs_with_defaults

    return knobs_with_defaults(node, defaults)


def _telemetry_knobs(cfg) -> Dict[str, Any]:
    from eksml_tpu.config import TELEMETRY_DEFAULTS

    return _knobs_with_fallback(getattr(cfg, "TELEMETRY", None),
                                TELEMETRY_DEFAULTS)


def _tracing_knobs(cfg) -> Dict[str, Any]:
    from eksml_tpu.config import TELEMETRY_TRACING_DEFAULTS

    return _knobs_with_fallback(
        getattr(getattr(cfg, "TELEMETRY", None), "TRACING", None),
        TELEMETRY_TRACING_DEFAULTS)


def _goodput_knobs(cfg) -> Dict[str, Any]:
    from eksml_tpu.config import TELEMETRY_GOODPUT_DEFAULTS

    return _knobs_with_fallback(
        getattr(getattr(cfg, "TELEMETRY", None), "GOODPUT", None),
        TELEMETRY_GOODPUT_DEFAULTS)


def cast_params_for_storage(params, param_dtype: str):
    """TRAIN.PARAM_DTYPE storage cast (the 1344/b8 memory plan): f32
    leaves → bf16; everything else keeps its dtype.  ONE definition
    shared by Trainer.init_state and profiling/predict.py, so the
    priced program has the memory plan production training uses.  Cast
    BEFORE tx.init so the momentum tree follows."""
    if param_dtype != "bfloat16":
        return params
    return jax.tree.map(
        lambda x: (x.astype(jnp.bfloat16)
                   if x.dtype == jnp.float32 else x), params)


def make_synthetic_train_step(model, tx, plan=None, param_sh=None,
                              opt_sh=None):
    """The synthetic-batch train step: grad of the model's total loss,
    the plan's just-in-time gather / storage-grad constraints when one
    is active, optimizer update under the ``optimizer`` named scope.

    The construction profiling/predict.py AOT-prices
    (``lower_train_step``): no loader, sentinel or telemetry around
    it, so the hermetic perf gate prices the model's real forward,
    backward and update on a synthetic batch.
    ``param_sh``/``opt_sh`` are the plan's state shardings
    (``init_sharded``); ignored without a plan."""

    def train_step(params, opt_state, batch, rng):
        def loss_fn(p):
            if plan is not None:
                p = plan.compute_params(p)  # fsdp just-in-time gather
            losses = model.apply({"params": p}, batch, rng)
            return losses["total_loss"], losses

        grads, losses = jax.grad(loss_fn, has_aux=True)(params)
        if plan is not None:
            grads = plan.storage_grads(grads)  # reduce-scatter
        # scope → "optimizer" in the profiling attribution
        with jax.named_scope("optimizer"):
            updates, new_opt = tx.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), new_opt,
                    losses["total_loss"])

    # donate only on accelerators — the compiled_step rule: on
    # XLA:CPU device buffers can alias external host memory (zero-copy
    # device_put, jit outputs) and donating them is undefined behavior
    # (the born-sharded 2d opt state turned a CPU smoke run into a
    # loss=nan + `buffer.IsAvailable()` abort).  Donation changes
    # buffer aliasing, not the instruction stream, so the CPU-lowered
    # priced program still matches the TPU-measured one.
    donate = () if jax.default_backend() == "cpu" else (0, 1)
    if plan is not None:
        repl = plan.replicated()
        return plan.jit(train_step,
                        in_shardings=(param_sh, opt_sh,
                                      plan.batch_sharding(), repl),
                        out_shardings=(param_sh, opt_sh, repl),
                        donate_argnums=donate)
    return jax.jit(train_step, donate_argnums=donate)


def _preregister_core_metrics(registry) -> None:
    """Create the always-present series so the FIRST scrape of a
    healthy run already shows every resilience/data counter at 0 —
    dashboards and alerts key on existence, not just increments."""
    for name, help_text in (
        ("eksml_resilience_preemptions",
         "SIGTERM preemption signals observed"),
        ("eksml_resilience_rollbacks",
         "divergence rollbacks to a previous checkpoint"),
        ("eksml_resilience_nonfinite_losses",
         "non-finite total_loss observations (divergence sentinel)"),
        ("eksml_resilience_watchdog_fires",
         "hang-watchdog deadline expiries (stack reports written)"),
        ("eksml_data_io_recoveries",
         "transient I/O errors absorbed by bounded retry"),
        ("eksml_data_pool_rebuilds",
         "decode process-pool self-heals after a worker death"),
        ("eksml_checkpoint_saves", "checkpoint commits started"),
        ("eksml_checkpoint_restores", "checkpoint restores completed"),
        ("eksml_checkpoint_fallbacks",
         "checkpoint integrity walk-backs to an earlier step"),
        ("eksml_checkpoint_restore_resharded",
         "checkpoint restores resharded across a topology change"),
    ):
        registry.counter(name, help_text)
    # the quarantine census is labeled by fault kind everywhere it
    # increments (robust.py) — preregister the SAME series, not a bare
    # one that would sit at 0 forever next to the real counters
    for kind in ("decode", "missing", "io_exhausted"):
        registry.counter(
            "eksml_data_quarantined_records",
            "distinct records quarantined by the data-ingest layer",
            labels={"kind": kind})
    # goodput ledger (telemetry/goodput.py): the badput family is
    # labeled by bucket everywhere it increments — preregister every
    # bucket (and the ratio gauge) so the FIRST scrape of a healthy
    # run shows the whole taxonomy at 0, and the phase events the
    # ledger reads (eval/compile, this PR's flight-recorder additions)
    # exist as countable series before the first incident
    from eksml_tpu.telemetry import goodput as goodput_mod

    registry.gauge(goodput_mod.RATIO_GAUGE,
                   "fraction of run wall-clock spent in train steps")
    registry.counter(goodput_mod.GOODPUT_COUNTER,
                     "training wall-clock seconds (the goodput "
                     "bucket)")
    for bucket in goodput_mod.BADPUT_BUCKETS:
        registry.counter(goodput_mod.BADPUT_COUNTER,
                         "non-training wall-clock seconds by bucket",
                         labels={"bucket": bucket})
    for kind in ("compile_start", "compile_done", "eval_start",
                 "eval_done"):
        registry.counter("eksml_flight_events",
                         "flight-recorder events by kind",
                         labels={"kind": kind})


def _config_digest(cfg) -> str:
    """Short stable digest of the finalized config — the run_start
    header field run_report.py uses to tell a relaunch-with-identical-
    config from a restart that changed hyperparameters."""
    import hashlib

    from eksml_tpu.config import dump_config

    try:
        return hashlib.sha256(
            dump_config(cfg).encode()).hexdigest()[:12]
    except Exception:  # noqa: BLE001 — a digest must never block a run
        return "unknown"


class Trainer:
    """Owns mesh, model, state, loop. One instance per host process."""

    def __init__(self, cfg, logdir: str, eval_fn=None,
                 write_metrics: bool = True):
        self.cfg = cfg
        self.logdir = logdir
        self.eval_fn = eval_fn

        if cfg.TPU.PROFILER_PORT and jax.process_index() == 0:
            # perf visibility (SURVEY.md §5.1): trace server for
            # `jax.profiler`/TensorBoard profile plugin — the
            # NCCL_DEBUG=INFO analogue
            jax.profiler.start_server(cfg.TPU.PROFILER_PORT)
        validate_topology(cfg.TPU.TOPOLOGY or "",
                          num_chips=(cfg.TRAIN.NUM_CHIPS
                                     if cfg.TRAIN.NUM_CHIPS > 1 else None),
                          chips_per_host=cfg.TRAIN.CHIPS_PER_HOST,
                          num_slices=cfg.TPU.NUM_SLICES)
        # the sharding plan decides the mesh axes: replicated keeps
        # the legacy (data, model) layout untouched; fsdp/2d insert
        # the fsdp axis and tensor/2d size the model axis, from
        # TRAIN.SHARDING.{FSDP,MODEL}_AXIS_SIZE
        # (parallel/sharding.py plan_mesh)
        mesh_shape, mesh_axes = plan_mesh(cfg)
        self.mesh = build_mesh(mesh_shape, mesh_axes,
                               num_slices=cfg.TPU.NUM_SLICES)
        # Horovod-style init allreduce: connect this mesh's collective
        # channels NOW, while all hosts are barrier-aligned — the lazy
        # first-collective connect otherwise races per-host compile
        # skew against a fixed deadline (collectives.py)
        warm_mesh_collectives(self.mesh)
        self.model = models.build_model(cfg)
        # {host span: step-metric keys it carries at log steps}: the
        # model's counters (none for the detector)
        self._counter_spans = models.counter_spans(cfg)
        self.tx, self.sched = make_optimizer(cfg)
        # write_metrics=False gives read-only consumers (eval_ckpt) a
        # Trainer that never touches the run's metrics.jsonl/TB events
        # (or its flight-recorder event files)
        self._telemetry = _telemetry_knobs(cfg)
        self._tracing = _tracing_knobs(cfg)
        self._goodput_cfg = _goodput_knobs(cfg)
        # live goodput meter — non-None only while fit runs (set up
        # there; _run_eval/_rollback credit through it)
        self._goodput = None
        run_info = {"config_digest": _config_digest(cfg)}
        self.writer = (MetricWriter(logdir, run_info=run_info)
                       if write_metrics and jax.process_index() == 0
                       else None)
        self.recorder = None
        self.tracer = None
        if write_metrics and self._telemetry["ENABLED"]:
            # one flight recorder per HOST (unlike the rank-0 writer):
            # resilience incidents are per-host facts
            prev = telemetry.install(telemetry.FlightRecorder(
                capacity=int(self._telemetry["FLIGHT_RECORDER_EVENTS"]),
                path=telemetry.events_path_for(
                    logdir, jax.process_index()),
                host_id=jax.process_index()))
            if prev is not None:
                prev.close()  # a prior Trainer's recorder in this proc
            self.recorder = telemetry.get()
            telemetry.event("run_start", pid=os.getpid(),
                            host_count=jax.process_count(), **run_info)
            if self._tracing["ENABLED"]:
                # span tracer, also per HOST: the whole point is the
                # cross-host timeline (trace-host<i>.json per host,
                # merged by tools/trace_summary.py --merge)
                prev_t = telemetry.install_tracer(telemetry.Tracer(
                    capacity=int(self._tracing["RING_EVENTS"]),
                    path=telemetry.trace_path_for(
                        logdir, jax.process_index()),
                    host_id=jax.process_index()))
                if prev_t is not None:
                    prev_t.flush()
                self.tracer = telemetry.get_tracer()
        # the plan owns every layout decision: batch spec, state
        # specs, and (via plan.jit) strategy executability — the
        # hard-coded PartitionSpec("data") / replicated pair is gone
        self.plan = ShardingPlan.from_config(cfg, self.mesh)
        if jax.process_index() == 0:
            log.info("sharding plan: %s over mesh %s",
                     self.plan.describe(), dict(self.mesh.shape))
        # the checkpoint manager carries THIS launch's topology
        # descriptor (persisted per step, compared at restore): mesh
        # shape/axes, slices, strategy, resolved fsdp width, device +
        # process counts — everything the restore side re-derives
        # fresh each launch and therefore cannot recover from the
        # checkpoint bytes alone.  getattr fallback: config trees
        # predating the elastic knob keep working (elastic on, the
        # default)
        self.ckpt = CheckpointManager(
            logdir, digest=cfg.RESILIENCE.CHECKPOINT_DIGEST,
            topology=current_topology(self.mesh, self.plan,
                                      num_slices=cfg.TPU.NUM_SLICES),
            elastic=bool(getattr(cfg.RESILIENCE, "ELASTIC_RESUME",
                                 True)))
        self._batch_sharding = self.plan.batch_sharding()
        self._replicated = replicated_sharding(self.mesh)
        # refined to the plan's per-leaf tree once init_state knows
        # the state structure
        self._state_sharding = self._replicated
        self._jit_step = None
        # set by _step_fn_with_prediction at the first step: the AOT
        # executable as (batch shape key, compiled), the seconds spent
        # in lower().compile(), and the roofline prediction of the
        # compiled program (TPU only)
        self.aot_step = None
        self.aot_compile_seconds = None
        self.prediction = None

    # -- state ---------------------------------------------------------

    def init_state(self, example_batch: Dict[str, np.ndarray]) -> TrainState:
        rng = jax.random.PRNGKey(self.cfg.TRAIN.SEED)
        sample = jax.tree.map(jnp.asarray, example_batch)

        def init_fn(r, b):
            return self.model.init(r, b, r)["params"]

        params, param_sh = self.plan.init_sharded(init_fn, rng, sample)
        load_pretrained = models.pretrained_loader(self.cfg)
        if load_pretrained is not None:
            params = load_pretrained(params, param_sh, self._replicated)
        params = cast_params_for_storage(
            params, getattr(self.cfg.TRAIN, "PARAM_DTYPE", "float32"))
        opt_state, opt_sh = self.plan.init_sharded(
            self.tx.init, params, deterministic=True)
        state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                           opt_state=opt_state, rng=rng)
        self._state_sharding = TrainState(
            step=self._replicated, params=param_sh,
            opt_state=opt_sh, rng=self._replicated)
        state = jax.device_put(state, self._state_sharding)
        self._publish_memory_budget(state)
        return state

    def _publish_memory_budget(self, state: TrainState) -> None:
        """One log line + two gauges per (re)init: the per-device
        cost of the state under the ACTIVE plan, so replicated-vs-fsdp
        runs are comparable from logs or /metrics alone."""
        pb, ob = publish_state_byte_gauges(state.params,
                                           state.opt_state)
        log.info(
            "memory budget/device: params %.2f MiB + optimizer state "
            "%.2f MiB (param_dtype=%s, sharding=%s)",
            pb / 2**20, ob / 2**20,
            getattr(self.cfg.TRAIN, "PARAM_DTYPE", "float32"),
            self.plan.describe())
        if log.isEnabledFor(logging.DEBUG):
            log.debug("%s", self.plan.explain(state.params, "params"))

    def _alt_restore_target(self, state):
        """Replicated-layout restore target for
        ``restore_with_fallback`` — the sharding-plan bridge a
        checkpoint committed under another plan restores through
        (both at startup and in the mid-run divergence rollback).
        None under the replicated plan (no alternate exists)."""
        if self.plan.strategy == "replicated":
            return None
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=self._replicated),
            state)

    def restore_or_init(self, example_batch) -> Tuple[TrainState, int]:
        """Auto-resume from the newest *verified* Orbax step (the
        behavior TPU preemption demands; the reference can only rerun
        by hand, SURVEY.md §5.3).  ``latest_step()`` is not trusted
        blindly: a kill mid-commit can leave the newest step dir
        truncated on the shared filesystem, so each candidate is
        integrity-checked (resilience/integrity.py manifests) and the
        restore walks back to the newest good step instead of crashing
        the relaunch.

        Plan-aware: the restore targets carry the plan's shardings, so
        a sharded plan restores shard-by-shard with no full gather.
        When the plan is NOT replicated, a replicated-layout fallback
        target rides along — a checkpoint an older (replicated) run
        committed still restores even when the plan-sharded restore
        cannot, and the device_put below re-applies the plan's specs.

        Topology-portable (ROADMAP item 4): everything topology-
        dependent was re-derived for THIS launch before we get here —
        ``plan_mesh``/``build_mesh`` from the current config/devices,
        the per-host batch from the current mesh, the data schedule
        from the current host count — so the targets describe the
        CURRENT topology and the manager reshards a checkpoint saved
        at another one (``RESILIENCE.ELASTIC_RESUME``): a preempted
        v5e-32 run relaunched on v5e-8 (or a shrunk/grown
        ``TPU.NUM_SLICES``) resumes from its forced checkpoint."""
        state = self.init_state(example_batch)
        restored = self.ckpt.restore_with_fallback(
            state, alt_state_like=self._alt_restore_target(state))
        if restored is not None:
            good, good_step = restored
            log.info("resuming from checkpoint step %d", good_step)
            state = jax.device_put(good, self._state_sharding)
            return state, good_step
        return state, 0

    # -- the step ------------------------------------------------------

    def _train_step(self, state: TrainState, batch) -> Tuple[TrainState,
                                                             Dict]:
        step_rng = jax.random.fold_in(state.rng, state.step)

        def loss_fn(params):
            # FSDP: gather the param shards just-in-time for compute
            # (identity under replicated — program unchanged)
            params = self.plan.compute_params(params)
            losses = self.model.apply({"params": params}, batch, step_rng)
            return losses["total_loss"], losses

        grads, losses = jax.grad(loss_fn, has_aux=True)(state.params)
        # FSDP: back to the storage layout (reduce-scatter), so the
        # optimizer below updates shards, not full copies
        grads = self.plan.storage_grads(grads)
        # scope → the "optimizer" attribution component
        # (eksml_tpu/profiling SCOPE_RULES)
        with jax.named_scope("optimizer"):
            updates, new_opt = self.tx.update(grads, state.opt_state,
                                              state.params)
            new_params = optax.apply_updates(state.params, updates)
            metrics = dict(losses)
            metrics["learning_rate"] = self.sched(state.step)
            metrics["grad_norm"] = optax.global_norm(grads)
        new_state = state.replace(step=state.step + 1, params=new_params,
                                  opt_state=new_opt)
        return new_state, metrics

    def compiled_step(self):
        if self._jit_step is None:
            # Donate the state only on accelerator backends.  On
            # XLA:CPU, device buffers can alias external host memory
            # (zero-copy device_put, Orbax restore/save references),
            # and donating such buffers is undefined behavior — the
            # chaos ladder's restore-then-train rungs hit all three
            # outcomes: `Check failed: buffer_info.buffer.
            # IsAvailable()` aborts, glibc heap corruption, and
            # checkpoints whose bytes were silently clobbered by the
            # next step.  On TPU the donation is the HBM win that
            # allows batch-4/chip and the async-save snapshot is a
            # real D2H copy, so it stays.
            donate = () if jax.default_backend() == "cpu" else (0,)
            # the PLAN supplies the in/out shardings (per-leaf trees
            # under fsdp/tensor/2d, the legacy replicated pair
            # otherwise)
            self._jit_step = self.plan.jit(
                self._train_step,
                in_shardings=(self._state_sharding, self._batch_sharding),
                out_shardings=(self._state_sharding, self._replicated),
                donate_argnums=donate)
        return self._jit_step

    # -- loop ----------------------------------------------------------

    def _globalize_batch(self, batch: Dict[str, np.ndarray]):
        """Host-local loader batch → batch-sharded global arrays.

        The loader yields each host ITS shard (per-host rows); in
        multi-process the global batch only exists as the concatenation
        of every host's rows, which ``host_local_array_to_global_array``
        assembles without any cross-host transfer (each host's rows
        already sit on its own devices).  A bare ``device_put`` onto the
        data-axis sharding would instead treat the local rows as the
        whole global batch and fail the divisibility check — the bug
        the composed multi-host e2e (tests/test_multihost_e2e.py)
        caught in round 3.
        """
        batch = {k: v for k, v in batch.items()
                 if k not in ("image_scale", "image_id")}
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            return multihost_utils.host_local_array_to_global_array(
                batch, self.mesh, self.plan.batch_spec)
        return jax.device_put(batch, self._batch_sharding)

    def fit(self, batches: Iterator[Dict[str, np.ndarray]],
            total_steps: int, start_step: int = 0,
            state: Optional[TrainState] = None,
            profile_steps: int = 0, data_health=None) -> TrainState:
        """``profile_steps``: capture a ``jax.profiler`` trace of that
        many post-compile steps into ``<logdir>/profile`` (the
        one-command perf-visibility path, SURVEY.md §5.1 — the
        reference's only analogue is NCCL_DEBUG=INFO ring dumps).
        The same executor also serves ``GET /debugz/profile?steps=N``
        on the telemetry port and the anomaly trigger
        (``TELEMETRY.TRACING.*`` knobs): both ask through a
        cooldown-guarded ProfileTrigger, captures land at step
        boundaries, and with tracing enabled the span ring flushes to
        ``<logdir>/trace-host<i>.json`` alongside the profiler trace.

        Goodput ledger (telemetry/goodput.py, ``TELEMETRY.GOODPUT.*``
        knobs): the run's wall-clock is classified into goodput vs
        badput buckets from the span/event exhaust above, downtime
        since the previous relaunch is recovered at fit start, the
        rolling ``eksml_goodput_ratio`` +
        ``eksml_badput_seconds_total{bucket=}`` land on /metrics at
        each log interval, and per-segment snapshots bank to
        ``<logdir>/goodput-host<i>.jsonl`` for the cross-restart
        merge (tools/goodput_report.py).

        Resilience wiring (eksml_tpu/resilience/, knobs under
        ``config.RESILIENCE``): SIGTERM forces a checkpoint at the next
        step boundary and exits with the resumable code; non-finite
        losses roll back to the last good checkpoint and never reach
        ``ckpt.save``; a heartbeat watchdog dumps all-thread stacks
        when a step exceeds its deadline.

        ``data_health``: the loader's ``LoaderHealth`` surface
        (data/robust.py).  When given, its scalars (queue depth,
        quarantine census, batch-build timing) ride the metric stream
        at every log step, and its report joins the watchdog's hang
        dump — so input starvation (TPU idle, queue empty past the
        deadline) reads as a stalled-phase diagnosis, not a generic
        hang."""
        # fit_setup: from here to the loop's first data_wait, on this
        # Trainer's own tracer (installed for this fit further down);
        # ended before the loop, or in the finally if setup raises
        setup_span = self._span("fit_setup")
        setup_span.__enter__()
        cfg = self.cfg
        res = cfg.RESILIENCE
        step_fn = None
        capture = None  # in-flight profiler capture (dict) or None
        t_last = time.time()
        steps_since_log = 0
        steps_per_epoch = cfg.TRAIN.STEPS_PER_EPOCH
        ckpt_every = max(1, cfg.TRAIN.CHECKPOINT_PERIOD) * steps_per_epoch
        eval_every = max(1, cfg.TRAIN.EVAL_PERIOD) * steps_per_epoch
        imgs_per_step = (cfg.TRAIN.BATCH_SIZE_PER_CHIP *
                         max(1, cfg.TRAIN.NUM_CHIPS))
        sync_every = cfg.TRAIN.SYNC_CHECK_PERIOD
        if sync_every and self.plan.strategy != "replicated":
            # the replica sync check fingerprints per-device LOCAL
            # shards assuming replication; under a sharded plan the
            # shards legitimately differ and the check would either
            # false-alarm or silently gather
            log.warning("TRAIN.SYNC_CHECK_PERIOD disabled: the "
                        "replica sync check assumes replicated "
                        "params (sharding strategy %r)",
                        self.plan.strategy)
            sync_every = 0

        preempt = None
        if res.GRACEFUL_SHUTDOWN:
            preempt = PreemptionHandler(
                exit_code=res.PREEMPT_EXIT_CODE).install()
        watchdog = None
        if res.WATCHDOG_TIMEOUT_SEC > 0:
            watchdog = HangWatchdog(
                res.WATCHDOG_TIMEOUT_SEC, report_dir=self.logdir,
                first_beat_factor=res.WATCHDOG_COMPILE_FACTOR).start()
            if data_health is not None:
                # loader heartbeat → hang report: queue depth, stage
                # timing, quarantine stats alongside the thread stacks
                watchdog.add_report_provider("data pipeline",
                                             data_health.report)
            if self.recorder is not None:
                # tail of the flight recorder = what happened BEFORE
                # the stall — usually the diagnosis
                watchdog.add_report_provider("flight recorder",
                                             self.recorder.report)

        # telemetry: pre-register the core series (a scrape before the
        # first incident must still show the counters at 0), publish
        # the loader's health surface as collect-time gauges, and serve
        # /metrics + /healthz from THIS pod while the loop runs
        registry = telemetry.default_registry()
        _preregister_core_metrics(registry)
        if data_health is not None:
            data_health.register_gauges(registry)
        # goodput ledger state — set up INSIDE the try below so any
        # later setup failure still reaches the finally that removes
        # the sinks (a leaked sink would feed every later fit's spans
        # into a dead meter — the PR 5 leaked-tracer class)
        goodput_bank_path = None
        prev_span_sink = None
        health_state = {"step": start_step, "total_steps": total_steps}
        # monotonic PROGRESS clock for /healthz liveness: the probe
        # reads seconds_since_last_step and (past the
        # HEALTHZ_STALE_SEC bound) a 503 — a wedged collective behind
        # an always-200 healthz is the silent hang k8s cannot see.
        # Every documented long-but-legitimate phase beats it too
        # (restore, checkpoint save, eval, rollback) so the probe
        # kills wedged pods, not pods mid-eval; the bound must still
        # cover the LONGEST single phase (first-step compile, one
        # eval pass) — the charts' probe initialDelay rides the same
        # value
        health_clock = {"last_step": time.monotonic()}

        def _progress() -> None:
            health_clock["last_step"] = time.monotonic()

        def _health() -> Dict[str, Any]:
            out = dict(health_state)
            out["seconds_since_last_step"] = round(
                time.monotonic() - health_clock["last_step"], 1)
            return out

        exporter = None
        # on-demand profiler captures (telemetry/tracing.py): ONE
        # trigger shared by /debugz/profile, the anomaly detector and
        # (via the same executor below) the --profile CLI flag
        profile_trigger = None
        detector = None
        if self._telemetry["ENABLED"]:
            profile_trigger = telemetry.ProfileTrigger(
                cooldown_sec=float(
                    self._tracing["PROFILE_COOLDOWN_SEC"]),
                max_captures=int(
                    self._tracing["MAX_CAPTURES_PER_RUN"]),
                default_steps=int(self._tracing["PROFILE_STEPS"]))
            # auto-captures ride the tracing knob: with TRACING
            # disabled (the shipped chart default) a sustained
            # slowdown must NOT surprise the operator with profiler
            # overhead + trace dumps they believed were switched off —
            # only the explicit /debugz request stays available
            if (self._tracing["ENABLED"]
                    and self._tracing["ANOMALY_TRIGGER"]):
                detector = telemetry.AnomalyDetector(
                    k_intervals=int(
                        self._tracing["ANOMALY_INTERVALS"]),
                    p95_factor=float(
                        self._tracing["ANOMALY_P95_FACTOR"]),
                    spread_factor=float(
                        self._tracing["ANOMALY_SPREAD_FACTOR"]))
        # ENABLED is the master switch for the whole layer: without it
        # neither the exporter NOR the aggregation collective runs
        aggregate_hosts = bool(self._telemetry["ENABLED"]
                               and self._telemetry["AGGREGATE_HOSTS"])
        sentinel = DivergenceSentinel(patience=res.NAN_PATIENCE,
                                      max_rollbacks=res.MAX_ROLLBACKS)
        nan_injected = False

        if self.tracer is not None:
            # (re)install for THIS fit — a second fit() on the same
            # Trainer must trace too, and the finally below uninstalls
            # so a finished run's tracer can't swallow later spans.
            # Before the prefetcher: its thread's first h2d_prefetch
            # span (seq 0) starts with the thread
            telemetry.install_tracer(self.tracer)
            self.tracer.mark_clock()
        # per-step completion stamps (device_step spans): a thread of
        # its own, only while a traced fit runs
        stamper = None
        # ordinal of the batch a data_wait took: the seq its
        # batch_build and h2d_prefetch spans carry (single producer,
        # FIFO), counted only where a span will hold it
        taken = 0

        # TRAIN.PREFETCH_TO_DEVICE: the next batch's host-shard →
        # device transfer runs on a worker thread while the device
        # executes the current step, instead of blocking here every
        # step.  Batch order is unchanged → losses bit-identical
        # (pinned in tests/test_prefetch.py); residual blocking is the
        # data/prefetch_wait_ms metric.
        prefetcher = None
        source = batches
        if getattr(cfg.TRAIN, "PREFETCH_TO_DEVICE", False):
            from eksml_tpu.data.loader import DevicePrefetcher

            with self._span("prefetcher_start"):
                prefetcher = DevicePrefetcher(batches,
                                              self._globalize_batch,
                                              health=data_health)
            source = prefetcher

        gc_meter = None
        step = start_step
        try:
            with self._span("telemetry_start"):
                # goodput ledger (telemetry/goodput.py): classify this
                # fit's wall-clock from the EXISTING span/event exhaust.
                # Downtime since the previous segment is recovered NOW
                # from the shared event file + checkpoint timestamps, so
                # the live eksml_goodput_ratio already reflects the
                # restart gap the relaunch is paying for.  self._goodput
                # is assigned BEFORE the sinks install, so the finally's
                # cleanup runs even for a partial setup.
                if (self._telemetry["ENABLED"]
                        and self._goodput_cfg["ENABLED"]):
                    from eksml_tpu.telemetry import goodput as goodput_mod

                    down_s, seg_start = goodput_mod.recover_downtime(
                        self.logdir, jax.process_index())
                    meter = telemetry.GoodputMeter(
                        fine=self.tracer is not None,
                        segment_start_wall=seg_start)
                    if down_s > 0:
                        meter.credit("downtime", down_s)
                        log.info("goodput: recovered %.1fs downtime since "
                                 "the previous segment", down_s)
                    self._goodput = meter
                    prev_span_sink = telemetry.install_span_sink(
                        meter.on_span)
                    telemetry.add_event_sink(meter.on_event)
                    if self._goodput_cfg["BANK"]:
                        goodput_bank_path = telemetry.goodput_path_for(
                            self.logdir, jax.process_index())
                # exporter starts INSIDE the try so any setup failure
                # below still reaches the finally that stops it — a leaked
                # server would squat the fixed port and keep serving stale
                # health state to probes
                if self._telemetry["ENABLED"]:
                    exporter = telemetry.TelemetryExporter(
                        port=int(self._telemetry["PORT"]),
                        health_fn=_health,
                        port_file=os.path.join(
                            self.logdir,
                            f"telemetry-host{jax.process_index()}.port"),
                        profile_trigger=profile_trigger,
                        stale_after_sec=float(
                            self._telemetry["HEALTHZ_STALE_SEC"]),
                    ).start()
                elif float(self._telemetry["HEALTHZ_STALE_SEC"]) > 0:
                    # the charts render a livenessProbe whenever
                    # healthz_stale_seconds > 0 — with telemetry disabled
                    # nothing serves /healthz, every probe gets connection
                    # refused, and kubelet restarts a HEALTHY pod forever.
                    # The combination is an operator error; say so loudly.
                    log.warning(
                        "TELEMETRY.HEALTHZ_STALE_SEC=%s is set but "
                        "TELEMETRY.ENABLED=False: /healthz will NOT be "
                        "served — if the chart rendered a livenessProbe "
                        "(healthz_stale_seconds > 0) kubelet will restart "
                        "this pod in a loop. Set healthz_stale_seconds=0 "
                        "when disabling telemetry.",
                        self._telemetry["HEALTHZ_STALE_SEC"])
            if self.tracer is not None:
                stamper = telemetry.StepStamper(jax.block_until_ready)
                gc_meter = telemetry.GcMeter(self.tracer).install()
            source_iter = iter(source)
            _end = object()
            setup_span.__exit__(None, None, None)
            setup_span = None
            while True:
                # data_wait: how long the step loop blocked on input —
                # the span that names a starving TPU in the timeline.
                # Input spans are tagged with the step they FEED
                # (step+1), so every span of one loop iteration joins
                # the train_step it produced — a step stalled on input
                # shows ITS OWN data_wait as the dominant span, not
                # the previous step's.  Until restore_or_init has run,
                # the feeding step is unknown (a resume jumps `step`
                # to the checkpoint) — an untagged span beats one
                # joined to the wrong train_step.
                feeds = step + 1 if state is not None else None
                wait_attrs = None
                if self.tracer is not None:
                    wait_attrs = {"seq": taken}
                    taken += 1
                with telemetry.span("data_wait", step=feeds,
                                    attrs=wait_attrs):
                    batch = next(source_iter, _end)
                if batch is _end:
                    break
                if watchdog:
                    watchdog.beat("globalize_batch", step)
                with telemetry.span("globalize_batch", step=feeds):
                    device_batch = (batch if prefetcher is not None
                                    else self._globalize_batch(batch))
                if state is None:
                    t_restore = time.perf_counter()
                    state, step = self.restore_or_init(device_batch)
                    _progress()  # a multi-GB restore is not a hang
                    if self._goodput is not None and step > 0:
                        # an actual resume: the whole restore walk is
                        # checkpoint_restore wall.  coarse_only — with
                        # spans on, the checkpoint_restore span inside
                        # the manager already fed the sink.
                        self._goodput.credit(
                            "checkpoint_restore",
                            time.perf_counter() - t_restore,
                            coarse_only=True)
                    if step >= total_steps:
                        break
                first_call = step_fn is None
                if watchdog:
                    # beat BEFORE the first-call AOT compile below: a
                    # hung multi-minute XLA compile must be stack-
                    # dumped as a stalled train_step, not pinned on
                    # globalize_batch (the previous beat)
                    watchdog.beat("train_step", step + 1)
                if first_call:
                    # first-shape compile window: the flight recorder
                    # gets explicit boundaries (the event stream was
                    # blind to compile — it read as a silent gap) and
                    # the goodput meter routes the first train_step
                    # span into the compile bucket instead of goodput
                    telemetry.event("compile_start", step=step + 1)
                    t_compile = time.perf_counter()
                    if self._goodput is not None:
                        self._goodput.begin_compile()
                    step_fn = self._step_fn_with_prediction(
                        self.compiled_step(), state, device_batch)
                # host-side dispatch of the compiled step (the device
                # executes async; blocking shows up in data_wait /
                # loss_sync instead — the Dapper-style host timeline)
                with telemetry.span("train_step", step=step + 1,
                                    step_trace=True):
                    state, metrics = step_fn(state, device_batch)
                if stamper is not None:
                    # the loss is an output of the step, never donated:
                    # the stamper's thread waits on it, this loop
                    # does not
                    stamper.stamp(step + 1, metrics["total_loss"])
                if watchdog and first_call:
                    # the compile happened inside that call; from here
                    # the steady-state deadline applies
                    watchdog.end_compile_headroom()
                if first_call:
                    compile_s = time.perf_counter() - t_compile
                    telemetry.event(
                        "compile_done", step=step + 1,
                        compile_ms=round(compile_s * 1e3, 1))
                    if self._goodput is not None:
                        self._goodput.end_compile(compile_s)
                step += 1
                steps_since_log += 1
                health_state["step"] = step
                _progress()

                if (res.FAULT_INJECT_NAN_STEP and not nan_injected
                        and step == res.FAULT_INJECT_NAN_STEP):
                    # chaos-ladder hook: poison the params ONCE — from
                    # here every loss is non-finite until the sentinel
                    # rolls back, exactly like a real divergence
                    nan_injected = True
                    log.warning("chaos: injecting NaN into params at "
                                "step %d (RESILIENCE.FAULT_INJECT_"
                                "NAN_STEP)", step)
                    state = state.replace(params=jax.tree.map(
                        lambda x: x * jnp.asarray(jnp.nan, x.dtype),
                        state.params))

                # on-demand profiler capture: ONE executor for all
                # three request paths — the --profile CLI flag, GET
                # /debugz/profile, and the anomaly trigger.  Start and
                # stop land at step boundaries with the loss
                # materialized, so the trace covers whole steps.
                if capture is None:
                    req = None
                    if profile_steps and jax.process_index() == 0:
                        # CLI path keeps its historical semantics:
                        # rank 0 only, starts after the first
                        # (compile) step, no trigger guard rails
                        req = {"steps": profile_steps, "reason": "cli",
                               "from_trigger": False}
                        profile_steps = 0
                    elif profile_trigger is not None:
                        req = profile_trigger.take()
                        if req is not None:
                            req["from_trigger"] = True
                    if req is not None:
                        # capture boundary: the trace must cover WHOLE
                        # steps, so the loss is materialized exactly
                        # once per accepted profile request (cooldown-
                        # guarded), never per step
                        jax.block_until_ready(metrics["total_loss"])  # eksml-lint: disable=host-sync
                        capture = self._start_capture(req, step)
                elif step >= capture["until"]:
                    # capture boundary (close): same once-per-capture
                    # cadence as the start sync above
                    jax.block_until_ready(metrics["total_loss"])  # eksml-lint: disable=host-sync
                    capture = self._finish_capture(capture,
                                                   profile_trigger,
                                                   step)

                log_step = (step % cfg.TRAIN.LOG_PERIOD == 0
                            or step == total_steps)
                ckpt_step = (step % ckpt_every == 0
                             or step == total_steps)
                # Divergence sentinel: observe the loss wherever the
                # loop materializes it anyway (log/checkpoint
                # boundaries), or every NAN_CHECK_PERIOD steps when the
                # operator buys a tighter guard with one device sync
                # per check.  A checkpoint boundary ALWAYS observes —
                # non-finite state must never reach ckpt.save.
                period = res.NAN_CHECK_PERIOD
                if (ckpt_step or (period > 0 and step % period == 0)
                        or (period == 0 and log_step)):
                    # sentinel observation: gated above on checkpoint/
                    # NAN_CHECK_PERIOD/log boundaries — the operator
                    # buys a tighter divergence guard with exactly one
                    # device sync per check, documented at the knob.
                    # loss_sync: on a log step THIS is where the loop
                    # waits for the device to catch up (seconds, when
                    # it ran ahead) — host_metrics below finds the
                    # loss already there.  In no goodput bucket: the
                    # device is at work while it lasts.
                    with telemetry.span("loss_sync", step=step):
                        loss_now = float(np.asarray(metrics["total_loss"]))  # eksml-lint: disable=host-sync
                    action = sentinel.observe(step, loss_now)
                    if action == ROLLBACK:
                        t_rb = time.perf_counter()
                        state, step = self._rollback(sentinel, state,
                                                     step,
                                                     watchdog=watchdog)
                        if self._goodput is not None:
                            # mid-run divergence recovery is restore
                            # wall too (span covers it in fine mode)
                            self._goodput.credit(
                                "checkpoint_restore",
                                time.perf_counter() - t_rb,
                                coarse_only=True)
                        _progress()  # recovery, not a hang
                        steps_since_log = 0
                        t_last = time.time()
                        continue

                if log_step:
                    # host_metrics: the log row's host work.  With
                    # the sentinel observing on log steps (the default)
                    # the wait for the device has already landed in
                    # loss_sync above; with NAN_CHECK_PERIOD > 0 it
                    # lands here, on log steps the sentinel skips
                    with telemetry.span("host_metrics", step=step):
                        # loss materialization at LOG_PERIOD cadence —
                        # the sync the log row needs anyway
                        metrics = jax.tree.map(
                            lambda x: float(np.asarray(x)), metrics)  # eksml-lint: disable=host-sync
                    # log_row: the rest of the log step's host work, while
                    # the device, drained by the wait above, idles until
                    # the next train_step.  Its args: the steps since the
                    # previous row and the collector's time over them
                    row = None
                    if gc_meter is not None:
                        row = dict(gc_meter.drain(), steps=steps_since_log)
                    with telemetry.span("log_row", step=step, attrs=row):
                        for name, keys in self._counter_spans.items():
                            # the model's counters of THIS step, where a
                            # reader of the span ring can see them
                            with telemetry.span(name, step=step, attrs={
                                    k: metrics[k] for k in keys
                                    if k in metrics}):
                                pass
                        if data_health is not None:
                            metrics.update(
                                {f"data/{k}": float(v) for k, v
                                 in data_health.scalars().items()
                                 if isinstance(v, (int, float))})
                        elif prefetcher is not None:
                            # no LoaderHealth surface (direct fit callers):
                            # still emit the prefetch wait
                            metrics["data/prefetch_wait_ms"] = round(
                                prefetcher.wait_ms_ewma or 0.0, 2)
                        dt = time.time() - t_last
                        t_last = time.time()
                        # normalize by the steps actually covered since the
                        # last log — the final step lands off the
                        # LOG_PERIOD boundary, where assuming a full period
                        # overstated throughput
                        metrics["images_per_sec"] = (
                            imgs_per_step * steps_since_log / max(dt, 1e-9))
                        step_time_ms = (dt * 1000.0
                                        / max(1, steps_since_log))
                        metrics["step_time_ms"] = round(step_time_ms, 2)
                        steps_since_log = 0
                        agg = None
                        if aggregate_hosts:
                            # cross-host min/max/mean + straggler index:
                            # host-side allgather OUTSIDE jit, zero RNG —
                            # a collective, so it runs on EVERY host at
                            # this (host-identical) log step, not just
                            # where the writer lives
                            hv = {k: metrics.get(f"data/{k}", 0.0)
                                  for k in telemetry.HOST_AGG_KEYS}
                            hv["step_time_ms"] = step_time_ms
                            with telemetry.span("host_aggregate",
                                                step=step):
                                agg = telemetry.aggregate_host_scalars(hv)
                            telemetry.publish_aggregates(agg, registry)
                            metrics.update(agg)
                        if detector is not None:
                            # anomaly trigger: a persistent step-time p95
                            # regression or straggler fires the SAME
                            # guarded capture /debugz/profile uses, so the
                            # incident's trace exists before anyone is
                            # paged.  agg values are host-identical (they
                            # came off a collective), so all hosts request
                            # together and each captures its own trace.
                            lag = spread = None
                            if agg is not None:
                                mean = agg.get("hosts/step_time_ms_mean",
                                               0.0)
                                if mean > 0:
                                    lag = agg.get("hosts/lagging")
                                    spread = (agg.get(
                                        "hosts/step_time_ms_max", 0.0)
                                        / mean)
                            reason = detector.observe(
                                step_time_ms, lagging_host=lag,
                                spread_ratio=spread)
                            if (reason is not None
                                    and profile_trigger is not None):
                                ok, detail = profile_trigger.request(
                                    steps=int(
                                        self._tracing["PROFILE_STEPS"]),
                                    reason=f"anomaly: {reason}")
                                log.warning(
                                    "telemetry anomaly at step %d: %s — "
                                    "profile capture %s (%s)", step,
                                    reason,
                                    "accepted" if ok else "rejected",
                                    detail)
                                telemetry.event(
                                    "anomaly_detected", step=step,
                                    reason=reason,
                                    capture=("accepted" if ok
                                             else detail))
                        if self._goodput is not None:
                            # rolling run-level SLI: the ratio gauge +
                            # monotonic per-bucket badput counters land on
                            # /metrics (the elastic controller's inputs),
                            # the banked snapshot line is what makes the
                            # ledger survive this process
                            with telemetry.span("goodput_publish", step=step):
                                snap = self._goodput.publish(registry,
                                                             steps=step)
                                metrics["goodput/ratio"] = \
                                    snap["goodput_ratio"]
                                if goodput_bank_path:
                                    self._goodput.bank(goodput_bank_path,
                                                       steps=step)
                        if self.writer:
                            with telemetry.span("write_scalars", step=step):
                                self.writer.write_scalars(step, metrics)
                        # live HBM gauges + the one-time predicted-vs-
                        # measured peak line (best-effort: CPU backends
                        # report no memory_stats and this is a silent
                        # no-op — test-pinned)
                        with telemetry.span("hbm_gauges", step=step):
                            self._publish_hbm()
                        log.info("step %d/%d loss=%.4f (%.1f img/s)", step,
                                 total_steps, metrics["total_loss"],
                                 metrics["images_per_sec"])

                if sync_every and step % sync_every == 0:
                    from eksml_tpu.parallel.collectives import \
                        assert_replicas_in_sync

                    assert_replicas_in_sync(state.params, self.mesh,
                                            rng=state.rng)

                if ckpt_step:
                    if not sentinel.allows_save():
                        log.warning(
                            "skipping checkpoint at step %d: last "
                            "observed total_loss is non-finite "
                            "(divergence sentinel)", step)
                        telemetry.event(
                            "checkpoint_skipped", step=step,
                            reason="non-finite loss observation")
                    else:
                        # hand Orbax the sharded jax arrays directly:
                        # async checkpointing snapshots to host (brief
                        # blocking D2H) and persists in a background
                        # thread.  Materializing to numpy first
                        # (round 1) forced the full write onto the
                        # step loop.  Donation is safe — the snapshot
                        # completes before save() returns.
                        if watchdog:
                            watchdog.beat("checkpoint_save", step)
                        t_save = time.time()
                        self.ckpt.save(step, state)
                        save_ms = (time.time() - t_save) * 1000
                        if self.writer:
                            self.writer.write_scalars(step, {
                                "checkpoint_save_ms": save_ms})
                        if self._goodput is not None:
                            # the step-loop blocking portion only —
                            # the async persist overlaps training by
                            # design and is not badput
                            self._goodput.credit(
                                "checkpoint_save", save_ms / 1e3,
                                coarse_only=True)
                        _progress()  # a slow shared-fs commit is not a hang
                if self.eval_fn and (step % eval_every == 0
                                     or step == total_steps):
                    if watchdog:
                        watchdog.beat("eval", step)
                    self._run_eval(state, step)
                    _progress()  # an eval pass is not a hang

                # graceful preemption: every host polls at the same
                # steps (the poll is a collective in multi-host) so a
                # SIGTERM on ANY host makes ALL hosts commit a forced
                # checkpoint together and exit resumable
                if preempt is not None and preempt.should_checkpoint(
                        step,
                        res.PREEMPT_SYNC_PERIOD or cfg.TRAIN.LOG_PERIOD):
                    self._graceful_exit(preempt, metrics, state, step)

                if step >= total_steps:
                    break
                if watchdog:
                    watchdog.beat("next_batch", step)
        finally:
            if setup_span is not None:
                setup_span.__exit__(*sys.exc_info())
            # fit_teardown and its children are recorded on this
            # Trainer's tracer directly: the ones after the ring's flush
            # and uninstall land in the ring, not in the flushed file
            with self._span("fit_teardown", step=step):
                if self._goodput is not None:
                    # final snapshot: the exporter may already be gone
                    # but the banked line is the segment's authoritative
                    # ledger row for the cross-restart merge — land it
                    # on EVERY exit path (preemption included)
                    with self._span("goodput_final"):
                        try:
                            self._goodput.publish(registry, steps=step)
                            if goodput_bank_path:
                                self._goodput.bank(goodput_bank_path,
                                                   steps=step, final=True)
                        except Exception:  # noqa: BLE001 — observability
                            log.exception("final goodput snapshot failed")
                        telemetry.remove_event_sink(self._goodput.on_event)
                        telemetry.install_span_sink(prev_span_sink)
                        self._goodput = None
                if watchdog:
                    watchdog.stop()
                if preempt is not None:
                    preempt.uninstall()
                if prefetcher is not None:
                    # stop the transfer thread and drop its queued device
                    # batches — an exception mid-loop must not leak the
                    # thread or pin prefetched HBM
                    with self._span("prefetcher_close"):
                        prefetcher.close()
                if exporter is not None:
                    # the scrape endpoint dies with the loop it
                    # describes; a relaunch (or a later fit) re-binds
                    # cleanly
                    with self._span("exporter_stop"):
                        exporter.stop()
                if stamper is not None:
                    # stamp every dispatched step (the device drains)
                    # while the tracer, and a capture in flight, still
                    # take the spans; on the way out of an error a wedged
                    # device must not hang the exit (the thread is a
                    # daemon).  After the teardown above, not before it:
                    # the loop runs ahead of the device, and an untraced
                    # fit stops its exporter (up to 0.5 s of server poll)
                    # while the device drains — so must a traced one (on
                    # a TPU v5e, 0.43 s a fit otherwise)
                    with self._span("device_drain"):
                        stamper.close(failed=sys.exc_info()[0] is not None)
                if capture is not None:
                    # run ended before the capture's steps elapsed —
                    # close the trace so it still lands (and a later
                    # start_trace won't raise)
                    self._finish_capture(capture, profile_trigger, step,
                                         truncated=True)
                if gc_meter is not None:
                    gc_meter.uninstall()
                if self.tracer is not None:
                    # steady-state spans land even without a capture:
                    # the cross-host merge works from whatever the ring
                    # holds
                    with self._span("ring_flush"):
                        self.tracer.flush()
                    # uninstall so later spans in this process (another
                    # Trainer, eval tooling) can't record into THIS run's
                    # ring and be flushed into its trace file
                    if telemetry.get_tracer() is self.tracer:
                        telemetry.install_tracer(None)
                # always drain the async checkpoint thread and buffered
                # metrics — an exception mid-loop must not abandon an
                # in-flight save or lose the last metric rows.  A drain
                # failure is swallowed ONLY while another exception is
                # already propagating (it must not mask where training
                # actually died); on the clean path it raises, so a
                # failed final commit cannot masquerade as a successful
                # run.
                propagating = sys.exc_info()[0] is not None
                try:
                    with self._span("ckpt_wait"):
                        self.ckpt.wait()
                    if self.writer:
                        with self._span("writer_flush"):
                            self.writer.flush()
                except Exception:
                    if not propagating:
                        raise
                    log.exception("draining checkpoint/metrics state "
                                  "during shutdown failed (keeping the "
                                  "original exception)")
        return state

    def _span(self, name: str, step: Optional[int] = None):
        """A span on this Trainer's own tracer, installed or not: the
        fit's edges run before the install and after the uninstall.
        The shared null span where tracing is off."""
        if self.tracer is None:
            return telemetry.NULL_SPAN
        return self.tracer.span(name, step=step)

    @staticmethod
    def _batch_shape_key(batch) -> Tuple:
        """Hashable (name, shape, dtype) signature of a device batch —
        the AOT-executable dispatch guard below."""
        return tuple(sorted(
            (k, tuple(np.shape(v)), str(getattr(v, "dtype", "?")))
            for k, v in batch.items()))

    def _step_fn_with_prediction(self, jit_step, state, batch):
        """AOT-compile the first batch shape and publish the
        ``eksml_train_predicted_step_time_ms`` gauge from its HLO
        (roofline model, profiling/predict.py) — the hermetic
        prediction next to every measured step-time scrape, published
        at fit start as the compile happens anyway.

        Returns the step callable: the AOT executable for batches
        matching the first shape (so the compile is paid ONCE — the
        jit wrapper never compiles this shape), and the jit wrapper
        for any other bucket canvas.  Knob-gated
        (``TELEMETRY.PREDICTED_STEP_TIME``).  A compile the backend
        refuses is the run's failure and propagates: retrying it
        through the jit wrapper would only pay the same compile again.
        The pricing half is telemetry and stays best-effort; it runs
        only for a TPU program (the chip table has no row for a CPU,
        and a CPU-lowered program priced under a chip's name is not a
        device number)."""
        if not (self._telemetry["ENABLED"]
                and self._telemetry.get("PREDICTED_STEP_TIME")):
            return jit_step
        first_key = self._batch_shape_key(batch)
        cached = self.aot_step
        if cached is not None and cached[0] == first_key:
            # a second fit on this trainer (the two-sequential-fits
            # pattern): the AOT executable is already compiled and the
            # gauge already published — lowering again would pay the
            # full XLA compile a second time
            compiled = cached[1]
        else:
            t0 = time.perf_counter()
            compiled = jit_step.lower(state, batch).compile()
            self.aot_step = (first_key, compiled)
            self.aot_compile_seconds = time.perf_counter() - t0
            if self.mesh.devices.flat[0].platform == "tpu":
                try:
                    self._price_compiled(compiled)
                except Exception:  # noqa: BLE001 — observability only
                    # the AOT compile is already paid: keep dispatching
                    # it even when the pricing half fell over
                    log.warning("predicted-step-time gauge unavailable",
                                exc_info=True)

        def dispatch(s, b):
            if self._batch_shape_key(b) == first_key:
                return compiled(s, b)
            return jit_step(s, b)  # another bucket: jit as before

        return dispatch

    def _price_compiled(self, compiled) -> None:
        """Price the compiled step's HLO against its chip's roofline
        (``predict_for_compiled``), publish the gauge and keep the
        prediction for the predicted-vs-measured lines."""
        from eksml_tpu.profiling import predict as predict_mod

        pred = predict_mod.predict_for_compiled(
            compiled.as_text(),
            device_kind=self.mesh.devices.flat[0].device_kind,
            mesh_shape=dict(self.mesh.shape),
            precision=str(self.cfg.TRAIN.PRECISION),
            num_slices=int(self.cfg.TPU.NUM_SLICES))
        predict_mod.publish_predicted_gauge(pred)
        self.prediction = pred
        s = pred["sections_ms"]
        c = pred.get("comms_ms") or {}
        h = pred.get("hbm") or {}
        log.info(
            "predicted step time (%s roofline): %.2f ms "
            "(fwd %.2f / bwd %.2f / comms %.2f / "
            "optimizer %.2f; comms ici %.2f / dcn %.2f / "
            "exposed %.2f; peak HBM %.1f MB)",
            pred["target"], pred["predicted_step_time_ms"],
            s["fwd"], s["bwd"], s["comms"], s["optimizer"],
            c.get("ici_ms", 0.0), c.get("dcn_ms", 0.0),
            c.get("exposed_ms", 0.0),
            h.get("peak_hbm_bytes", 0) / 1e6)

    def _publish_hbm(self) -> None:
        """Publish ``eksml_train_hbm_bytes_in_use`` /
        ``eksml_train_hbm_peak_bytes`` from the first local device's
        ``memory_stats()`` at log steps, and — once, when a roofline
        prediction exists — log predicted-vs-measured peak so
        calibration evidence for the memory model banks itself on the
        next hardware round.  Best-effort throughout: backends
        without the stats (CPU returns None) are a silent no-op."""
        from eksml_tpu.profiling import memory as memory_mod

        try:
            device = jax.local_devices()[0]
        except Exception:  # noqa: BLE001 — observability only
            return
        stats = memory_mod.publish_hbm_gauges(device)
        if stats is None:
            return
        predicted = (self.prediction or {}).get("hbm") or {}
        measured_peak = stats.get("peak_bytes")
        if (measured_peak and predicted.get("peak_hbm_bytes")
                and not getattr(self, "_hbm_peak_logged", False)):
            self._hbm_peak_logged = True
            pp = predicted["peak_hbm_bytes"]
            log.info(
                "hbm peak: predicted %.1f MB vs measured %.1f MB "
                "(x%.2f) — memory-model calibration point",
                pp / 1e6, measured_peak / 1e6,
                measured_peak / max(pp, 1))

    def _start_capture(self, req: Dict, step: int) -> Dict:
        """Begin a bounded profiler capture: ``jax.profiler`` trace
        into ``<logdir>/profile`` plus a span-ring marker.  A profiler
        that refuses to start degrades to span-only capture — the
        capture must never take down training."""
        started = False
        try:
            # Python tracer off: on, the first traced step of a
            # process waits ~1 s for the host (my chip runs, PRs 24,
            # 25).  The host tracer stays at its default level: it
            # records the spans' annotations, and level 1 kept the TPU
            # runtime's per-chunk transfer events all the same (2.3 M
            # against 2.6 M in 5 steps; they are what stop_trace takes
            # ~10 s to write, PERF.md §6)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(
                os.path.join(self.logdir, "profile"),
                profiler_options=options)
            started = True
        except Exception:  # noqa: BLE001 — observability is best-effort
            log.warning("jax.profiler capture failed to start — "
                        "continuing with span capture only",
                        exc_info=True)
        until = step + int(req["steps"])
        if self.tracer is not None:
            self.tracer.instant("profile_capture_start", step=step,
                                reason=str(req.get("reason", "?")))
        telemetry.event("profile_capture", step=step,
                        reason=str(req.get("reason", "?")),
                        steps=int(req["steps"]),
                        profiler=started)
        log.info("profile capture started at step %d (%s): %d "
                 "step(s) into %s/profile", step,
                 req.get("reason", "?"), int(req["steps"]),
                 self.logdir)
        return {"until": until, "profiler": started,
                "reason": str(req.get("reason", "?")),
                "from_trigger": bool(req.get("from_trigger", False))}

    def _finish_capture(self, capture: Dict, trigger, step: int,
                        truncated: bool = False) -> None:
        """Close an in-flight capture: stop the profiler trace, flush
        the span ring to ``trace-host<i>.json``, start the trigger's
        cooldown.  Returns None (the new ``capture`` state)."""
        if capture["profiler"]:
            try:
                jax.profiler.stop_trace()
                log.info("profiler trace%s written to %s/profile",
                         " (truncated run)" if truncated else "",
                         self.logdir)
            except Exception:  # noqa: BLE001 — shutdown must proceed
                log.warning("jax.profiler stop_trace failed",
                            exc_info=True)
        span_path = None
        if self.tracer is not None:
            self.tracer.instant("profile_capture_done", step=step,
                                reason=capture["reason"])
            span_path = self.tracer.flush()
        telemetry.event("profile_capture_done", step=step,
                        reason=capture["reason"],
                        truncated=bool(truncated),
                        spans=span_path or "")
        if capture["from_trigger"] and trigger is not None:
            trigger.finish()
        return None

    def _rollback(self, sentinel: DivergenceSentinel, state: TrainState,
                  step: int, watchdog=None) -> Tuple[TrainState, int]:
        """Divergence recovery: restore the newest verified checkpoint
        and continue from there.  The data iterator is NOT rewound, so
        the re-run consumes fresh batches — the window that fed the
        divergence is skipped.  Raises DivergenceError when there is
        nothing to restore or the rollback budget is spent."""
        if watchdog:
            # a multi-GB restore from the shared fs legitimately
            # exceeds a step-sized deadline — this is recovery, not a
            # hang
            watchdog.beat("rollback_restore", step)
        restored = self.ckpt.restore_with_fallback(
            state, alt_state_like=self._alt_restore_target(state))
        if restored is None:
            raise sentinel.no_checkpoint_to_restore(step)
        good, good_step = restored
        sentinel.register_rollback(step, good_step)
        telemetry.event("rollback", step=step, to_step=good_step,
                        first_bad_step=sentinel.first_bad_step)
        if self.writer:
            self.writer.write_scalars(
                good_step, {"resilience/rollback_from": float(step)})
        return jax.device_put(good, self._state_sharding), good_step

    def _graceful_exit(self, preempt: PreemptionHandler,
                       metrics: Dict, state: TrainState,
                       step: int) -> None:
        """SIGTERM grace window: commit a forced checkpoint (unless
        the state is non-finite), flush metrics, and exit with the
        documented resumable code — the chart's podFailurePolicy maps
        it to restart-not-fail, so the relaunch loses at most the
        in-flight step.  The finiteness check reads THIS step's loss
        (one device sync — the process is exiting anyway) rather than
        the sentinel's possibly steps-old observation, so a recovered
        blip cannot block the forced save."""
        # telemetry for the signal is published HERE, not in the
        # signal handler — the handler must stay flag-only (a lock
        # acquisition in signal context deadlocks against whatever
        # critical section it interrupted, see preemption._on_signal)
        telemetry.default_registry().counter(
            "eksml_resilience_preemptions",
            "SIGTERM preemption signals observed").inc()
        telemetry.event("sigterm", step=step,
                        signal_time=preempt.signal_time)
        # land any in-flight periodic commit first; if THIS step was
        # just checkpointed in the same iteration, a forced re-save
        # would delete and rewrite it — doubling the commit cost the
        # grace window was sized for and briefly unprotecting a good
        # checkpoint
        self.ckpt.wait()
        if self.ckpt.latest_step() == step:
            log.warning("preemption: step %d already committed; "
                        "exiting resumable (code %d)", step,
                        preempt.exit_code)
        elif math.isfinite(float(np.asarray(metrics["total_loss"]))):
            log.warning("preemption: forcing checkpoint at step %d",
                        step)
            self.ckpt.save(step, state, force=True)
            self.ckpt.wait()
            log.warning("preemption: checkpoint at step %d committed; "
                        "exiting resumable (code %d)", step,
                        preempt.exit_code)
        else:
            log.warning("preemption: last observed loss non-finite — "
                        "NOT committing a poisoned checkpoint; exiting "
                        "resumable (code %d)", preempt.exit_code)
        if self.writer:
            self.writer.write_scalars(
                step, {"resilience/preempted": 1.0})
            self.writer.flush()
        telemetry.event("preempt_exit", step=step,
                        exit_code=preempt.exit_code)
        raise preempt.preempted(step)

    def _run_eval(self, state, step):
        # explicit eval boundaries in the event stream: eval was
        # invisible to the flight recorder (a long silent gap), so the
        # goodput ledger would misattribute it to host_overhead.  The
        # done event carries the measured wall either way it ends.
        telemetry.event("eval_start", step=step)
        t_eval = time.perf_counter()
        ok = True
        try:
            params = state.params
            if self.plan.strategy != "replicated":
                # the eval/predict stack jits its own programs against
                # plain replicated params — hand it a gathered copy
                # rather than leaking the training layout into it
                params = jax.device_put(params, self._replicated)
            with telemetry.span("eval", step=step):
                results = self.eval_fn(self.model, params, step)
            if results and self.writer:
                self.writer.write_scalars(
                    step, {f"val/{k}": v for k, v in results.items()})
        except Exception:
            ok = False
            log.exception("eval at step %d failed", step)
        finally:
            eval_s = time.perf_counter() - t_eval
            telemetry.event("eval_done", step=step, ok=ok,
                            eval_ms=round(eval_s * 1e3, 1))
            if self._goodput is not None:
                # coarse_only: in fine mode the eval span above
                # already fed the sink
                self._goodput.credit("eval", eval_s, coarse_only=True)


# ---- CLI ------------------------------------------------------------


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description="TPU-native Mask-RCNN trainer (eksml_tpu)")
    # flag names preserved from the reference's train.py invocation
    # (charts/maskrcnn/templates/maskrcnn.yaml:56-72)
    p.add_argument("--logdir", default=None,
                   help="run directory on the shared filesystem")
    p.add_argument("--config", nargs="*", default=[],
                   help="KEY=VALUE dotted-path config overrides")
    p.add_argument("--load", default=None,
                   help="explicit checkpoint step to restore")
    p.add_argument("--synthetic", action="store_true",
                   help="train on generated data (no COCO on disk)")
    p.add_argument("--total-steps", type=int, default=None,
                   help="override steps (default: epochs × steps/epoch)")
    p.add_argument("--profile", type=int, default=0, metavar="N",
                   help="trace N post-compile steps into "
                        "<logdir>/profile (TensorBoard profile plugin)")
    return p.parse_args(argv)


def main(argv=None):
    # force=True: an import that installed a root handler on the way
    # makes a plain basicConfig a silent no-op — dropping every INFO
    # diagnostic (resume step, integrity fallbacks, "training
    # complete") from the pod log
    logging.basicConfig(
        level=logging.INFO, force=True,
        format="%(asctime)s %(levelname)s %(name)s: %(message)s")

    from eksml_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    args = parse_args(argv)

    cfg = config_from_env(global_config)
    cfg.freeze(False)
    if args.logdir:
        cfg.TRAIN.LOGDIR = args.logdir
    if args.synthetic:
        cfg.DATA.SYNTHETIC = True
    cfg.update_args(args.config)
    cfg = finalize_configs(is_training=True)

    initialize_from_env(cfg)
    log.info("process %d/%d, devices: %d", jax.process_index(),
             jax.process_count(), len(jax.devices()))

    from eksml_tpu.data import build_train_loader

    eval_fn = None
    if cfg.MODEL.NAME == "maskrcnn" and not cfg.DATA.SYNTHETIC:
        from eksml_tpu.evalcoco import make_eval_fn

        eval_fn = make_eval_fn(cfg)

    trainer = Trainer(cfg, cfg.TRAIN.LOGDIR, eval_fn=eval_fn)
    # everything after the Trainer exists runs under the try: dataset
    # preflight (strict mode raises) and loader construction (a
    # resumed over-threshold quarantine ledger raises) must still
    # reach the finally that closes the checkpoint manager — live
    # Orbax threads at interpreter teardown flake-crash and can garble
    # the actionable abort message
    try:
        # batch sizing follows the mesh, not local_devices(): a subset
        # mesh (single-chip smoke on a multi-device host) must not
        # inflate the per-host batch
        local_chips = sum(d.process_index == jax.process_index()
                          for d in trainer.mesh.devices.flat)
        per_host_batch = cfg.TRAIN.BATCH_SIZE_PER_CHIP * max(
            1, local_chips)
        # the model's loader, chosen by MODEL.NAME as the model is
        loader = build_train_loader(
            cfg, per_host_batch, num_hosts=jax.process_count(),
            host_id=jax.process_index())

        total_steps = (args.total_steps
                       if args.total_steps is not None
                       else cfg.TRAIN.STEPS_PER_EPOCH
                       * cfg.TRAIN.MAX_EPOCHS)
        trainer.fit(loader.batches(None), total_steps,
                    profile_steps=args.profile,
                    data_health=loader.health)
    except PreemptedError as e:
        log.warning("preempted at step %d: exiting with resumable "
                    "code %d (JobSet restarts without burning a "
                    "maxRestarts entry; relaunch auto-resumes)",
                    e.step, e.exit_code)
        raise  # SystemExit subclass: the process exits with the code
    else:
        log.info("training complete at %d steps", total_steps)
    finally:
        # ALWAYS shut Orbax's background threads down before
        # interpreter teardown — a live async-save thread at
        # Py_Finalize is a flaky shutdown crash, and on the preemption
        # path a teardown crash would replace the documented resumable
        # exit code with a signal death the chart counts as a genuine
        # failure.  A close() error is swallowed only while an
        # exception (incl. PreemptedError) is already propagating —
        # the exit status must stay what that exception says; on the
        # clean path it raises, so a failed final commit surfaces.
        propagating = sys.exc_info()[0] is not None
        try:
            trainer.ckpt.close()
        except Exception:
            if not propagating:
                raise
            log.exception("checkpoint manager close failed during "
                          "shutdown (keeping the original exit "
                          "status)")


if __name__ == "__main__":
    main()
