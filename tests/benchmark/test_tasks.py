"""A second task is data: a toy sequence task (token rows, Adam, no
detector key anywhere), written into a temporary tree as NEW files
only -- its task module, configuration, mix, workload and manifest --
loads through ``harness.load_cell`` and runs every step of a run short
of ``Trainer``: the loader's first batches, the tap on the step with
Adam's state, the task's reference, ``compare.numbers`` ->
``compare.judge``, and ``read_per_layer`` with ``step_mfu_pct``.  No
file that exists is touched.  CPU only; never a device number."""

import ast
import inspect
import json
import os
import sys
import types

import numpy as np
import pytest

import bench_smoke
from bench_smoke import program_config_put_back  # noqa: F401
from benchmark import compare, harness, tasks
from benchmark.metrics import step_mfu_pct
from benchmark.tasks import detection

INTERFACE = {"spec_mismatches", "build_loader", "first_moment",
             "reference_steps", "extra_numbers", "train_ops_per_row"}

TOY_TASK = '''
"""Toy sequence task: next-token loss of embed -> head on rows of
tokens, Adam.  The reference is numpy in float64."""

import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8


def init_params(spec, seed):
    rng = np.random.RandomState(seed)
    v, d = spec["vocab"], spec["width"]
    return {"embed": rng.normal(0, 0.5, (v, d)),
            "head": rng.normal(0, 0.5, (d, v))}


def spec_mismatches(cfg, spec, hyper):
    rows = cfg.TRAIN.NUM_CHIPS * cfg.TRAIN.BATCH_SIZE_PER_CHIP
    return ([] if hyper["global_batch"] == rows else
            [f"global_batch: file {hyper['global_batch']}, program {rows}"])


class TokenLoader:
    health = None

    def __init__(self, mix, rows, seed):
        rng = np.random.RandomState(seed)
        self.rows = rows
        self.data = rng.randint(0, mix["vocab"],
                                (mix["sequences"], mix["length"] + 1))

    def batches(self, n):
        i = 0
        while n is None or i < n:
            at = (i * self.rows) % (len(self.data) - self.rows + 1)
            yield {"tokens": self.data[at:at + self.rows].astype(np.int32)}
            i += 1


def build_loader(cell, cfg, seed, logdir):
    rows = cfg.TRAIN.BATCH_SIZE_PER_CHIP * cell.chips
    return TokenLoader(cell.workload["traffic"], rows, seed), rows


def first_moment(opt_state):
    import optax

    (adam,) = [s for s in opt_state
               if isinstance(s, optax.ScaleByAdamState)]
    return adam.mu


def _loss_and_grads(p, tokens):
    x, y = tokens[:, :-1].ravel(), tokens[:, 1:].ravel()
    h = p["embed"][x]
    logits = h @ p["head"]
    logits -= logits.max(axis=1, keepdims=True)
    prob = np.exp(logits)
    prob /= prob.sum(axis=1, keepdims=True)
    n = len(x)
    loss = -np.log(prob[np.arange(n), y]).mean()
    dlogits = prob
    dlogits[np.arange(n), y] -= 1.0
    dlogits /= n
    g_embed = np.zeros_like(p["embed"])
    np.add.at(g_embed, x, dlogits @ p["head"].T)
    return loss, {"embed": g_embed, "head": h.T @ dlogits}


def _norms(tree):
    return {k: float(np.sqrt(np.sum(np.square(v)))) for k, v in tree.items()}


def reference_steps(spec, hyper, seed, batches, precision="float32",
                    rows=None):
    p = init_params(spec, seed)
    p0 = {k: v.copy() for k, v in p.items()}
    mu = {k: np.zeros_like(v) for k, v in p.items()}
    nu = {k: np.zeros_like(v) for k, v in p.items()}
    out = {"loss": [], "terms": []}
    for s, batch in enumerate(batches):
        tokens = batch["tokens"] if rows is None else batch["tokens"][rows]
        loss, g = _loss_and_grads(p, tokens)
        out["loss"].append(float(loss))
        out["terms"].append({"total_loss": float(loss),
                             "token_loss": float(loss)})
        for k in p:
            mu[k] = B1 * mu[k] + (1 - B1) * g[k]
            nu[k] = B2 * nu[k] + (1 - B2) * g[k] ** 2
            p[k] = p[k] - hyper["learning_rate"] * (
                mu[k] / (1 - B1 ** (s + 1))) / (
                np.sqrt(nu[k] / (1 - B2 ** (s + 1))) + EPS)
        if s == 0:
            out["grad_norm"] = _norms(g)
            out["first_trace_norm"] = _norms(mu)
    out["delta_norm"] = _norms({k: p[k] - p0[k] for k in p})
    return out


def extra_numbers(program, reference):
    p, r = program["terms"][0], reference["terms"][0]
    return {"token_loss_step1":
            abs(p["token_loss"] - r["token_loss"]) / abs(r["token_loss"])}


def train_ops_per_row(spec):
    # the head's matmul, forward + weight gradient + input gradient
    return 3 * 2 * spec["length"] * spec["width"] * spec["vocab"]
'''

TOY_CONFIG = {
    "name": "toy-seq", "source": "this test", "task": "toy_seq",
    "precision": "float32", "batch_per_chip": 4, "reduced": {},
    "overrides": ["TRAIN.BATCH_SIZE_PER_CHIP=4"],
    "model": {"vocab": 32, "width": 8, "length": 12},
    "optimizer": {"learning_rate": 0.01},
}
TOY_MIX = {"name": "toy-rows", "sequences": 64, "length": 12, "vocab": 32}
TOY_WORKLOAD = {
    "name": "toy-seq-train", "config": "toy-seq", "traffic": "toy-rows",
    "chips": 1, "why": "a task that is not the detector's",
    "warmup_steps": 4, "follow_steps": 3, "trace_steps": 3,
    # float32 jax against float64 numpy: rounding only
    "limits": {"loss_step1": 1e-5, "loss_step2": 1e-5, "loss_step3": 1e-5,
               "token_loss_step1": 1e-5, "first_grad_worst_leaf": 1e-4,
               "delta3_worst_leaf": 1e-3, "frozen_moved": 0.0},
}


def _dump(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        if isinstance(obj, str):
            f.write(obj)
        else:
            json.dump(obj, f)


@pytest.fixture
def tree(tmp_path, monkeypatch):
    """A checkout's worth of NEW files for the toy task; the tasks
    package also looks in the tree's ``benchmark/tasks``, as it would
    find a file a later PR adds beside ``detection.py``."""
    root = str(tmp_path)
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench_smoke.ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    mfu = next(m for m in real["per_layer"] if m["name"] == "step_mfu_pct")
    manifest = {
        "command": real["command"], "paths": ["benchmark"],
        "run_seconds": real["run_seconds"],
        "configs": [{"name": "toy-seq", "source": "this test",
                     "file": "benchmark/configs/toy-seq.json",
                     "reduced": [], "why": "a second task"}],
        "workloads": [{k: TOY_WORKLOAD[k] for k in
                       ("name", "config", "traffic", "chips", "why")}],
        "end_to_end": real["end_to_end"],
        "per_layer": [dict(mfu, workloads=["toy-seq-train"])],
    }
    _dump(os.path.join(root, "BENCHMARK.json"), manifest)
    _dump(os.path.join(bench, "configs", "toy-seq.json"), TOY_CONFIG)
    _dump(os.path.join(bench, "mixes", "toy-rows.json"), TOY_MIX)
    _dump(os.path.join(bench, "workloads", "toy-seq-train.json"),
          TOY_WORKLOAD)
    _dump(os.path.join(bench, "tasks", "toy_seq.py"), TOY_TASK)
    monkeypatch.setattr(tasks, "__path__", list(tasks.__path__)
                        + [os.path.join(bench, "tasks")])
    yield root
    sys.modules.pop("benchmark.tasks.toy_seq", None)
    vars(tasks).pop("toy_seq", None)


class ToyTrainer:
    """What ``StepTap`` taps, with nothing of the detector's: a jitted
    Adam step on the toy model."""

    def __init__(self, task, spec, hyper, seed):
        import jax
        import jax.numpy as jnp
        import optax

        tx = optax.adam(hyper["learning_rate"])
        params = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32),
                              task.init_params(spec, seed))
        self.state = types.SimpleNamespace(params=params,
                                           opt_state=tx.init(params))

        def loss_fn(p, tokens):
            logits = p["embed"][tokens[:, :-1]] @ p["head"]
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, tokens[:, 1:]).mean()

        @jax.jit
        def step(params, opt_state, tokens):
            loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        def jit_step(state, batch):
            p, o, loss = step(state.params, state.opt_state,
                              batch["tokens"])
            return (types.SimpleNamespace(params=p, opt_state=o),
                    {"total_loss": loss, "token_loss": loss})

        self.jit_step = jit_step

    def _step_fn_with_prediction(self, jit_step, state, batch):
        return jit_step


def _program_readings(cell, batches, seed, broken=False):
    trainer = ToyTrainer(cell.task, cell.spec, cell.hyper, seed)
    tap = harness.StepTap(trainer, len(batches), cell.task.first_moment)
    jit_step = trainer.jit_step
    if broken:                      # the step returns its state unchanged
        def jit_step(state, batch):
            return state, trainer.jit_step(state, batch)[1]
    state = trainer.state
    for batch in batches:
        step = trainer._step_fn_with_prediction(jit_step, state, batch)
        state, _ = step(state, batch)
    tap.remove()
    return tap.readings()


def test_a_toy_task_runs_every_step_short_of_the_trainer(tree):
    cell = harness.load_cell(tree, "toy-seq-train")
    assert cell.task.__name__ == "benchmark.tasks.toy_seq"
    assert cell.task.__file__.startswith(tree)
    assert INTERFACE <= set(vars(cell.task))
    assert "canvas" not in cell.spec and "momentum" not in cell.hyper
    assert cell.hyper == {"learning_rate": 0.01, "global_batch": 4}
    assert [m["name"] for m in cell.per_layer] == ["step_mfu_pct"]

    # the loader's first batches, through the program's config
    cfg = harness.program_config(cell, 11, str(tree), False)
    assert cell.task.spec_mismatches(cfg, cell.spec, cell.hyper) == []
    batches = harness.first_batches(cell, 11, 3)
    assert [b["tokens"].shape for b in batches] == [(4, 13)] * 3
    assert not np.array_equal(batches[0]["tokens"], batches[1]["tokens"])

    # the tap reads Adam's mu; the reference follows the same batches
    seed = 11
    program = _program_readings(cell, batches, seed)
    reference = cell.task.reference_steps(cell.spec, cell.hyper, seed,
                                          batches)
    assert all(not k.startswith("rpn_") for t in program["terms"]
               + reference["terms"] for k in t)
    assert set(program["first_trace_norm"]) == {"embed", "head"}
    # mu after one step is (1 - b1) x gradient
    for k, g in reference["grad_norm"].items():
        assert program["first_trace_norm"][k] == pytest.approx(0.1 * g,
                                                               rel=1e-4)
    values, where = compare.numbers(program, reference,
                                    cell.task.extra_numbers)
    assert "token_loss_step1" in values and "rpn_loss_step1" not in values
    ok, rows = compare.judge(values, cell.workload["limits"])
    assert ok, rows
    assert len(rows) == len(cell.workload["limits"])

    # a state left unchanged reads 1 by the leaf measures, as in any task
    stuck, _ = compare.numbers(
        _program_readings(cell, batches, seed, broken=True), reference,
        cell.task.extra_numbers)
    ok, _ = compare.judge(stuck, cell.workload["limits"])
    assert not ok
    assert stuck["delta3_worst_leaf"] == stuck["first_grad_worst_leaf"] == 1.0
    # and the half-batch fault's rows reach the task's reference
    half = cell.task.reference_steps(cell.spec, cell.hyper, seed, batches,
                                     rows=[0, 1])
    assert half["loss"][0] != reference["loss"][0]

    # the whole-step share is priced by the task
    ctx = harness.TraceContext(
        spec=cell.spec, task=cell.task, chips=1, images_per_step=4,
        images_per_sec_per_chip=1000.0, window_s=1.0, window_steps=250,
        traced_steps=0, feature_itemsize=4, peak=bench_smoke.CPU_PEAK)
    ops = 6 * 12 * 8 * 32
    assert step_mfu_pct.read(ctx) == pytest.approx(100 * ops * 1000 / 1e12)
    assert harness.read_per_layer(cell, ctx) == {
        "step_mfu_pct": {"value": step_mfu_pct.read(ctx), "unit": "%"}}


@pytest.mark.parametrize("change,says", [
    (lambda c: c.pop("task"), 'no "task" key'),
    (lambda c: c.update(task="nowhere"), "benchmark/tasks/nowhere.py"),
], ids=["no_task_key", "no_module"])
def test_a_missing_or_unknown_task_names_the_configurations_file(
        tree, change, says):
    path = os.path.join(tree, "benchmark", "configs", "toy-seq.json")
    config = dict(TOY_CONFIG)
    change(config)
    _dump(path, config)
    with pytest.raises(KeyError) as e:
        harness.load_cell(tree, "toy-seq-train")
    assert path in str(e.value) and says in str(e.value)


def test_a_task_modules_own_missing_import_is_not_hidden(tree):
    _dump(os.path.join(tree, "benchmark", "tasks", "toy_seq.py"),
          "import no_such_module_anywhere\n")
    with pytest.raises(ModuleNotFoundError, match="no_such_module"):
        harness.load_cell(tree, "toy-seq-train")


def _imports(path):
    with open(path) as f:
        mod = ast.parse(f.read())
    out = set()
    for node in ast.walk(mod):
        if isinstance(node, ast.Import):
            out |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            base = "." * node.level + (node.module or "")
            out |= {base} | {f"{base}.{a.name}" for a in node.names}
    return out


@pytest.mark.parametrize("name", ["harness.py", "compare.py", "control.py"])
def test_the_general_files_know_no_task(name):
    path = os.path.join(bench_smoke.ROOT, "benchmark", name)
    task_only = ("benchmark.reference", "benchmark.flops", "eksml_tpu.data")
    assert not [i for i in _imports(path) if i.startswith(task_only)]
    with open(path) as f:
        text = f.read()
    for word in ("DetectionLoader", "rpn_", "canvas", "train_ops_per_image"):
        assert word not in text, (name, word)


def test_the_interface_is_the_six_functions():
    """So that it cannot grow unnoticed: a task module's functions are
    the six that the harness and the control call."""
    own = {n for n, f in vars(detection).items()
           if inspect.isfunction(f) and f.__module__ == detection.__name__}
    assert own == INTERFACE
    for name in INTERFACE:
        assert name in (tasks.__doc__ or "")
    both = [bench_smoke.smoke_cell(mask) for mask in (True, False)]
    assert all(c.task is detection for c in both)
    assert all(c.config["task"] == "detection" for c in both)


def test_detections_own_number():
    terms = [{"rpn_cls_loss": 0.5, "rpn_box_loss": 0.25}]
    off = [{"rpn_cls_loss": 0.5, "rpn_box_loss": 0.28}]
    got = detection.extra_numbers({"terms": off}, {"terms": terms})
    assert got == {"rpn_loss_step1": pytest.approx(0.04),
                   "rpn_box_loss_step1": pytest.approx(0.12)}
    assert detection.extra_numbers({"terms": []}, {"terms": terms}) == {}
    values, _ = compare.numbers(
        {"loss": [1.0], "terms": off, "first_trace_norm": {"a": 1.0},
         "delta_norm": {"a": 1.0}},
        {"loss": [1.0], "terms": terms, "grad_norm": {"a": 1.0},
         "first_trace_norm": {"a": 1.0}, "delta_norm": {"a": 1.0}},
        detection.extra_numbers)
    assert list(values)[:3] == ["loss_step1", "rpn_loss_step1",
                                "rpn_box_loss_step1"]


# ``rpn_box_loss_step1`` at the cells' own size (chip runs of PR 32
# after the check's refusal; PERF.md section 4): the largest a sound
# run read over the 43 seeds of both cells (the RPN's first step is the
# same in both), and the least that half a batch left out read on 11.
BOX_SOUND_MAX = 0.00483
BOX_HALF_BATCH_LEAST = 0.0417
# ``rpn_loss_step1`` read 0.00809 (seed 1126389669, both cells) and
# 0.00711 (seed 32304) in sound runs, where half a batch reads 0.0080 at
# the least: no limit lies between, so none is set.
RPN_SOUND_MAX, RPN_HALF_BATCH_LEAST = 0.00809, 0.0080


@pytest.mark.parametrize("name", ["mask-r50-train-1344-b4",
                                  "frcnn-r50-train-1344-b4"])
def test_the_detector_cells_hold_the_box_term_between_its_readings(name):
    limits = harness.load_cell(bench_smoke.ROOT, name).workload["limits"]
    limit = limits["rpn_box_loss_step1"]
    assert 3 * BOX_SOUND_MAX < limit < BOX_HALF_BATCH_LEAST / 2
    # the more of the room above the lower reading
    assert limit / BOX_SOUND_MAX > BOX_HALF_BATCH_LEAST / limit
    assert "rpn_loss_step1" not in limits
    assert RPN_SOUND_MAX > RPN_HALF_BATCH_LEAST
    sound = {"rpn_box_loss_step1": BOX_SOUND_MAX}
    half = {"rpn_box_loss_step1": BOX_HALF_BATCH_LEAST}
    only = {"rpn_box_loss_step1": limit}
    assert compare.judge(sound, only)[0]
    assert not compare.judge(half, only)[0]


def test_first_moment_is_the_one_trace_of_sgd_momentum():
    import jax.numpy as jnp
    import optax

    params = {"w": jnp.ones((3,))}
    tx = optax.chain(optax.add_decayed_weights(1e-4),
                     optax.trace(decay=0.9), optax.scale(-0.1))
    _, state = tx.update({"w": jnp.full((3,), 2.0)}, tx.init(params), params)
    np.testing.assert_allclose(detection.first_moment(state)["w"],
                               2.0 + 1e-4)
    with pytest.raises(RuntimeError, match="found 0"):
        detection.first_moment(optax.adam(1e-3).init(params))
