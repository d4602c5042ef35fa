"""Ouro, the looped model (eksml_tpu/models/lm/ouro.py), against the
benchmark's plain reference (benchmark/reference/looplm) at the tiny
preset on the CPU in float32: bit-equal initial weights, every loss
term, the gradient leaf by leaf, what tying the weights means for the
gradient, what one pass reduces to, the exit distribution, the rotary
pairing, the attention core at the published head width, and the
configuration's rules.

Tolerances: float32 on both sides from equal weights, so a gap is the
order of summation (blockwise against full-score attention, chunked
against blocked logits, sums of log-sigmoids against products of
sigmoids).  Seen: 4e-7 on the losses, 9e-7 on the worst gradient leaf;
held to 5e-6 and 2e-5.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference.looplm import model as ref
from eksml_tpu import models
from eksml_tpu.config import (LM_TINY_OVERRIDES, OURO_TINY_OVERRIDES,
                              finalize_configs)
from eksml_tpu.models.lm import attention, model as lm_model, ouro

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOSS_RTOL, GRAD_RTOL = 5e-6, 2e-5


def tiny_cfg(config, *more):
    config.update_args(list(OURO_TINY_OVERRIDES)
                       + ["TRAIN.BATCH_SIZE_PER_CHIP=2"] + list(more))
    return finalize_configs(is_training=True)


def tiny_spec(cfg, **changes):
    """The reference's spec for the program's tiny config: the real
    configuration file's model block with the tiny sizes."""
    lm = cfg.LM
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ouro-2.6b-l6.json")) as f:
        spec = json.load(f)["model"]
    spec.update(
        hidden_size=lm.HIDDEN_SIZE, num_attention_heads=lm.NUM_HEADS,
        num_key_value_heads=lm.NUM_HEADS, head_dim=lm.HEAD_DIM,
        intermediate_size=lm.INTERMEDIATE_SIZE, total_ut_steps=lm.UT_STEPS,
        layers_held=lm.NUM_LAYERS, vocab_rows=lm.VOCAB_ROWS,
        seq_len=lm.SEQ_LEN, exit_entropy_weight=lm.EXIT_ENTROPY_WEIGHT)
    spec.update(changes)
    return spec


def tokens_of(cfg, seed=0, rows=2):
    return np.random.RandomState(seed).randint(
        0, cfg.LM.VOCAB_ROWS, (rows, cfg.LM.SEQ_LEN + 1)).astype(np.int32)


def stirred(params, seed=3):
    """Equal weights on both sides, but not the fresh ones: norm scales
    off one and a gate that prefers some positions, so that a scale left
    out or a gate ignored would show."""
    rng = np.random.RandomState(seed)

    def stir(path, x):
        kind = path[-1].key
        if kind == "scale":
            return x * jnp.asarray(rng.uniform(0.7, 1.4, x.shape),
                                   jnp.float32)
        if kind == "bias":
            return x + 0.3
        if path[-2].key == "gate":
            return x * 20.0
        return x

    return jax.tree_util.tree_map_with_path(stir, params)


def program_losses(cfg):
    model = models.build_model(cfg)
    return jax.jit(lambda p, b: model.apply({"params": p}, b, None))


def reference_losses(spec):
    def run(params, tokens):
        with jax.default_matmul_precision("highest"):
            return ref.losses(params, tokens, spec)
    return jax.jit(run)


@pytest.mark.parametrize("passes", [3, 4])
def test_reference_remakes_the_programs_initial_weights(fresh_config,
                                                        passes):
    """flax's key rule restated: bit-equal weights whatever the number
    of passes (the passes share one set), norm scales one, the gate's
    bias zero and its column drawn."""
    cfg = tiny_cfg(fresh_config, f"LM.UT_STEPS={passes}")
    model = models.build_model(cfg)
    assert isinstance(model, ouro.Ouro)
    rng = jax.random.PRNGKey(5)
    batch = {"tokens": tokens_of(cfg)}
    got = jax.jit(lambda r, b: model.init(r, b, r)["params"])(rng, batch)
    want = ref.init_params(tiny_spec(cfg), 5)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(path))
    assert set(got) == {"embed", "head", "loop"}
    assert set(got["loop"]) == {"block0", "block1", "final_norm", "gate"}
    gate = got["loop"]["gate"]
    assert float(jnp.max(jnp.abs(gate["bias"]))) == 0.0
    assert 0.01 < float(jnp.std(gate["kernel"])) < 0.04
    leaves = jax.tree_util.tree_leaves(got)
    assert all(x.dtype == jnp.float32 for x in leaves)


@pytest.mark.parametrize("passes", [3, 4])
def test_every_loss_term_and_the_gradient_are_the_references(fresh_config,
                                                             passes):
    cfg = tiny_cfg(fresh_config, f"LM.UT_STEPS={passes}")
    spec = tiny_spec(cfg)
    tokens = tokens_of(cfg, seed=passes)
    params = stirred(ref.init_params(spec, 7))
    got = program_losses(cfg)(params, {"tokens": tokens})
    want = reference_losses(spec)(params, tokens)
    terms = ({f"ce_pass{t + 1}_loss" for t in range(passes)}
             | {"expected_ce_loss", "exit_entropy_loss", "total_loss"})
    assert set(want) == terms
    assert {k for k in got if k.endswith("_loss")} == terms
    for k in terms:
        np.testing.assert_allclose(got[k], want[k], rtol=LOSS_RTOL,
                                   err_msg=k)
    # the counters are the terms' own numbers, under the span's names
    p = [float(got[f"loop_exit_p{t + 1}"]) for t in range(passes)]
    assert sum(p) == pytest.approx(1.0, abs=1e-6) and min(p) > 0.01
    assert float(got["exit_entropy_loss"]) == pytest.approx(
        -0.1 * float(got["loop_exit_entropy"]), rel=1e-6)
    assert float(got["loop_ce_pass1"]) == float(got["ce_pass1_loss"])
    assert all(v.dtype == jnp.float32 for v in got.values())

    model = models.build_model(cfg)
    g_got = jax.jit(jax.grad(lambda p: model.apply(
        {"params": p}, {"tokens": tokens}, None)["total_loss"]))(params)
    g_want = jax.jit(jax.grad(
        lambda p: reference_losses(spec)(p, tokens)["total_loss"]))(params)
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(g_got)[0],
            jax.tree_util.tree_leaves(g_want)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.linalg.norm(b) > 0, path
        assert (np.linalg.norm(a - b)
                <= GRAD_RTOL * np.linalg.norm(b)), path


def _untied_total(per_pass, shared, tokens, spec):
    """The loss with pass t running its OWN copy ``per_pass[t]`` of the
    stack (blocks, final norm, gate); embedding and head shared.  The
    reference's pieces, put together pass by pass."""
    eps, passes = spec["rms_norm_eps"], spec["total_ut_steps"]
    h = shared["embed"]["kernel"][tokens[:, :-1]]
    ce, lam = [], []
    for t in range(passes):
        loop = per_pass[t]
        for i in range(spec["layers_held"]):
            h = ref.block(loop[f"block{i}"], h, spec, None)
        h = ref.rms_norm(h, loop["final_norm"]["scale"], eps)
        ce.append(ref.cross_entropies(h, shared["head"]["kernel"],
                                      tokens[:, 1:], None))
        lam.append(jax.nn.sigmoid(
            h @ loop["gate"]["kernel"][:, 0] + loop["gate"]["bias"][0]))
    p, left = [], jnp.ones_like(ce[0])
    for lam_t in lam[:-1]:
        p.append(lam_t * left)
        left = left * (1.0 - lam_t)
    p = jnp.stack(p + [left])
    return jnp.mean(jnp.sum(p * jnp.stack(ce), axis=0)
                    + spec["exit_entropy_weight"]
                    * jnp.sum(p * jnp.log(p), axis=0))


def test_tied_weights_the_gradient_is_the_sum_over_the_passes(fresh_config):
    """Each pass given a copy of the stack with equal weights: the
    program's gradient of a tied leaf is the sum of the copies'
    gradients, pass by pass (and no pass's share is nothing)."""
    cfg = tiny_cfg(fresh_config)
    spec = tiny_spec(cfg)
    passes = spec["total_ut_steps"]
    tokens = tokens_of(cfg, seed=9)
    params = stirred(ref.init_params(spec, 11))
    model = models.build_model(cfg)
    tied = jax.jit(jax.grad(lambda p: model.apply(
        {"params": p}, {"tokens": tokens}, None)["total_loss"]))(params)
    shared = {k: params[k] for k in ("embed", "head")}

    def untied(per_pass):
        with jax.default_matmul_precision("highest"):
            return _untied_total(per_pass, shared, jnp.asarray(tokens), spec)

    per_pass = jax.jit(jax.grad(untied))([params["loop"]] * passes)
    summed = jax.tree.map(lambda *g: sum(g), *per_pass)
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(tied["loop"])[0],
            jax.tree_util.tree_leaves(summed)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.linalg.norm(a - b) <= GRAD_RTOL * np.linalg.norm(b), path
    # every pass of a block matters: no pass's gradient is a rounding
    for t in range(passes):
        share = np.linalg.norm(np.asarray(
            per_pass[t]["block0"]["mlp"]["down"]["kernel"]))
        assert share > 0.05 * np.linalg.norm(np.asarray(
            summed["block0"]["mlp"]["down"]["kernel"])), t
    # the last pass's gate enters nothing
    assert float(jnp.max(jnp.abs(
        per_pass[-1]["gate"]["kernel"]))) == 0.0


def test_one_pass_is_a_plain_decoder(fresh_config):
    """T = 1: the exit distribution is (1), its entropy zero, and the
    total is the one pass's plain cross-entropy, the reference's."""
    cfg = tiny_cfg(fresh_config, "LM.UT_STEPS=1")
    spec = tiny_spec(cfg)
    tokens = tokens_of(cfg, seed=2)
    params = stirred(ref.init_params(spec, 13))
    got = program_losses(cfg)(params, {"tokens": tokens})
    assert float(got["loop_exit_p1"]) == 1.0
    assert float(got["loop_exit_entropy"]) == 0.0
    assert float(got["exit_entropy_loss"]) == 0.0
    assert float(got["total_loss"]) == float(got["ce_pass1_loss"])
    assert float(got["expected_ce_loss"]) == float(got["ce_pass1_loss"])
    assert "ce_pass2_loss" not in got
    want = reference_losses(spec)(params, tokens)
    np.testing.assert_allclose(got["total_loss"], want["total_loss"],
                               rtol=LOSS_RTOL)
    assert models.counter_spans(cfg) == {"loop_exit": (
        "loop_exit_p1", "loop_exit_entropy", "loop_ce_pass1")}


def test_the_exit_distribution_sums_to_one_and_trains_the_gate(
        fresh_config):
    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.normal(size=(4, 3, 5)) * 3.0, jnp.float32)
    p, entropy = ouro.exit_distribution(logits)
    np.testing.assert_allclose(jnp.sum(p, axis=0), 1.0, atol=1e-6)
    lam = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    np.testing.assert_allclose(p[0], lam[0], rtol=1e-6)
    np.testing.assert_allclose(p[2], lam[2] * (1 - lam[0]) * (1 - lam[1]),
                               rtol=1e-5)
    np.testing.assert_allclose(
        p[3], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]), rtol=1e-5)
    p64 = np.asarray(p, np.float64)
    np.testing.assert_allclose(entropy, -np.sum(p64 * np.log(p64), axis=0),
                               rtol=1e-5)
    # far-off logits: no NaN where a probability underflows
    far, h = ouro.exit_distribution(jnp.asarray([[200.0], [-200.0], [0.0]]))
    assert np.isfinite(np.asarray(far)).all() and float(h[0]) == 0.0
    # a fresh gate: (1/2, 1/4, 1/8, 1/8), entropy 1.75 ln 2
    fresh, h0 = ouro.exit_distribution(jnp.zeros((4, 1)))
    np.testing.assert_allclose(fresh[:, 0], [0.5, 0.25, 0.125, 0.125])
    assert float(h0[0]) == pytest.approx(1.75 * np.log(2.0), rel=1e-6)

    # the weights p_t carry gradient to the gate through the expected
    # cross-entropy alone (beta = 0 here)
    cfg = tiny_cfg(fresh_config, "LM.EXIT_ENTROPY_WEIGHT=0.0")
    spec = tiny_spec(cfg)
    params = stirred(ref.init_params(spec, 17))
    model = models.build_model(cfg)
    g = jax.jit(jax.grad(lambda p: model.apply(
        {"params": p}, {"tokens": tokens_of(cfg)}, None)[
            "expected_ce_loss"]))(params)
    assert float(jnp.linalg.norm(g["loop"]["gate"]["kernel"])) > 1e-6
    assert float(jnp.abs(g["loop"]["gate"]["bias"][0])) > 1e-8


def test_half_split_rotary_is_the_complex_rotation_and_not_joyais():
    """(x[j] + i x[j + d/2]) e^{i pos theta^(-2j/d)}, restated with
    complex numbers; ``model.rope`` pairs neighbours and gives another
    vector for the same input."""
    rng = np.random.RandomState(4)
    x = rng.normal(size=(2, 9, 3, 16)).astype(np.float32)
    theta, d = 1.0e6, 16
    z = x[..., :d // 2].astype(np.complex128) + 1j * x[..., d // 2:]
    ang = (np.arange(9)[:, None]
           * theta ** (-np.arange(0, d, 2) / d)[None, :])
    z = z * np.exp(1j * ang)[None, :, None, :]
    want = np.concatenate([z.real, z.imag], axis=-1)
    got = np.asarray(ouro.rope_half(jnp.asarray(x), theta))
    np.testing.assert_allclose(got, want, atol=2e-6)
    other = np.asarray(lm_model.rope(jnp.asarray(x), theta))
    assert np.abs(other - got)[:, 1:].max() > 0.1
    np.testing.assert_array_equal(got[:, 0], x[:, 0])     # position 0
    # it is the reference's rotation too
    np.testing.assert_allclose(
        ref.rotary(jnp.asarray(x[0]), theta), got[0], atol=2e-6)


def test_splash_in_the_interpreter_equals_the_blockwise_path_at_128_wide():
    """The two formulations ``causal_attention`` chooses between, at the
    published head width (q, k and v all 128 wide): values and
    gradients."""
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 256, 2, 128)) * s,
                           jnp.float32) for s in (0.1, 1.0, 1.0))

    def run(impl):
        return lambda *a: attention.causal_attention(*a, 64, impl=impl)

    got, want = jax.jit(run("splash"))(q, k, v), run("xla")(q, k, v)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(
        want, attention.full_scores_attention(q, k, v), atol=1e-5)
    grads = [jax.jit(jax.grad(lambda *a, f=run(impl): jnp.sum(
        jnp.sin(f(*a))), argnums=(0, 1, 2)))(q, k, v)
        for impl in ("splash", "xla")]
    for a, b in zip(*grads):
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_decay_on_matrices_only(fresh_config):
    cfg = tiny_cfg(fresh_config)
    params = ref.init_params(tiny_spec(cfg), 1)
    mask = models.decay_mask(cfg)(params)
    flat = {"/".join(p.key for p in path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(mask)[0]}
    assert flat["loop/gate/kernel"] and not flat["loop/gate/bias"]
    assert flat["embed/kernel"] and flat["loop/block1/mlp/down/kernel"]
    assert not any(v for k, v in flat.items() if k.endswith("scale"))
    assert ref.decay_mask(params) == mask


@pytest.mark.parametrize("name, stray", [
    ("ouro", "LM.Q_LORA_RANK=64"), ("ouro", "LM.EXPERTS_HELD=(0,8)"),
    ("ouro", "LM.NUM_MTP=0"),
    ("joyai_llm_flash", "LM.UT_STEPS=2"),
    ("joyai_llm_flash", "LM.HEAD_DIM=64"),
    ("joyai_llm_flash", "LM.EXIT_ENTROPY_WEIGHT=0.05")])
def test_each_models_keys_are_held_to_it(fresh_config, name, stray):
    """A key only the other sequence model reads, moved from its
    default, is an error named by the key; the detector is not asked."""
    tiny = OURO_TINY_OVERRIDES if name == "ouro" else LM_TINY_OVERRIDES
    fresh_config.update_args(list(tiny) + [stray])
    with pytest.raises(AssertionError, match=stray.split("=")[0]):
        finalize_configs(is_training=True)
    fresh_config.freeze(False)
    fresh_config.MODEL.NAME = "maskrcnn"
    fresh_config.TRAIN.OPTIMIZER = "sgd"
    finalize_configs(is_training=True)


def test_the_looped_scopes_are_attribution_components():
    from eksml_tpu.profiling.attribution import resolve_component

    for scope, component in (("loop_attn", "loop-attn"),
                             ("loop_attn_core", "loop-attn-core"),
                             ("loop_mlp", "loop-mlp"),
                             ("loop_head", "loop-head"),
                             ("loop_exit", "loop-exit")):
        path = f"jit(_train_step)/jvp(Ouro)/loop/while/body/{scope}/dot"
        assert resolve_component(path) == component
    assert resolve_component(
        "jit(_train_step)/transpose(jvp(Ouro))/loop/loop_attn_core/x"
    ) == "loop-attn-core-bwd"
