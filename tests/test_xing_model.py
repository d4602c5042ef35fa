"""Xing4.0 (eksml_tpu/models/lm/xing.py, mhc.py) against the benchmark's
plain reference (benchmark/reference/mhc_lm) at the tiny preset on the
CPU: bit-equal initial weights, the loss and every leaf's gradient in
float32 and bfloat16, three AdamW steps, Sinkhorn's projection and its
gradient, YaRN's table and the softmax factor against DeepSeek-V3's
formulas, the eight shares of one expert layer against the uncut layer,
JoyAI's ``MLA`` unchanged at its defaults, and the configuration's
rules.

Tolerances, float32: both sides from equal weights, so a gap is the
order of summation (blockwise against full-score attention, the grouped
product against one expert after another, chunked against whole
logits, a fused stream mix against an einsum).  Seen: 1e-7 on the loss,
2e-5 on the worst gradient leaf of the block, 1.3e-4 on the maps' own
leaves, whose gradients are 1e-7 to 1e-4 of the largest leaf's (the four
streams start as copies of one embedding, so the maps' effect is the
small difference between them); held to 5e-6 on the loss and, leaf by
leaf, 2e-5 of the leaf's norm plus 1e-8 of the largest leaf's.
"""

import hashlib
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.reference.mhc_lm import model as ref, train as ref_train
from benchmark.tasks import mhc_lm as task
from eksml_tpu import models
from eksml_tpu.config import (LM_TINY_OVERRIDES, XING_TINY_OVERRIDES,
                              finalize_configs)
from eksml_tpu.models.lm import mhc, model as lm_model, xing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOSS_RTOL, GRAD_RTOL, GRAD_FLOOR = 5e-6, 2e-5, 1e-8


def tiny_cfg(config, *more):
    config.update_args(list(XING_TINY_OVERRIDES)
                       + ["TRAIN.BATCH_SIZE_PER_CHIP=2"] + list(more))
    return finalize_configs(is_training=True)


def load_config_file():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "xing4.0-29b-a4b-ep8.json")) as f:
        return json.load(f)


def tiny_spec(cfg, **changes):
    """The reference's spec for the program's tiny config: the real
    configuration file's model block with the tiny sizes."""
    lm = cfg.LM
    spec = load_config_file()["model"]
    spec.update(
        hidden_size=lm.HIDDEN_SIZE, num_attention_heads=lm.NUM_HEADS,
        num_key_value_heads=lm.NUM_HEADS, q_lora_rank=lm.Q_LORA_RANK,
        kv_lora_rank=lm.KV_LORA_RANK, qk_nope_head_dim=lm.QK_NOPE_HEAD_DIM,
        qk_rope_head_dim=lm.QK_ROPE_HEAD_DIM, v_head_dim=lm.V_HEAD_DIM,
        rope_theta=lm.ROPE_THETA,
        rope_scaling=task.rope_scaling(lm.ROPE_SCALING),
        intermediate_size=lm.INTERMEDIATE_SIZE,
        moe_intermediate_size=lm.MOE_INTERMEDIATE_SIZE,
        n_routed_experts=lm.N_ROUTED_EXPERTS,
        num_experts_per_tok=lm.NUM_EXPERTS_PER_TOK,
        routed_scaling_factor=lm.ROUTED_SCALING_FACTOR,
        layers_held=lm.NUM_LAYERS, dense_layers_held=lm.FIRST_K_DENSE,
        experts_held=list(lm.EXPERTS_HELD), vocab_rows=lm.VOCAB_ROWS,
        seq_len=lm.SEQ_LEN, init_std=lm.INIT_STD,
        hc_phi_std=mhc.phi_std(lm.HC_MULT, lm.HIDDEN_SIZE))
    spec.update(changes)
    return spec


def tokens_of(cfg, seed=0, rows=2):
    return np.random.RandomState(seed).randint(
        0, cfg.LM.VOCAB_ROWS, (rows, cfg.LM.SEQ_LEN + 1)).astype(np.int32)


def stirred(params, seed=3):
    """Equal weights on both sides, but not the fresh ones: norm scales
    and the maps' gains off one, the maps' offsets off zero, and the
    sublayers' output projections 40 times larger, so that the streams
    part from one another within the first block and a map left out or
    misread shows in the loss."""
    rng = np.random.RandomState(seed)

    def stir(path, x):
        keys = [p.key for p in path]
        if keys[-1] == "scale":
            return x * jnp.asarray(rng.uniform(0.7, 1.4, x.shape),
                                   jnp.float32)
        if keys[-2:] == ["bias", "bias"]:
            return x + jnp.asarray(rng.normal(size=x.shape) * 0.5,
                                   jnp.float32)
        if keys[-2] in ("o", "down", "experts_down"):
            return x * 40.0
        return x

    return jax.tree_util.tree_map_with_path(stir, params)


def reference_losses(spec):
    def run(params, tokens):
        with jax.default_matmul_precision("highest"):
            return ref.losses(params, tokens, spec)
    return jax.jit(run)


def test_reference_remakes_the_programs_initial_weights(fresh_config):
    """flax's key rule restated: bit-equal weights; two sets of maps a
    block, one ``[4 C, 24]`` projection each at sigma ``(4 C)^-1/2 / 2``,
    gains one and offsets zero; the held routing bias."""
    cfg = tiny_cfg(fresh_config)
    model = models.build_model(cfg)
    assert isinstance(model, xing.Xing)
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
    assert set(got["block0"]) == {"attn", "attn_hc", "attn_norm", "mlp",
                                  "mlp_hc", "mlp_norm"}
    assert "router_bias" in got["block1"]["moe"]
    maps = got["block1"]["mlp_hc"]
    assert {k: v[next(iter(v))].shape for k, v in maps.items()} == {
        "norm": (256,), "phi": (256, 24), "alpha": (3,), "bias": (24,)}
    assert float(jnp.std(maps["phi"]["kernel"])) == pytest.approx(
        0.5 / 16, rel=0.1)
    np.testing.assert_array_equal(maps["alpha"]["scale"], 1.0)
    np.testing.assert_array_equal(maps["bias"]["bias"], 0.0)
    assert all(x.dtype == jnp.float32
               for x in jax.tree_util.tree_leaves(got))


@pytest.mark.parametrize("remat", [True, False])
def test_the_loss_and_every_leafs_gradient_are_the_references(fresh_config,
                                                              remat):
    cfg = tiny_cfg(fresh_config, f"TRAIN.REMAT={remat}")
    spec = tiny_spec(cfg)
    tokens = tokens_of(cfg, seed=4)
    params = stirred(ref.init_params(spec, 7))
    model = models.build_model(cfg)
    got = jax.jit(lambda p, b: model.apply({"params": p}, b, None))(
        params, {"tokens": tokens})
    want = reference_losses(spec)(params, tokens)
    assert set(want) == {"ce_loss", "total_loss"}
    assert {k for k in got if k.endswith("_loss")} == set(want)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=LOSS_RTOL,
                                   err_msg=k)
    # the counters: pairs of the two expert layers, no pair dropped,
    # the projection within 1e-5 of doubly stochastic, streams mixed
    assert 0 < float(got["moe_pairs_held"]) < 2 * 2 * 64 * 2
    assert float(got["moe_pairs_dropped"]) == 0.0
    assert 0.0 < float(got["mhc_sinkhorn_err"]) < 1e-5
    assert 0.25 < float(got["mhc_res_diag"]) < 0.4

    g_got = jax.jit(jax.grad(lambda p: model.apply(
        {"params": p}, {"tokens": tokens}, None)["total_loss"]))(params)
    g_want = jax.jit(jax.grad(
        lambda p: reference_losses(spec)(p, tokens)["total_loss"]))(params)
    _assert_leaves_close(g_got, g_want, GRAD_RTOL, GRAD_FLOOR)


def _assert_leaves_close(got, want, rtol, floor):
    """Leaf by leaf: ``|a - b| <= rtol |b| + floor max_leaf |b|``; every
    trained leaf has a gradient (the held routing bias has none)."""
    flat = jax.tree_util.tree_flatten_with_path(want)[0]
    largest = max(float(np.linalg.norm(b)) for _, b in flat)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            [b for _, b in flat]):
        a, b = np.asarray(a, np.float32), np.asarray(b)
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            assert not np.any(a) and not np.any(b), name
            continue
        assert np.linalg.norm(b) > 0, name
        assert (np.linalg.norm(a - b) <= rtol * np.linalg.norm(b)
                + floor * largest), name


def test_in_bfloat16_the_loss_and_gradient_stay_near_the_references(
        fresh_config):
    """The program computing in bfloat16 (the cell's precision) against
    the float32 reference.  The loss within 5e-5 (seen 5.4e-6: logits of
    a small head).  Every leaf's gradient within 25% of its norm plus
    1e-4 of the largest leaf's, and the median leaf within 3%: a top-2
    that falls differently in bfloat16 for a few of the 128 tokens moves
    the first expert layer's banks, router, norm and maps by 10-18%
    (seen), as the accepted lm cells' worst leaves move on the chip
    (PERF.md section 4); the rest stay near bfloat16's own rounding."""
    cfg = tiny_cfg(fresh_config, "TRAIN.PRECISION=bfloat16")
    spec = tiny_spec(cfg)
    tokens = tokens_of(cfg, seed=4)
    params = stirred(ref.init_params(spec, 7))
    model = models.build_model(cfg)
    assert model.dtype == jnp.bfloat16
    got = jax.jit(lambda p, b: model.apply({"params": p}, b, None))(
        params, {"tokens": tokens})
    want = reference_losses(spec)(params, tokens)
    np.testing.assert_allclose(got["total_loss"], want["total_loss"],
                               rtol=5e-5)
    g_got = jax.jit(jax.grad(lambda p: model.apply(
        {"params": p}, {"tokens": tokens}, None)["total_loss"]))(params)
    g_want = jax.jit(jax.grad(
        lambda p: reference_losses(spec)(p, tokens)["total_loss"]))(params)
    _assert_leaves_close(g_got, g_want, 0.25, 1e-4)
    gaps = [np.linalg.norm(np.asarray(a, np.float32) - b) / np.linalg.norm(b)
            for a, b in zip(jax.tree_util.tree_leaves(g_got),
                            map(np.asarray, jax.tree_util.tree_leaves(g_want)))
            if np.any(b)]
    assert np.median(gaps) < 0.03


def test_three_adamw_steps_follow_the_references(fresh_config):
    """The trainer's optimizer over the program's gradient against the
    reference's hand-written clip and AdamW on the host: the loss of
    each step and every leaf's change after the third."""
    from eksml_tpu.train import make_optimizer

    cfg = tiny_cfg(fresh_config, "TRAIN.WEIGHT_DECAY=0.1",
                   "TRAIN.GRADIENT_CLIP=1.0", "TRAIN.BASE_LR=0.004",
                   "TRAIN.WARMUP_STEPS=10", "TRAIN.WARMUP_INIT_FACTOR=0.1",
                   "TRAIN.ADAM_B2=0.95")
    spec = tiny_spec(cfg)
    hyper = dict(load_config_file()["optimizer"], global_batch=2,
                 weight_decay=0.1, gradient_clip=1.0, base_lr=0.004,
                 warmup_steps=10, warmup_init_factor=0.1)
    batches = [{"tokens": tokens_of(cfg, seed=s)} for s in (1, 2, 3)]
    want = ref_train.run_steps(spec, hyper, 9, batches)

    model = models.build_model(cfg)
    tx, _ = make_optimizer(cfg)
    p0 = ref.init_params(spec, 9)

    @jax.jit
    def step(params, opt_state, batch):
        (loss, _), grads = jax.value_and_grad(
            lambda p: (lambda t: (t["total_loss"], t))(
                model.apply({"params": p}, batch, None)),
            has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    params, opt_state, got = p0, tx.init(p0), []
    for batch in batches:
        params, opt_state, loss = step(params, opt_state, batch)
        got.append(float(loss))
    np.testing.assert_allclose(got, want["loss"], rtol=LOSS_RTOL)
    flat = {"/".join(p.key for p in path): np.asarray(leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                jax.tree.map(lambda a, b: a - b, params, p0))[0]}
    assert set(flat) == set(want["delta_norm"])
    # seen: 1.1e-4 on a map's norm scale (the maps' gradients are the
    # small difference between near-copies of one stream, summed in
    # another order on each side, and Adam's first steps follow their
    # signs), under 1e-5 on every other leaf
    for name, delta in flat.items():
        norm = float(np.sqrt(np.sum(np.square(delta, dtype=np.float64))))
        # Adam moves every trained leaf; the held routing bias stays
        assert (norm == 0.0) == name.endswith("router_bias/bias"), name
        rel = 3e-4 if "_hc/" in name else 3e-5
        assert norm == pytest.approx(want["delta_norm"][name], rel=rel,
                                     abs=1e-12), name


def _init_logits(cfg, positions=4096, seed=0):
    """``H~_res`` of a fresh ``Maps`` on streams of unit RMS: the
    logits Sinkhorn meets at step 0 (standard deviation one half)."""
    layer = mhc.Maps(cfg.LM)
    x = jnp.asarray(np.random.RandomState(seed).normal(
        size=(1, positions, 4, cfg.LM.HIDDEN_SIZE)), jnp.float32)
    params = layer.init(jax.random.PRNGKey(seed), x)["params"]
    phi, v = params["phi"]["kernel"], jax.vmap(lambda r: r / jnp.sqrt(
        jnp.mean(r * r)))(x.reshape(positions, -1))
    return (v @ phi)[:, 8:].reshape(positions, 4, 4)


def test_sinkhorn_projects_the_inits_logits_and_its_gradient_is_exact(
        fresh_config):
    """On logits of the size the init gives, 20 iterations bring every
    row and column sum within 1e-5 of one at every position (the
    denominators' eps of 1e-6 is the floor); with the column step left
    out the columns stay 10% and more off.  The gradient through all 20
    iterations matches central finite differences (float64)."""
    cfg = tiny_cfg(fresh_config)
    logits = _init_logits(cfg)
    assert 0.4 < float(jnp.std(logits)) < 0.6
    m = mhc.sinkhorn(logits, 20, 1e-6)
    for axis in (-1, -2):
        assert float(jnp.max(jnp.abs(jnp.sum(m, axis) - 1))) < 1e-5
    assert float(mhc.res_stats(m)["err"]) < 1e-5
    assert np.all(np.asarray(m) > 0)
    rows_only = jnp.exp(logits) / jnp.sum(jnp.exp(logits), -1,
                                          keepdims=True)
    assert float(mhc.res_stats(rows_only)["err"]) > 0.1

    from jax.test_util import check_grads

    with jax.enable_x64(True):
        z = jnp.asarray(np.asarray(logits[:3]), jnp.float64)
        w = jnp.asarray(np.random.RandomState(1).normal(size=z.shape))
        check_grads(lambda a: jnp.sum(w * mhc.sinkhorn(a, 20, 1e-6)),
                    (z,), order=1, modes=("rev",), atol=1e-7, rtol=1e-6)


def deepseek_yarn(dim, base, factor, original_max, beta_fast, beta_slow):
    """DeepSeek-V3's ``modeling_deepseek.py`` YaRN table, restated:
    ``yarn_find_correction_range`` and ``yarn_linear_ramp_mask``."""
    def correction_dim(rotations):
        return (dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / factor
    return inter * (1 - mask) + extra * mask


def test_yarn_table_and_softmax_factor_are_deepseek_v3s(fresh_config):
    """At the published block (the config's defaults): the table over
    the 64 rotary dimensions, cos and sin unscaled (mscale / mscale_all_dim
    = 1) and the softmax scale 192^-1/2 x (0.1 ln 64 + 1)^2 = 2.0047."""
    lm = fresh_config.LM
    lm.freeze(False)
    lm.ROPE_THETA, lm.QK_ROPE_HEAD_DIM = 10000, 64
    got = xing.rotary(lm)
    lm.freeze()
    want = deepseek_yarn(64, 10000, 64, 4096, 32, 1)
    np.testing.assert_allclose(got["rope_inv_freq"], want, rtol=2e-7)
    assert set(got) == {"rope_inv_freq", "softmax_factor"}
    assert got["softmax_factor"] == pytest.approx(
        (0.1 * math.log(64) + 1) ** 2) == pytest.approx(2.0047397, rel=1e-7)
    spec = load_config_file()["model"]
    inv, factor, softmax = ref.yarn_rotary(spec)
    np.testing.assert_allclose(inv, want, rtol=2e-7)
    assert (factor, softmax) == (1.0, got["softmax_factor"])
    # pairs that turn more than 32 times over 4,096 positions keep
    # 10000^(-2j/64); the slowest are divided by 64; a ramp between
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # (the ramp runs over pairs 10 .. 23: floor and ceil of 10.47 and
    # 22.51, the pairs that turn 32 times and once)
    np.testing.assert_allclose(want[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(want[23:], plain[23:] / 64, rtol=1e-12)
    assert np.all((want[11:23] < plain[11:23])
                  & (want[11:23] > plain[11:23] / 64))
    # mscale_all_dim 0 (DeepSeek-V3's "if mscale_all_dim") keeps the plain
    # softmax scale
    assert xing.mscale(64, 0) == 1.0 and xing.mscale(1, 1) == 1.0


def test_the_rotary_and_the_softmax_factor_reach_the_scores(fresh_config):
    """MLA with the YaRN table and the factor against MLA with JoyAI's
    defaults: the same weights give other outputs, and the reference's
    attention with the same table and factor gives the program's."""
    cfg = tiny_cfg(fresh_config)
    spec = tiny_spec(cfg)
    h = jnp.asarray(np.random.RandomState(2).normal(size=(2, 64, 64)),
                    jnp.float32)
    yarn = lm_model.MLA(cfg.LM, jnp.float32, **xing.rotary(cfg.LM))
    plain = lm_model.MLA(cfg.LM, jnp.float32)
    params = yarn.init(jax.random.PRNGKey(0), h)
    params = jax.tree.map(lambda x: x * 8.0, params)  # sharp scores
    a, b = yarn.apply(params, h), plain.apply(params, h)
    assert float(jnp.max(jnp.abs(a - b))) > 1e-2
    with jax.default_matmul_precision("highest"):
        want = ref.mla(params["params"], h, spec, None)
    np.testing.assert_allclose(a, want, rtol=1e-4, atol=1e-5)


# JoyAI's MLA at LM_TINY_OVERRIDES on fixed inputs: sha256 of the output
# bytes (as float32) of the parent commit's module, CPU, in float32 and
# bfloat16; the new arguments' defaults leave the bits as they were
JOYAI_MLA_DIGEST = {
    "float32": "bcc528652f786d31121e55148ebeb335753bf9ed29f3e809075360f120f1f92d",
    "bfloat16": "00a44b25c5247c3225c07331558cdc438d5cae2c994fb11dc6e8754eadc08acd",
}


def test_joyais_mla_is_unchanged_at_its_defaults(fresh_config):
    fresh_config.update_args(list(LM_TINY_OVERRIDES))
    cfg = finalize_configs(is_training=True)
    h = jnp.asarray(np.random.RandomState(7).normal(size=(2, 64, 64)),
                    jnp.float32)
    for dtype in (jnp.float32, jnp.bfloat16):
        mla = lm_model.MLA(cfg.LM, dtype)
        params = mla.init(jax.random.PRNGKey(3), h)
        y = jax.jit(lambda p, x: mla.apply(p, x))(params, h.astype(dtype))
        digest = hashlib.sha256(
            np.asarray(y.astype(jnp.float32)).tobytes()).hexdigest()
        assert digest == JOYAI_MLA_DIGEST[jnp.dtype(dtype).name]
    # and the plain table handed in explicitly is the default one
    d = cfg.LM.QK_ROPE_HEAD_DIM
    x = jnp.asarray(np.random.RandomState(1).normal(size=(2, 9, 3, d)),
                    jnp.float32)
    table = tuple(float(f) for f in 1.0 / (cfg.LM.ROPE_THETA ** (
        np.arange(0, d, 2, dtype=np.float32) / d)))
    np.testing.assert_array_equal(lm_model.rope(x, cfg.LM.ROPE_THETA),
                                  lm_model.rope(x, 0.0, table))


def _moe_layer(cfg, held, seed=3):
    lm = cfg.LM.clone()
    lm.freeze(False)
    lm.EXPERTS_HELD = tuple(held)
    layer = lm_model.MoE(lm, jnp.float32)
    rng = np.random.RandomState(seed)
    h = jnp.asarray(rng.normal(size=(2, lm.SEQ_LEN, lm.HIDDEN_SIZE)),
                    jnp.float32)
    return layer, layer.init(jax.random.PRNGKey(seed), h)["params"], h


def test_the_eight_shares_add_up_to_the_uncut_layer(fresh_config):
    """The deployment's EP8 at the tiny widths: 64 routed experts, 4 a
    token, eight chips holding 8 each.  Their routed parts, with the
    shared expert counted once, sum to what the reference computes with
    all 64 held; each share alone is the reference's share."""
    cfg = tiny_cfg(fresh_config, "LM.N_ROUTED_EXPERTS=64",
                   "LM.NUM_EXPERTS_PER_TOK=4")
    n = cfg.LM.N_ROUTED_EXPERTS
    whole, params, h = _moe_layer(cfg, (0, n))
    spec = tiny_spec(cfg, experts_held=[0, n])
    with jax.default_matmul_precision("highest"):
        uncut = ref.moe(params, h, spec, None)
        sh = params["shared"]
        shared = ref.swiglu(h, sh["gate"]["kernel"], sh["up"]["kernel"],
                            sh["down"]["kernel"], None)
    total, pairs = shared, 0.0
    for first in range(0, n, 8):
        layer, _, _ = _moe_layer(cfg, (first, 8))
        share = jax.tree.map(lambda x: x, params)
        for bank in ("experts_gate", "experts_up", "experts_down"):
            share[bank] = {"kernel": params[bank]["kernel"][first:first + 8]}
        out, counters = layer.apply({"params": share}, h)
        with jax.default_matmul_precision("highest"):
            np.testing.assert_allclose(out, ref.moe(
                share, h, tiny_spec(cfg, experts_held=[first, 8]), None),
                atol=5e-6)
        total = total + (out - shared)
        pairs += float(counters["pairs_held"])
        assert float(counters["pairs_dropped"]) == 0.0
    np.testing.assert_allclose(total, uncut, atol=1e-5)
    assert pairs == h.shape[0] * h.shape[1] * 4
    np.testing.assert_allclose(whole.apply({"params": params}, h)[0],
                               uncut, atol=5e-6)


def test_the_streams_start_as_copies_and_end_summed(fresh_config):
    """``expand`` copies the embedding into each stream; with ``H_res``
    the identity, ``H_pre`` one-hot and ``H_post`` zero a sublayer reads
    one stream and changes nothing; ``write`` is ``H_res x + H_post^T y``
    entry by entry; ``reduce`` sums the streams."""
    rng = np.random.RandomState(0)
    e = jnp.asarray(rng.normal(size=(2, 5, 8)), jnp.float32)
    x = mhc.expand(e, 4)
    assert x.shape == (2, 5, 4, 8)
    for i in range(4):
        np.testing.assert_array_equal(x[:, :, i], e)
    x = jnp.asarray(rng.normal(size=(2, 5, 4, 8)), jnp.float32)
    pre = jnp.zeros((2, 5, 4)).at[..., 2].set(1.0)
    np.testing.assert_array_equal(mhc.read(x, pre), x[:, :, 2])
    eye = jnp.broadcast_to(jnp.eye(4), (2, 5, 4, 4))
    y = jnp.asarray(rng.normal(size=(2, 5, 8)), jnp.float32)
    np.testing.assert_array_equal(
        mhc.write(x, y, jnp.zeros((2, 5, 4)), eye), x)
    res = jnp.asarray(rng.uniform(size=(2, 5, 4, 4)), jnp.float32)
    post = jnp.asarray(rng.uniform(size=(2, 5, 4)), jnp.float32)
    want = (np.einsum("bsij,bsjc->bsic", res, x)
            + np.asarray(post)[..., None] * np.asarray(y)[:, :, None])
    np.testing.assert_allclose(mhc.write(x, y, post, res), want, rtol=1e-6)
    np.testing.assert_allclose(mhc.reduce(x), np.sum(x, axis=2), rtol=1e-6)


def test_the_copies_maps_res_columns_get_no_exact_gradient(fresh_config):
    """Why the task leaves block 0's attention maps out of
    ``delta3_worst_leaf_past_copies`` (``tasks/mhc_lm.COPIES_MAP``): they
    read four exact copies of the embedding, and in float64 the
    reference's gradient into their ``H_res`` columns of ``phi`` is
    rounding, while the maps after them (up to the last, whose streams
    are summed and whose columns Sinkhorn's last step holds at one) get a
    gradient of their own there, and every map one into its ``H_pre`` /
    ``H_post`` columns."""
    from benchmark.tasks import mhc_lm as mhc_task

    cfg = tiny_cfg(fresh_config)
    spec = tiny_spec(cfg)
    n, layers = cfg.LM.HC_MULT, cfg.LM.NUM_LAYERS
    tokens = jnp.asarray(np.random.RandomState(0).randint(
        2, cfg.LM.VOCAB_ROWS, size=(2, cfg.LM.SEQ_LEN + 1)))
    with jax.enable_x64(True):
        params = jax.tree.map(lambda x: jnp.asarray(x, jnp.float64),
                              ref.init_params(spec, 3))
        grads = jax.grad(lambda p: ref.losses(p, tokens, spec)[
            "total_loss"])(params)
    res, gates = {}, {}
    for b in range(layers):
        for m in ("attn_hc", "mlp_hc"):
            phi = np.asarray(grads[f"block{b}"][m]["phi"]["kernel"])
            res[f"block{b}/{m}/"] = np.linalg.norm(phi[:, 2 * n:])
            gates[f"block{b}/{m}/"] = np.linalg.norm(phi[:, :2 * n])
    between = [k for k in res if k not in (mhc_task.COPIES_MAP,
                                           f"block{layers - 1}/mlp_hc/")]
    assert len(between) == 2 * layers - 2
    assert res[mhc_task.COPIES_MAP] < 1e-6 * min(res[k] for k in between)
    assert min(gates.values()) > 1e3 * min(res[k] for k in between)


def test_decay_on_matrices_only(fresh_config):
    cfg = tiny_cfg(fresh_config)
    params = ref.init_params(tiny_spec(cfg), 1)
    mask = models.decay_mask(cfg)(params)
    flat = {"/".join(p.key for p in path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(mask)[0]}
    assert flat["block1/attn_hc/phi/kernel"] and flat["embed/kernel"]
    assert flat["block2/moe/experts_up/kernel"]
    for leaf in ("block1/attn_hc/alpha/scale", "block1/mlp_hc/bias/bias",
                 "block1/mlp_hc/norm/scale", "block1/moe/router_bias/bias"):
        assert not flat[leaf], leaf
    assert ref.decay_mask(params) == mask


@pytest.mark.parametrize("stray, match", [
    ("LM.NUM_MTP=1", "NUM_MTP"),
    ("LM.HC_RES_CLAMP=(30.0,-30.0)", "HC_RES_CLAMP"),
    ("LM.HC_SINKHORN_ITERS=0", "0"),
    ("LM.ROPE_SCALING.TYPE=longrope", "longrope"),
    ("LM.ROPE_SCALING.MSCALE=0.707", "MSCALE")])
def test_the_configurations_rules(fresh_config, stray, match):
    fresh_config.update_args(list(XING_TINY_OVERRIDES) + [stray])
    with pytest.raises(AssertionError, match=match):
        finalize_configs(is_training=True)


def test_xings_scopes_are_attribution_components():
    from eksml_tpu.profiling.attribution import resolve_component

    for scope, component in (("mhc_pre", "mhc-pre"), ("mhc_post", "mhc-post"),
                             ("mla", "mla-proj"), ("mla_core", "mla-core"),
                             ("moe_route", "moe-route"),
                             ("moe_experts", "moe-experts"),
                             ("dense_mlp", "dense-mlp"),
                             ("lm_loss", "lm-loss")):
        path = f"jit(_train_step)/jvp(Xing)/block1/attn_hc/{scope}/dot"
        assert resolve_component(path) == component
    assert resolve_component(
        "jit(_train_step)/transpose(jvp(Xing))/block1/mhc_post/mul"
    ) == "mhc-post-bwd"
