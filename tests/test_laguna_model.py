"""Laguna (eksml_tpu/models/lm/laguna.py) against the benchmark's plain
reference (benchmark/reference/swa_moe) at the tiny preset on the CPU
in float32: bit-equal initial weights, the loss, the gradient leaf by
leaf, three AdamW steps, the routing sets layer by layer, YaRN's table
against ``transformers`` and against pinned values, the partial
half-split rotation, the gate a head, the eight shares of one expert
layer against the uncut layer, and the configuration's rules.

Tolerances: float32 on both sides from equal weights, so a gap is the
order of summation (blockwise against full-score attention, the grouped
product against one expert after another, chunked against row-wise
logits).  Seen: 1e-7 on the loss, 4e-7 on the worst gradient leaf, 2e-6
on a parameter's change after three steps; held to 5e-6, 2e-5, 1e-4.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.reference.swa_moe import model as ref, train as ref_train
from benchmark.tasks import swa_moe as task
from eksml_tpu import models
from eksml_tpu.config import LAGUNA_TINY_OVERRIDES, finalize_configs
from eksml_tpu.models.lm import laguna, model as lm_model, ouro

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOSS_RTOL, GRAD_RTOL = 5e-6, 2e-5


def tiny_cfg(config, *more):
    config.update_args(list(LAGUNA_TINY_OVERRIDES)
                       + ["TRAIN.BATCH_SIZE_PER_CHIP=2"] + list(more))
    return finalize_configs(is_training=True)


def load_config_file():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "laguna-xs.2-ep8.json")) as f:
        return json.load(f)


def tiny_spec(cfg, **changes):
    """The reference's spec for the program's tiny config: the real
    configuration file's model block with the tiny sizes."""
    lm = cfg.LM
    spec = load_config_file()["model"]
    dense = lm.FIRST_K_DENSE
    spec.update(
        hidden_size=lm.HIDDEN_SIZE, head_dim=lm.HEAD_DIM,
        num_key_value_heads=lm.NUM_KV_HEADS,
        num_attention_heads_per_layer=list(lm.HEADS_PER_LAYER),
        layer_types=list(lm.LAYER_TYPES),
        mlp_layer_types=["dense"] * dense
        + ["sparse"] * (lm.NUM_LAYERS - dense),
        sliding_window=lm.SLIDING_WINDOW,
        rope_parameters={"full_attention": task._rope(lm.ROPE_FULL),
                         "sliding_attention": task._rope(lm.ROPE_WINDOW)},
        intermediate_size=lm.INTERMEDIATE_SIZE,
        moe_intermediate_size=lm.MOE_INTERMEDIATE_SIZE,
        shared_expert_intermediate_size=lm.MOE_INTERMEDIATE_SIZE,
        num_experts=lm.N_ROUTED_EXPERTS,
        num_experts_per_tok=lm.NUM_EXPERTS_PER_TOK,
        layers_held=lm.NUM_LAYERS, experts_held=list(lm.EXPERTS_HELD),
        vocab_rows=lm.VOCAB_ROWS, seq_len=lm.SEQ_LEN,
        init_std=lm.INIT_STD)
    spec.update(changes)
    return spec


def tokens_of(cfg, seed=0, rows=2):
    return np.random.RandomState(seed).randint(
        0, cfg.LM.VOCAB_ROWS, (rows, cfg.LM.SEQ_LEN + 1)).astype(np.int32)


def stirred(params, seed=3):
    """Equal weights on both sides, but not the fresh ones: norm scales
    off one and gates off one half, so that a scale or a gate left out
    would show."""
    rng = np.random.RandomState(seed)

    def stir(path, x):
        if path[-1].key == "scale":
            return x * jnp.asarray(rng.uniform(0.7, 1.4, x.shape),
                                   jnp.float32)
        if path[-2].key == "g":
            return x * 30.0
        return x

    return jax.tree_util.tree_map_with_path(stir, params)


def reference_losses(spec):
    def run(params, tokens):
        with jax.default_matmul_precision("highest"):
            return ref.losses(params, tokens, spec)
    return jax.jit(run)


def test_reference_remakes_the_programs_initial_weights(fresh_config):
    """flax's key rule restated: bit-equal weights; an attention of each
    layer's own head count, a gate's column a head, no routing bias."""
    cfg = tiny_cfg(fresh_config)
    model = models.build_model(cfg)
    assert isinstance(model, laguna.Laguna)
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
    assert set(got) == {"embed", "head", "final_norm", "block0", "block1",
                        "block2"}
    assert set(got["block0"]) == {"attn", "attn_norm", "mlp", "mlp_norm"}
    assert set(got["block1"]["moe"]) == {
        "router", "experts_gate", "experts_up", "experts_down", "shared"}
    shapes = {layer: {k: v["kernel"].shape
                      for k, v in got[f"block{layer}"]["attn"].items()}
              for layer in (0, 1)}
    assert shapes[0] == {"q": (64, 64), "k": (64, 32), "v": (64, 32),
                         "g": (64, 4), "o": (64, 64)}
    assert shapes[1] == {"q": (64, 96), "k": (64, 32), "v": (64, 32),
                         "g": (64, 6), "o": (96, 64)}
    assert 0.9 < float(jnp.std(got["embed"]["kernel"])) < 1.1
    assert 0.015 < float(jnp.std(got["head"]["kernel"])) < 0.025
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
    # and the window's constant (S 64, window 24, blocks of 16)
    assert 0 < float(got["moe_pairs_held"]) < 2 * 2 * 64 * 2
    assert float(got["moe_pairs_dropped"]) == 0.0
    assert float(got["window_tile_share"]) == pytest.approx(
        (24 * 25 // 2 + 40 * 24) / (16 * 16 * (1 + 2 + 3 + 3)))
    assert all(v.dtype == jnp.float32 for v in got.values())

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
    conf = load_config_file()
    hyper = dict(conf["optimizer"], global_batch=2, weight_decay=0.1,
                 gradient_clip=1.0, base_lr=0.004, warmup_steps=10,
                 warmup_init_factor=0.1)
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
    for name, delta in flat.items():
        norm = float(np.sqrt(np.sum(np.square(delta, dtype=np.float64))))
        assert norm == pytest.approx(want["delta_norm"][name],
                                     rel=1e-4), name
        assert norm > 0.0, name


def test_the_routing_sets_are_the_references_layer_by_layer(fresh_config):
    """What each expert layer's router picked in the program
    (``MoE``'s sown ids) against the reference's top-k on the
    reference's own residual stream."""
    cfg = tiny_cfg(fresh_config, "TRAIN.REMAT=False")
    spec = tiny_spec(cfg)
    tokens = tokens_of(cfg, seed=6)
    params = stirred(ref.init_params(spec, 8))
    model = models.build_model(cfg)
    _, state = model.apply({"params": params}, {"tokens": tokens}, None,
                           mutable=["intermediates"])
    x = params["embed"]["kernel"][tokens[:, :-1]]
    with jax.default_matmul_precision("highest"):
        for layer in range(spec["layers_held"]):
            p = params[f"block{layer}"]
            if spec["mlp_layer_types"][layer] == "sparse":
                seen = x + ref.attention(
                    p["attn"], ref.rms_norm(x, p["attn_norm"]["scale"],
                                            1e-6), spec, layer, None)
                h = ref.rms_norm(seen, p["mlp_norm"]["scale"], 1e-6)
                ids, gates = ref.routing(p["moe"],
                                         h.reshape(-1, h.shape[-1]), spec)
                got = state["intermediates"][f"block{layer}"]["moe"][
                    "routing"][0]
                np.testing.assert_array_equal(np.sort(got, axis=1),
                                              np.sort(ids, axis=1))
                np.testing.assert_allclose(jnp.sum(gates, axis=1), 2.5,
                                           rtol=1e-6)
            x = ref.block(p, x, spec, layer, None)
    assert set(state["intermediates"]) == {"block1", "block2"}


def _moe_layer(cfg, held, seed=3):
    lm = cfg.LM.clone()
    lm.freeze(False)
    lm.EXPERTS_HELD = tuple(held)
    layer = lm_model.MoE(lm, jnp.float32, selection_bias=False)
    rng = np.random.RandomState(seed)
    h = jnp.asarray(rng.normal(size=(2, lm.SEQ_LEN, lm.HIDDEN_SIZE)),
                    jnp.float32)
    return layer, layer.init(jax.random.PRNGKey(seed), h)["params"], h


def test_the_eight_shares_add_up_to_the_uncut_layer(fresh_config):
    """Eight chips each holding one eighth of one layer's routed
    experts (the deployment's EP8 at the tiny size: 1 of 8 each): their
    routed parts, with the shared expert counted once, sum to what the
    reference computes with every expert held; no share has a selection
    bias."""
    cfg = tiny_cfg(fresh_config)
    n = cfg.LM.N_ROUTED_EXPERTS
    whole, params, h = _moe_layer(cfg, (0, n))
    assert "router_bias" not in params
    spec = tiny_spec(cfg, experts_held=[0, n])
    uncut = ref.moe(params, h, spec, None)
    sh = params["shared"]
    shared = ref.swiglu(h, sh["gate"]["kernel"], sh["up"]["kernel"],
                        sh["down"]["kernel"], None)
    total, pairs = shared, 0.0
    for first in range(0, n, n // 8):
        layer, _, _ = _moe_layer(cfg, (first, n // 8))
        share = jax.tree.map(lambda x: x, params)
        for bank in ("experts_gate", "experts_up", "experts_down"):
            share[bank] = {"kernel":
                           params[bank]["kernel"][first:first + n // 8]}
        out, counters = layer.apply({"params": share}, h)
        # a share alone is the reference's share
        np.testing.assert_allclose(out, ref.moe(
            share, h, tiny_spec(cfg, experts_held=[first, n // 8]), None),
            atol=5e-6)
        total = total + (out - shared)
        pairs += float(counters["pairs_held"])
        assert float(counters["pairs_dropped"]) == 0.0
    np.testing.assert_allclose(total, uncut, atol=1e-5)
    assert pairs == h.shape[0] * h.shape[1] * cfg.LM.NUM_EXPERTS_PER_TOK
    np.testing.assert_allclose(whole.apply({"params": params}, h)[0],
                               uncut, atol=5e-6)


# transformers 4.57.6 _compute_yarn_parameters for Laguna-XS.2's
# full_attention block (head_dim 128, partial_rotary_factor 0.5): the 32
# frequencies, float32
YARN_PINNED = [
    1.0, 0.663601279258728, 0.44036662578582764, 0.2922278344631195,
    0.193922758102417, 0.12868738174438477, 0.07775502651929855,
    0.04652704298496246, 0.02751009352505207, 0.01602250710129738,
    0.009150584228336811, 0.005088901147246361, 0.002724390709772706,
    0.001374835497699678, 0.0006249547586776316, 0.00022400968009606004,
    2.2097085093264468e-05, 1.466365392843727e-05, 9.730819328979123e-06,
    6.457383733504685e-06, 4.2851279431488365e-06, 2.843616130121518e-06,
    1.8870272242565989e-06, 1.2522335737230605e-06, 8.309837085107574e-07,
    5.514418148777622e-07, 3.659374669950921e-07, 2.428365633022622e-07,
    1.6114664447286486e-07, 1.0693711516296389e-07, 7.096360121749967e-08,
    4.7091532451304374e-08]


def test_yarns_table_is_transformers_and_the_pinned_one(fresh_config):
    """The program's table at the published block (the config's
    defaults), the reference's own formula, the values pinned above and,
    where it is installed, ``transformers``' function itself."""
    full = fresh_config.LM.ROPE_FULL
    inv, factor = laguna.rotary_table(full, 128)
    assert inv.shape == (32,) and inv.dtype == np.float32
    assert factor == 1.4158883083359672 == pytest.approx(
        0.1 * np.log(64.0) + 1.0)
    np.testing.assert_allclose(inv, YARN_PINNED, rtol=2e-6)
    published = load_config_file()["model"]["rope_parameters"]
    np.testing.assert_allclose(
        ref.yarn_frequencies(64, published["full_attention"]), YARN_PINNED,
        rtol=2e-6)
    # dimensions that turn more than 64 times over 4,096 positions keep
    # theta^(-2j/64); the slowest are divided by 64; a ramp between
    plain = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:6], plain[:6], rtol=2e-6)
    np.testing.assert_allclose(inv[16:], plain[16:] / 64, rtol=2e-6)
    assert np.all(inv[6:16] < plain[6:16])
    assert np.all(inv[6:16] > plain[6:16] / 64)
    # the window layers: the plain table over the whole head
    inv_w, none = laguna.rotary_table(fresh_config.LM.ROPE_WINDOW, 128)
    assert none is None and inv_w.shape == (64,)
    np.testing.assert_allclose(
        inv_w, 10000.0 ** (-np.arange(0, 128, 2) / 128), rtol=2e-6)

    rope_utils = pytest.importorskip("transformers.modeling_rope_utils")
    from transformers import PretrainedConfig

    block = published["full_attention"]
    hf = PretrainedConfig(
        rope_theta=block["rope_theta"], head_dim=128, hidden_size=2048,
        num_attention_heads=48, max_position_embeddings=262144,
        partial_rotary_factor=block["partial_rotary_factor"],
        rope_scaling={k: v for k, v in block.items()
                      if k not in ("rope_theta", "partial_rotary_factor")})
    theirs, their_factor = rope_utils._compute_yarn_parameters(hf, "cpu")
    np.testing.assert_allclose(inv, theirs.numpy(), rtol=2e-6)
    assert their_factor == factor
    # and at the tiny preset's block, which the model tests run
    hf.rope_theta, hf.head_dim = 100, 16
    hf.rope_scaling = {"rope_type": "yarn", "factor": 4, "beta_fast": 4,
                       "beta_slow": 1, "attention_factor": 1.1386,
                       "original_max_position_embeddings": 32}
    tiny, _ = rope_utils._compute_yarn_parameters(hf, "cpu")
    np.testing.assert_allclose(
        laguna.yarn_inv_freq(8, 100, 4, 32, 4, 1), tiny.numpy(), rtol=2e-6)
    assert len(set(np.round(tiny.numpy() * 100 ** (np.arange(4) / 4), 4))
               ) == 3       # kept, halfway, interpolated


def test_the_partial_half_split_rotation_is_the_complex_one():
    """(x[j] + i x[j + r/2]) e^{i pos f_j} over the first r dimensions,
    cos and sin scaled, the rest untouched; the reference's rotation
    too; over the whole head with the plain table it is Ouro's."""
    rng = np.random.RandomState(4)
    x = rng.normal(size=(2, 9, 3, 16)).astype(np.float32)
    inv = np.asarray([1.0, 0.3, 0.05, 0.002], np.float32)    # r = 8
    z = x[..., :4].astype(np.complex128) + 1j * x[..., 4:8]
    z = z * np.exp(1j * np.arange(9)[:, None] * inv[None, :])[
        None, :, None, :] * 1.25
    want = np.concatenate([z.real, z.imag, x[..., 8:]], axis=-1)
    got = np.asarray(ouro.rotate_half(jnp.asarray(x), inv, 1.25))
    np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    rope = {"rope_type": "yarn", "rope_theta": 100, "factor": 4,
            "original_max_position_embeddings": 32, "beta_fast": 4,
            "beta_slow": 1, "attention_factor": 1.1386,
            "partial_rotary_factor": 0.5}
    np.testing.assert_allclose(
        ref.rotary(jnp.asarray(x[0]), rope, 16),
        ouro.rotate_half(jnp.asarray(x), laguna.yarn_inv_freq(
            8, 100, 4, 32, 4, 1), 1.1386)[0], atol=2e-6)
    plain = 1.0e6 ** (-np.arange(0, 16, 2, dtype=np.float32) / 16)
    np.testing.assert_allclose(
        ouro.rotate_half(jnp.asarray(x), jnp.asarray(plain)),
        ouro.rope_half(jnp.asarray(x), 1.0e6), atol=2e-6)


def test_the_gate_a_head_scales_the_attention_output(fresh_config):
    """Zero gate weights: every gate one half, the layer's output half
    of the ungated core's through ``o``; drawn weights: head h at
    position i scaled by ``sigmoid(x_i . Wg[:, h])`` and by nothing
    else."""
    cfg = tiny_cfg(fresh_config)
    layer = laguna.Attention(cfg.LM, jnp.float32, layer=1)
    rng = np.random.RandomState(0)
    h = jnp.asarray(rng.normal(size=(1, 64, 64)), jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), h)["params"]
    core = _core(params, h)                           # [1, 64, 6, 16]
    o = params["o"]["kernel"]
    zero = dict(params, g={"kernel": jnp.zeros_like(params["g"]["kernel"])})
    np.testing.assert_allclose(
        layer.apply({"params": zero}, h),
        0.5 * core.reshape(1, 64, 96) @ o, atol=2e-6)
    wide = dict(params, g={"kernel": params["g"]["kernel"] * 40.0})
    gate = jax.nn.sigmoid(h @ wide["g"]["kernel"])    # [1, 64, 6]
    assert float(jnp.min(gate)) < 0.2 and float(jnp.max(gate)) > 0.8
    np.testing.assert_allclose(
        layer.apply({"params": wide}, h),
        (core * gate[..., None]).reshape(1, 64, 96) @ o, atol=2e-6)


def _core(params, h):
    """The ungated attention output ``[B, S, H, D]`` of the tiny
    preset's window layer, through the reference's pieces (a zero gate
    is one half; ``o`` the identity)."""
    spec = {"num_attention_heads_per_layer": [0, 6],
            "num_key_value_heads": 2, "head_dim": 16,
            "layer_types": ["", "sliding_attention"], "sliding_window": 24,
            "rope_parameters": {"sliding_attention": {
                "rope_type": "default", "rope_theta": 10000,
                "partial_rotary_factor": 1.0}}}
    wide = {k: {"kernel": v["kernel"]} for k, v in params.items()}
    wide["g"] = {"kernel": jnp.zeros_like(params["g"]["kernel"])}
    wide["o"] = {"kernel": jnp.eye(96, dtype=jnp.float32)}
    return 2.0 * ref.attention(wide, h, spec, 1, None).reshape(1, 64, 6, 16)


def test_decay_on_matrices_only(fresh_config):
    cfg = tiny_cfg(fresh_config)
    params = ref.init_params(tiny_spec(cfg), 1)
    mask = models.decay_mask(cfg)(params)
    flat = {"/".join(p.key for p in path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(mask)[0]}
    assert flat["block1/attn/g/kernel"] and flat["block1/moe/router/kernel"]
    assert flat["embed/kernel"] and flat["block2/moe/experts_up/kernel"]
    assert not any(v for k, v in flat.items() if k.endswith("scale"))
    assert ref.decay_mask(params) == mask


@pytest.mark.parametrize("stray, match", [
    ("LM.HEADS_PER_LAYER=(4,6)", "one entry"),
    ("LM.LAYER_TYPES=('full_attention','sliding_attention')", "one entry"),
    ("LM.HEADS_PER_LAYER=(4,5,4)", "5"),
    ("LM.LAYER_TYPES=('full_attention','chunked','full_attention')",
     "chunked"),
    ("LM.SLIDING_WINDOW=0", "0"),
    ("LM.ROPE_FULL.TYPE=longrope", "longrope")])
def test_the_configurations_rules(fresh_config, stray, match):
    fresh_config.update_args(list(LAGUNA_TINY_OVERRIDES) + [stray])
    with pytest.raises(AssertionError, match=match):
        finalize_configs(is_training=True)


def test_lagunas_scopes_are_attribution_components():
    from eksml_tpu.profiling.attribution import resolve_component

    for scope, component in (("gqa", "gqa-proj"),
                             ("gqa_core_full", "gqa-core-full"),
                             ("gqa_core_window", "gqa-core-window"),
                             ("moe_route", "moe-route"),
                             ("moe_experts", "moe-experts"),
                             ("dense_mlp", "dense-mlp"),
                             ("lm_loss", "lm-loss")):
        path = f"jit(_train_step)/jvp(Laguna)/block1/attn/{scope}/dot"
        assert resolve_component(path) == component
    assert resolve_component(
        "jit(_train_step)/transpose(jvp(Laguna))/block1/gqa_core_window/x"
    ) == "gqa-core-window-bwd"
