"""The sequence model (eksml_tpu/models/lm) against the benchmark's
plain reference at the tiny preset on the CPU in float32: the key rule
of its weights, attention block by block against full scores, the
expert layer against the dense-gates reference (routing sets, nothing
dropped, the share adds up), the chunked losses, and what decays."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference.lm import model as ref
from eksml_tpu.config import LM_TINY_OVERRIDES, finalize_configs
from eksml_tpu.models.lm import attention, moe
from eksml_tpu.models.lm import model as lm_model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def tiny_cfg(fresh_config):
    fresh_config.update_args(list(LM_TINY_OVERRIDES)
                             + ["TRAIN.BATCH_SIZE_PER_CHIP=2"])
    return finalize_configs(is_training=True)


def tiny_spec(cfg, **changes):
    """The reference's spec for the program's tiny config: the real
    configuration file's model block with the tiny sizes."""
    lm = cfg.LM
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "joyai-llm-flash-ep16.json")) as f:
        spec = json.load(f)["model"]
    spec.update(
        hidden_size=lm.HIDDEN_SIZE, num_attention_heads=lm.NUM_HEADS,
        q_lora_rank=lm.Q_LORA_RANK, kv_lora_rank=lm.KV_LORA_RANK,
        qk_nope_head_dim=lm.QK_NOPE_HEAD_DIM,
        qk_rope_head_dim=lm.QK_ROPE_HEAD_DIM, v_head_dim=lm.V_HEAD_DIM,
        intermediate_size=lm.INTERMEDIATE_SIZE,
        moe_intermediate_size=lm.MOE_INTERMEDIATE_SIZE,
        n_routed_experts=lm.N_ROUTED_EXPERTS,
        num_experts_per_tok=lm.NUM_EXPERTS_PER_TOK,
        layers_held=lm.NUM_LAYERS, experts_held=list(lm.EXPERTS_HELD),
        vocab_rows=lm.VOCAB_ROWS, seq_len=lm.SEQ_LEN)
    spec.update(changes)
    return spec


def tokens_of(cfg, seed=0, rows=2):
    return np.random.RandomState(seed).randint(
        0, cfg.LM.VOCAB_ROWS, (rows, cfg.LM.SEQ_LEN + 1)).astype(np.int32)


def test_reference_remakes_the_programs_initial_weights(tiny_cfg):
    """flax's key rule restated (every module holds one parameter, so
    each key is the root folded with the module's path): bit-equal
    weights, norm scales one, the routing bias drawn and held."""
    model = lm_model.JoyAIFlash.from_config(tiny_cfg)
    rng = jax.random.PRNGKey(5)
    batch = {"tokens": tokens_of(tiny_cfg)}
    got = jax.jit(lambda r, b: model.init(r, b, r)["params"])(rng, batch)
    want = ref.init_params(tiny_spec(tiny_cfg), 5)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(path))
    bias = got["block1"]["moe"]["router_bias"]["bias"]
    assert 0.003 < float(jnp.std(bias)) < 0.03


@pytest.mark.parametrize("block", [8, 16, 64])
def test_blockwise_attention_equals_full_scores(block):
    """Running maxima and sums over blocks of keys give what the S x S
    softmax gives, values and gradients, for a value width of its own."""
    rng = np.random.RandomState(1)
    q, k = (jnp.asarray(rng.normal(size=(2, 64, 3, 24)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(2, 64, 3, 16)), jnp.float32)
    full = attention.full_scores_attention(q, k, v)
    got = attention.blockwise_attention(q, k, v, block)
    np.testing.assert_allclose(got, full, atol=2e-5)

    def loss(fn, *a):
        return jnp.sum(jnp.sin(fn(*a)))

    g_full = jax.grad(lambda *a: loss(attention.full_scores_attention, *a),
                      argnums=(0, 1, 2))(q, k, v)
    g_got = jax.grad(lambda *a: loss(
        lambda *b: attention.blockwise_attention(*b, block), *a),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_got, g_full):
        np.testing.assert_allclose(a, b, atol=5e-5)
    # causal: the first position attends to itself alone
    np.testing.assert_allclose(got[:, 0], v[:, 0], atol=1e-6)


def test_attention_impl_is_chosen_by_platform_and_a_misfit_block_is_an_error():
    assert attention.resolve_impl() == "xla"            # the CPU's
    assert attention.resolve_impl("splash") == "splash"
    x = jnp.zeros((1, 24, 1, 8))
    with pytest.raises(ValueError, match="no multiple"):
        attention.blockwise_attention(x, x, x, 16)


def test_rope_in_place_gives_the_references_scores():
    """Rotating interleaved pairs in place (program) and moving them to
    halves first (reference, the Hugging Face way) permute q and k
    alike: every q.k is the same."""
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.normal(size=(1, 32, 2, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 32, 2, 8)), jnp.float32)
    prog = jnp.einsum("bqhd,bkhd->bhqk", lm_model.rope(q, 1e4),
                      lm_model.rope(k, 1e4))
    want = jnp.einsum("qhd,khd->hqk", ref.rotary(q[0], 1e4),
                      ref.rotary(k[0], 1e4))
    np.testing.assert_allclose(prog[0], want, atol=1e-5)
    # position 0 is not rotated
    np.testing.assert_allclose(lm_model.rope(q, 1e4)[:, 0], q[:, 0])


def _moe_layer(cfg, held, seed=3, force_held=False):
    """(program's MoE module bound to EXPERTS_HELD=held, its params as
    the reference names them, a [2, S, hidden] input)."""
    lm = cfg.LM.clone()
    lm.freeze(False)
    lm.EXPERTS_HELD = tuple(held)
    layer = lm_model.MoE(lm, jnp.float32)
    rng = np.random.RandomState(seed)
    h = jnp.asarray(rng.normal(size=(2, lm.SEQ_LEN, lm.HIDDEN_SIZE)),
                    jnp.float32)
    params = layer.init(jax.random.PRNGKey(seed), h)["params"]
    if force_held:
        # a large bias on the held experts: every token picks only them
        bias = np.full((lm.N_ROUTED_EXPERTS,), -10.0, np.float32)
        bias[held[0]:held[0] + held[1]] = 10.0
        params = jax.tree.map(lambda x: x, params)
        params["router_bias"]["bias"] = jnp.asarray(bias)
    return layer, params, h


def test_routing_sets_and_the_expert_layer_equal_the_reference(tiny_cfg):
    layer, params, h = _moe_layer(tiny_cfg, (0, 4))
    (out, counters), state = layer.apply({"params": params}, h,
                                         mutable=["intermediates"])
    spec = tiny_spec(tiny_cfg)
    ids, gates = ref.routing(params, h.reshape(-1, h.shape[-1]), spec)
    got_ids = state["intermediates"]["routing"][0]
    np.testing.assert_array_equal(np.sort(got_ids, axis=1),
                                  np.sort(ids, axis=1))
    # gates: 2.5 x score / sum of the selected scores, k a token
    np.testing.assert_allclose(jnp.sum(gates, axis=1), 2.5, rtol=1e-6)
    assert int(jnp.sum(gates > 0)) == ids.size
    want = ref.moe(params, h, spec, None)
    np.testing.assert_allclose(out, want, atol=2e-6)
    held = int(np.sum(np.asarray(ids) < 4))
    assert float(counters["pairs_held"]) == held
    assert float(counters["pairs_dropped"]) == 0.0
    assert float(counters["load_max_over_mean"]) >= 1.0
    # the selection-only bias is held (no gradient); the router's
    # weights train through the gates, as the reference's do
    g, gh = jax.grad(lambda p, x: jnp.sum(
        layer.apply({"params": p}, x)[0]), argnums=(0, 1))(params, h)
    assert float(jnp.max(jnp.abs(g["router_bias"]["bias"]))) == 0.0
    assert float(jnp.max(jnp.abs(g["experts_up"]["kernel"]))) > 0.0
    want_g, gated = jax.grad(lambda p, x: jnp.sum(
        ref.moe(p, x, spec, None)), argnums=(0, 1))(params, h)
    assert float(jnp.max(jnp.abs(want_g["router"]["kernel"]))) > 0.0
    np.testing.assert_allclose(g["router"]["kernel"],
                               want_g["router"]["kernel"], atol=5e-6)
    np.testing.assert_allclose(gh, gated, atol=5e-6)


def test_nothing_is_dropped_when_every_token_picks_only_held_experts(
        tiny_cfg):
    """The worst routing for the sorted buffer: all k x tokens pairs
    are held.  Still the reference's answer, and the counters say so."""
    layer, params, h = _moe_layer(tiny_cfg, (2, 4), force_held=True)
    out, counters = layer.apply({"params": params}, h)
    tokens = h.shape[0] * h.shape[1]
    k = tiny_cfg.LM.NUM_EXPERTS_PER_TOK
    assert float(counters["pairs_held"]) == tokens * k
    assert float(counters["pairs_dropped"]) == 0.0
    want = ref.moe(params, h, tiny_spec(tiny_cfg, experts_held=[2, 4]),
                   None)
    np.testing.assert_allclose(out, want, atol=5e-6)
    # and with no held expert picked at all, the shared expert alone
    layer0, params0, _ = _moe_layer(tiny_cfg, (0, 4), force_held=True)
    bias = -np.asarray(params0["router_bias"]["bias"])
    params0["router_bias"]["bias"] = jnp.asarray(bias)
    out0, counters0 = layer0.apply({"params": params0}, h)
    assert float(counters0["pairs_held"]) == 0.0
    sh = params0["shared"]
    np.testing.assert_allclose(out0, ref.swiglu(
        h, sh["gate"]["kernel"], sh["up"]["kernel"], sh["down"]["kernel"],
        None), atol=2e-6)


def test_the_shares_add_up_to_the_uncut_layer(tiny_cfg):
    """Two chips holding experts [0,4) and [4,8) of one layer: their
    routed parts, with the shared expert counted once, sum to what the
    reference computes with all 8 experts held."""
    n = tiny_cfg.LM.N_ROUTED_EXPERTS
    whole, params, h = _moe_layer(tiny_cfg, (0, n))
    spec = tiny_spec(tiny_cfg, experts_held=[0, n])
    uncut = ref.moe(params, h, spec, None)
    sh = params["shared"]
    shared = ref.swiglu(h, sh["gate"]["kernel"], sh["up"]["kernel"],
                        sh["down"]["kernel"], None)
    total = shared
    pairs = 0.0
    for first in (0, n // 2):
        layer, _, _ = _moe_layer(tiny_cfg, (first, n // 2))
        share = jax.tree.map(lambda x: x, params)
        for bank in ("experts_gate", "experts_up", "experts_down"):
            share[bank] = {"kernel":
                           params[bank]["kernel"][first:first + n // 2]}
        out, counters = layer.apply({"params": share}, h)
        total = total + (out - shared)
        pairs += float(counters["pairs_held"])
    np.testing.assert_allclose(total, uncut, atol=5e-6)
    assert pairs == h.shape[0] * h.shape[1] * tiny_cfg.LM.NUM_EXPERTS_PER_TOK
    np.testing.assert_allclose(whole.apply({"params": params}, h)[0],
                               uncut, atol=5e-6)


def test_losses_are_the_references_and_chunks_change_nothing(tiny_cfg):
    model = lm_model.JoyAIFlash.from_config(tiny_cfg)
    batch = {"tokens": tokens_of(tiny_cfg, seed=4)}
    rng = jax.random.PRNGKey(0)
    params = model.init(rng, batch, rng)["params"]
    got = model.apply({"params": params}, batch, rng)
    want = ref.losses(params, jnp.asarray(batch["tokens"]),
                      tiny_spec(tiny_cfg))
    for term in ("ce_loss", "mtp_loss", "total_loss"):
        np.testing.assert_allclose(got[term], want[term], rtol=2e-6)
    np.testing.assert_allclose(
        got["total_loss"], got["ce_loss"] + 0.3 * got["mtp_loss"],
        rtol=1e-6)
    # three expert layers (two in the trunk, one in the MTP block)
    tokens = 2 * tiny_cfg.LM.SEQ_LEN
    assert 0 < float(got["moe_pairs_held"]) <= 3 * tokens * 2
    assert float(got["moe_pairs_dropped"]) == 0.0
    # the cross-entropy in chunks of positions is the whole one
    rs = np.random.RandomState(0)
    h = jnp.asarray(rs.normal(size=(2, 64, 16)), jnp.float32)
    head = jnp.asarray(rs.normal(size=(16, 40)), jnp.float32)
    targets = jnp.asarray(rs.randint(0, 40, (2, 64)))
    weights = jnp.ones((2, 64)).at[:, -1].set(0.0)
    whole = ref.cross_entropy(h, head, targets, weights, None)
    for chunk in (16, 128, 4096):
        np.testing.assert_allclose(lm_model.chunked_cross_entropy(
            h, head, targets, weights, chunk), whole, rtol=1e-6)
    with pytest.raises(ValueError, match="no multiple"):
        lm_model.chunked_cross_entropy(h, head, targets, weights, 48)


def test_decay_on_matrices_only(tiny_cfg):
    model = lm_model.JoyAIFlash.from_config(tiny_cfg)
    batch = {"tokens": tokens_of(tiny_cfg)}
    rng = jax.random.PRNGKey(0)
    shapes = jax.eval_shape(lambda: model.init(rng, batch, rng)["params"])
    mask = lm_model.decay_mask(shapes)
    flat = {"/".join(p.key for p in path): v for path, v in
            jax.tree_util.tree_flatten_with_path(mask)[0]}
    assert flat["embed/kernel"] and flat["block1/moe/experts_up/kernel"]
    assert flat["block1/moe/router/kernel"]          # a matrix like any
    assert not flat["block1/moe/router_bias/bias"]   # held, never decayed
    assert not any(v for k, v in flat.items() if k.endswith("/scale"))
    want = ref.decay_mask(ref.init_params(tiny_spec(tiny_cfg), 0))
    assert mask == want


def test_route_gates_follow_the_published_rule():
    """top-k of score + bias; gates from the scores alone, normalised
    over the selected, times the scaling factor."""
    h = jnp.asarray([[1.0, 0.0], [0.0, 1.0]])
    kernel = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [-1.0, 0.0, 1.0, 2.0]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 5.0])      # expert 3 always wins
    ids, gates = moe.route(h, kernel, bias, 2, 2.5)
    assert sorted(np.asarray(ids[0]).tolist()) == [0, 3]
    assert sorted(np.asarray(ids[1]).tolist()) == [2, 3]
    s = jax.nn.sigmoid(jnp.asarray([2.0, -1.0]))
    order = np.argsort(np.asarray(ids[0]))
    np.testing.assert_allclose(np.asarray(gates[0])[order],
                               2.5 * s / jnp.sum(s), rtol=1e-6)


def test_splash_kernel_in_the_interpreter_equals_full_scores():
    """The TPU's path (jax's splash-attention kernel, the cell's block
    sizes cut to the sequence, fused backward) run in Pallas's
    interpreter at the published head widths: values and gradients of
    the S x S softmax, and one cached kernel object serves two traces."""
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.normal(size=(1, 256, 2, 192)) * 0.1, jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 256, 2, 192)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 256, 2, 128)), jnp.float32)

    def splash(q, k, v):
        return attention.causal_attention(q, k, v, 64, impl="splash")

    got = jax.jit(splash)(q, k, v)
    full = attention.full_scores_attention(q, k, v)
    np.testing.assert_allclose(got, full, atol=1e-5)
    g_got = jax.jit(jax.grad(
        lambda *a: jnp.sum(jnp.sin(splash(*a))), argnums=(0, 1, 2)))(q, k, v)
    g_full = jax.grad(lambda *a: jnp.sum(jnp.sin(
        attention.full_scores_attention(*a))), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_got, g_full):
        np.testing.assert_allclose(a, b, atol=5e-5)
    assert attention._splash_kernel.cache_info().currsize >= 1


def test_permute_rows_transposes_to_the_inverse_gather():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.normal(size=(12, 5)), jnp.float32)
    order = jnp.asarray(rng.permutation(12), jnp.int32)
    inverse = jnp.zeros((12,), jnp.int32).at[order].set(
        jnp.arange(12, dtype=jnp.int32))
    np.testing.assert_array_equal(moe.permute_rows(x, order, inverse),
                                  x[order])
    w = jnp.asarray(rng.normal(size=(12, 5)), jnp.float32)
    got = jax.grad(lambda x: jnp.sum(moe.permute_rows(x, order, inverse)
                                     * w))(x)
    want = jax.grad(lambda x: jnp.sum(x[order] * w))(x)
    np.testing.assert_allclose(got, want, rtol=1e-6)
