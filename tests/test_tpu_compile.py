"""AOT compiles of the Pallas ROIAlign kernels for a DESCRIBED v5e.

The TPU compiler is installed in the sandbox and compiles for a chip
that is described, not attached (``on-chip-measurement`` guide §2.3), so
what Mosaic would refuse on the chip — a misaligned slice, too much
scoped vmem — is refused here at no chip time.  This is the stronger
form of the old on-hardware compile probe: the kernels of the training
main path at the PRODUCTION shapes (batch 4, 1344² FPN levels, C=256,
the box head's 512 ROIs × 7² and the mask head's 128 ROIs × 14², bf16;
batch 1 in f32), forward and backward.

A compile that passes is not a chip run: numeric agreement on the chip
is ``chip_smoke.py``'s kernel phase.

The topology is described inside a module-scoped fixture (only the
xdist worker that is given this file loads libtpu), never at import;
the persistent compile cache is off around these compiles because an
entry written for a described chip cannot be read back without one.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from eksml_tpu.ops.pallas import pallas_batched_multilevel_roi_align

STRIDES = (4, 8, 16, 32)
CANVAS = 1344
CHANNELS = 256
HEADS = {"box": (512, 7), "mask": (128, 14)}  # ROIs/image, out_size
BATCH = {"bfloat16": 4, "float32": 1}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(head, dtype, sharding):
    n, out_size = HEADS[head]
    b = BATCH[dtype]
    feats = tuple(jax.ShapeDtypeStruct(
        (b, CANVAS // s, CANVAS // s, CHANNELS), jnp.dtype(dtype),
        sharding=sharding) for s in STRIDES)
    rois = jax.ShapeDtypeStruct((b, n, 4), jnp.float32,
                                sharding=sharding)
    return feats, rois, out_size


def _roi_align(feats, rois, out_size):
    """The kernels under the scope the program's dispatch gives them
    (``ops/roi_align.dispatch_roi_align``).  An HLO instruction takes
    its name from the innermost scope of its ``op_name``, and a
    transform wraps the OUTERMOST one: with no scope around it the
    backward call would come out as ``transpose_jvp_roi_align_bwd__``,
    which no program path produces."""
    with jax.named_scope("roi_align"):
        return pallas_batched_multilevel_roi_align(
            feats, rois, STRIDES, out_size, 2, 2)


def _kernel_sites(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
KERNEL_NAMES = ("roi_align_fwd", "roi_align_bwd", "roi_align_seed_copy")


def _kernel_names(compiled) -> list:
    """The kernel each ``tpu_custom_call`` instruction of the compiled
    text is named for (``%roi_align_bwd.3 = ..`` -> ``roi_align_bwd``):
    what the profiler's ``XLA Ops`` line, and so the per-kernel
    rooflines, tell the three kernels apart by."""
    names = []
    for line in compiled.as_text().splitlines():
        if ('custom_call_target="tpu_custom_call"' in line
                and "custom-call(" in line):
            names.append(_INSTRUCTION.match(line).group(1)
                         .split(".", 1)[0])
    return names


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("head", ["box", "mask"])
def test_forward_compiles_for_v5e(one_chip, head, dtype):
    feats, rois, out_size = _shapes(head, dtype, one_chip)

    def fwd(fs, r):
        return _roi_align(fs, r, out_size)

    compiled = jax.jit(fwd).lower(feats, rois).compile()
    assert _kernel_sites(compiled) >= 1
    assert set(_kernel_names(compiled)) == {"roi_align_fwd"}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("head", ["box", "mask"])
def test_grad_compiles_for_v5e(one_chip, head, dtype):
    """Forward + the transpose kernel (strips of the f32 accumulators,
    asynchronous write-back): whoever calls the forward kernel gets
    the backward kernel."""
    feats, rois, out_size = _shapes(head, dtype, one_chip)

    def loss(fs, r):
        return _roi_align(fs, r, out_size).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss)).lower(feats, rois).compile()
    # the forward is dead code under grad-of-sum; what must be there is
    # the backward's read-modify-write kernel (plus its HBM laundering)
    assert _kernel_sites(compiled) >= 1
    names = _kernel_names(compiled)
    assert "roi_align_bwd" in names
    assert set(names) <= set(KERNEL_NAMES), names


def test_every_custom_call_carries_its_kernels_name(one_chip):
    """Forward kept alive beside the backward (``value_and_grad`` of a
    loss that returns the pooled features too): no ``tpu_custom_call``
    of the step is left unnamed, and the three kernels come out under
    three names, with the scope the attribution rules match."""
    feats, rois, out_size = _shapes("box", "bfloat16", one_chip)

    def loss(fs, r):
        out = _roi_align(fs, r, out_size)
        return out.astype(jnp.float32).sum(), out

    compiled = jax.jit(jax.value_and_grad(loss, has_aux=True)).lower(
        feats, rois).compile()
    names = _kernel_names(compiled)
    assert set(names) == set(KERNEL_NAMES), names
    # .../jvp(roi_align)/roi_align_fwd/pallas_call: the kernel's name
    # is the innermost scope, and the attribution's roi rule still
    # matches the path
    from eksml_tpu.profiling.attribution import SCOPE_RULES

    roi_rule = re.compile(dict((c, p) for c, p, _ in SCOPE_RULES)["roi"])
    paths = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert paths and all(roi_rule.search(p) for p in paths), paths
    assert any(p.endswith("/roi_align_fwd/pallas_call") for p in paths)


@pytest.fixture(scope="module")
def four_chip_mesh(topo):
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))


@pytest.mark.parametrize("head", ["box", "mask"])
def test_dispatch_compiles_on_a_four_chip_mesh(four_chip_mesh,
                                               monkeypatch, head):
    """The data-parallel program: batch 4 split over the (4, 1) mesh.
    XLA's SPMD partitioner refuses a bare Mosaic kernel there; under
    ``batch_partition`` (what ``ShardingPlan.jit`` declares) the
    dispatch runs the kernel once per batch shard and the program
    compiles, forward and backward."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from eksml_tpu.ops.pallas import roi_align_kernel
    from eksml_tpu.ops.roi_align import (batch_partition,
                                         dispatch_roi_align)

    # the gate asks jax.default_backend(), the CPU here
    monkeypatch.setattr(roi_align_kernel.jax, "default_backend",
                        lambda: "tpu")
    spec = P(("data", "model"))
    feats, rois, out_size = _shapes(
        head, "bfloat16", NamedSharding(four_chip_mesh, spec))

    def loss(fs, r):
        return dispatch_roi_align(fs, r, STRIDES, out_size).astype(
            jnp.float32).sum()

    def declared(fs, r):
        with batch_partition(four_chip_mesh, spec):
            return jax.value_and_grad(loss)(fs, r)

    compiled = jax.jit(declared).lower(feats, rois).compile()
    assert _kernel_sites(compiled) >= 2  # forward and backward
    with pytest.raises(NotImplementedError, match="partitioned"):
        jax.jit(loss).lower(feats, rois).compile()


# ---- the sequence model's kernels (models/lm) at the published widths


def test_lm_attention_core_compiles_for_v5e(one_chip, monkeypatch):
    """jax's splash-attention kernel, forward and the fused backward
    kernel, at the cell's shapes: 2 rows x 4096 positions, 32 heads,
    q/k 192 wide and v 128 (a value width of its own), bf16.  The
    kernels keep the names the roofline readers look for."""
    from eksml_tpu.models.lm import attention

    # the gate asks jax.default_backend(), the CPU here: compile the
    # kernel itself, not the interpreter
    monkeypatch.setattr(attention.jax, "default_backend", lambda: "tpu")
    attention._splash_kernel.cache_clear()

    def s(width):
        return jax.ShapeDtypeStruct((2, 4096, 32, width), jnp.bfloat16,
                                    sharding=one_chip)

    def loss(q, k, v):
        with jax.named_scope("mla_core"):
            o = attention.causal_attention(q, k, v, 512)
        return o.astype(jnp.float32).sum()

    try:
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            s(192), s(192), s(128)).compile()
    finally:
        attention._splash_kernel.cache_clear()
    names = set(_kernel_names(compiled))
    # one fused backward kernel (dq, dk, dv) beside the forward
    assert names == {"splash_mha_fwd_residuals",
                     "splash_mha_dkv_no_residuals"}, names


def test_looped_attention_core_compiles_for_v5e(one_chip, monkeypatch):
    """The same kernel and the same block sizes at the looped model's
    widths: 2 rows x 4096 positions, 16 heads, q, k and v all 128 wide,
    bf16 (``_splash_kernel``'s one choice serves both lm models: PERF.md
    section 6, PR 33).  The kernels keep the names the ``loop_`` roofline
    readers look for."""
    from eksml_tpu.models.lm import attention

    monkeypatch.setattr(attention.jax, "default_backend", lambda: "tpu")
    attention._splash_kernel.cache_clear()
    s = jax.ShapeDtypeStruct((2, 4096, 16, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        with jax.named_scope("loop_attn_core"):
            o = attention.causal_attention(q, k, v, 512)
        return o.astype(jnp.float32).sum()

    try:
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            s, s, s).compile()
    finally:
        attention._splash_kernel.cache_clear()
    assert set(_kernel_names(compiled)) == {
        "splash_mha_fwd_residuals", "splash_mha_dkv_no_residuals"}


@pytest.mark.parametrize("heads, window", [(48, None), (64, 512)])
def test_grouped_window_attention_cores_compile_for_v5e(one_chip,
                                                        monkeypatch, heads,
                                                        window):
    """Laguna's two cores at the cell's shapes: 2 rows x 8192 positions,
    48 query heads full causal and 64 under a 512 window, over 8
    key-value heads of 128, bf16: the grouped (``mqa``) kernels, forward
    and the fused backward, one key-value head a group of query heads.
    The kernels keep the names the ``swa_`` roofline readers look for."""
    from eksml_tpu.models.lm import attention

    monkeypatch.setattr(attention.jax, "default_backend", lambda: "tpu")
    attention._splash_kernel.cache_clear()

    def s(h):
        return jax.ShapeDtypeStruct((2, 8192, h, 128), jnp.bfloat16,
                                    sharding=one_chip)

    def loss(q, k, v):
        with jax.named_scope("gqa_core_window" if window
                             else "gqa_core_full"):
            o = attention.causal_attention(q, k, v, 512, window=window)
        return o.astype(jnp.float32).sum()

    try:
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            s(heads), s(8), s(8)).compile()
    finally:
        attention._splash_kernel.cache_clear()
    assert set(_kernel_names(compiled)) == {
        "splash_mqa_fwd_residuals", "splash_mqa_dkv_no_residuals"}


def test_lm_grouped_product_compiles_for_v5e(one_chip):
    """The expert layer's routed part at the cell's size: 8192 tokens x
    8 pairs through 16 held experts of 2048 x 768.  XLA lowers
    ``ragged_dot`` to its grouped Mosaic kernel (work follows the group
    sizes), named ``ragged-dot-none*``: forward, input and weight
    gradients alike."""
    from eksml_tpu.models.lm import moe

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(h, ids, gates, wg, wu, wd):
        out, _ = moe.held_experts(h, ids, gates, wg, wu, wd, 0)
        return out.astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 3, 4, 5))).lower(
        s((8192, 2048)), s((8192, 8), jnp.int32),
        s((8192, 8), jnp.float32), s((16, 2048, 768)),
        s((16, 2048, 768)), s((16, 768, 2048))).compile()
    names = _kernel_names(compiled)
    # 2 forward products feed the gradient (the third's value is dead
    # code under grad-of-sum), 3 input-gradient and 3 weight-gradient
    assert names.count("ragged-dot-none") == 8, names
    assert "ragged-dot-metadata" in names
