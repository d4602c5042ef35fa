"""ImageNet-R50-AlignPadding.npz → Flax param tree.

The reference initializes the backbone from
``/efs/data/pretrained-models/ImageNet-R50-AlignPadding.npz``
(charts/maskrcnn/values.yaml:22, templates/maskrcnn.yaml:69;
downloaded at eks-cluster/prepare-s3-bucket.sh:33-34).  That file is a
TensorPack-format flat dict of numpy arrays with keys like::

    conv0/W                      [7,7,3,64]   (HWIO — matches Flax Conv)
    conv0/bn/gamma|beta|mean/EMA|variance/EMA
    group{g}_block{b}/conv{1,2,3}/W  + /bn/...
    group{g}_block{b}/convshortcut/W + /bn/...

This loader maps those keys onto :class:`eksml_tpu.models.resnet.
ResNetBackbone`'s parameter tree.  HWIO conv layout means weights drop
in without transposition.  Missing keys fall back to the initialized
values (so a partially-matching npz still loads).
"""

from __future__ import annotations

import logging
from typing import Dict, Tuple

import numpy as np

log = logging.getLogger(__name__)


def _bn_map(src: Dict[str, np.ndarray], prefix: str):
    return {
        "scale": src.get(f"{prefix}/gamma"),
        "bias": src.get(f"{prefix}/beta"),
        "mean": src.get(f"{prefix}/mean/EMA"),
        "var": src.get(f"{prefix}/variance/EMA"),
    }


def load_r50_npz(path: str, params: Dict) -> Tuple[Dict, int, int]:
    """Merge TensorPack npz weights into a Flax backbone param dict.

    ``params`` is the (mutable copy of the) ``params["backbone"]``
    subtree.  Returns ``(params, loaded, total_expected)``.
    """
    src = dict(np.load(path))
    # strip a possible saved-model style prefix
    src = {k.replace(":0", ""): v for k, v in src.items()}
    loaded = 0
    expected = 0

    def put(dst: Dict, key: str, value):
        nonlocal loaded
        if value is None:
            return
        if key in dst and dst[key].shape == value.shape:
            dst[key] = value.astype(dst[key].dtype)
            loaded += 1

    def put_conv_bn(dst_conv: Dict, dst_bn: Dict, conv_key: str):
        nonlocal expected
        expected += 5
        put(dst_conv, "kernel", src.get(f"{conv_key}/W"))
        for k, v in _bn_map(src, f"{conv_key}/bn").items():
            put(dst_bn, k, v)

    # stem: conv0 + its BN (FrozenBN_0 sits right after conv0 in our tree)
    if "conv0" in params:
        put_conv_bn(params["conv0"], params.get("FrozenBN_0", {}), "conv0")

    for name, sub in params.items():
        if not name.startswith("group"):
            continue
        # our names: group{g}_block{b} containing conv1..3, convshortcut
        for conv_name in ("conv1", "conv2", "conv3", "convshortcut"):
            if conv_name in sub:
                # FrozenBN modules are auto-numbered in declaration order:
                # conv1→FrozenBN_0, conv2→FrozenBN_1, conv3→FrozenBN_2,
                # convshortcut→FrozenBN_3
                bn_idx = {"conv1": 0, "conv2": 1, "conv3": 2,
                          "convshortcut": 3}[conv_name]
                put_conv_bn(sub[conv_name], sub.get(f"FrozenBN_{bn_idx}", {}),
                            f"{name}/{conv_name}")
    return params, loaded, expected


def save_r50_npz(path: str, params: Dict) -> int:
    """Inverse of :func:`load_r50_npz` — used by tests to build a
    TensorPack-layout npz from a Flax tree."""
    out = {}

    def grab(conv: Dict, bn: Dict, key: str):
        out[f"{key}/W"] = np.asarray(conv["kernel"])
        if bn:
            out[f"{key}/bn/gamma"] = np.asarray(bn["scale"])
            out[f"{key}/bn/beta"] = np.asarray(bn["bias"])
            out[f"{key}/bn/mean/EMA"] = np.asarray(bn["mean"])
            out[f"{key}/bn/variance/EMA"] = np.asarray(bn["var"])

    if "conv0" in params:
        grab(params["conv0"], params.get("FrozenBN_0", {}), "conv0")
    for name, sub in params.items():
        if not name.startswith("group"):
            continue
        for conv_name in ("conv1", "conv2", "conv3", "convshortcut"):
            if conv_name in sub:
                bn_idx = {"conv1": 0, "conv2": 1, "conv3": 2,
                          "convshortcut": 3}[conv_name]
                grab(sub[conv_name], sub.get(f"FrozenBN_{bn_idx}", {}),
                     f"{name}/{conv_name}")
    np.savez(path, **out)
    return len(out)


def load_backbone_into(params, param_sh, replicated, path: str):
    """``params`` with its ``backbone`` subtree filled from the npz at
    ``path`` (the Trainer's init-time hook, models.pretrained_loader).
    Gathers ONLY the backbone subtree to ``replicated`` (under fsdp the
    shards can live on other hosts' devices, where a bare np.asarray
    would fail); a full-tree gather would put a complete replica on
    every device and hand back the init-time memory win in exactly the
    configs fsdp exists for."""
    import jax

    bb = jax.tree.map(np.asarray,
                      jax.device_put(params["backbone"], replicated))
    bb, loaded, expected = load_r50_npz(path, bb)
    log.info("backbone weights: loaded %d/%d arrays from %s",
             loaded, expected, path)
    params = dict(params)
    params["backbone"] = jax.device_put(bb, param_sh["backbone"])
    return params
