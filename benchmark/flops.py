"""Operations and bytes the algorithm REQUIRES, from shapes alone.

A multiply-add is 2 operations.  Nothing here reads a compiled program:
remat recompute, padding to hardware tiles and the interpolation
matmuls of a particular ROIAlign kernel are the implementation's, not
the algorithm's.  ``spec`` is the ``model`` block of a configuration
file; the input is the cell's canvas (the padded image the model is
given).
"""

from __future__ import annotations


def _conv(h, w, k, cin, cout):
    return 2 * h * w * k * k * cin * cout


def resnet_layers(blocks, h, w):
    """[(name, forward ops, stage)] of the bottleneck ResNet on an
    h x w input; stage -1 is the stem."""
    out = [("conv0", _conv(h // 2, w // 2, 7, 3, 64), -1)]
    h, w = h // 4, w // 4
    cin = 64
    for stage, n in enumerate(blocks):
        ch = 64 * 2 ** stage
        for b in range(n):
            if b == 0 and stage > 0:
                ho, wo = h // 2, w // 2
            else:
                ho, wo = h, w
            name = f"group{stage}_block{b}"
            out.append((name + "/conv1", _conv(h, w, 1, cin, ch), stage))
            out.append((name + "/conv2", _conv(ho, wo, 3, ch, ch), stage))
            out.append((name + "/conv3", _conv(ho, wo, 1, ch, ch * 4), stage))
            if cin != ch * 4:
                out.append((name + "/convshortcut",
                            _conv(ho, wo, 1, cin, ch * 4), stage))
            cin, h, w = ch * 4, ho, wo
    return out


def resnet_forward_ops(blocks, h, w):
    return sum(f for _, f, _ in resnet_layers(blocks, h, w))


def forward_parts(spec, h, w):
    """Forward operations of one image by component."""
    f = spec["fpn_channels"]
    parts = {"backbone": resnet_forward_ops(spec["resnet_blocks"], h, w)}
    fpn = 0
    for i in range(4):
        hl, wl = h // (4 * 2 ** i), w // (4 * 2 ** i)
        fpn += _conv(hl, wl, 1, 256 * 2 ** i, f) + _conv(hl, wl, 3, f, f)
    parts["fpn"] = fpn
    a = len(spec["anchor_ratios"])
    parts["rpn_head"] = sum(
        _conv(h // s, w // s, 3, f, f) + _conv(h // s, w // s, 1, f, 5 * a)
        for s in spec["strides"])
    n, fc, k = spec["frcnn_batch_per_im"], spec["fc_head_dim"], \
        spec["num_classes"]
    parts["box_head"] = 2 * n * (49 * f * fc + fc * fc + fc * 5 * k)
    if spec["mask"]:
        m = max(1, int(n * spec["frcnn_fg_ratio"]))
        d, r = spec["mask_head_dim"], spec["mask_resolution"]
        half = r // 2
        parts["mask_head"] = m * (
            _conv(half, half, 3, f, d) + 3 * _conv(half, half, 3, d, d)
            + 2 * half * half * 4 * d * d + _conv(r, r, 1, d, k))
    parts["roi_align"] = sum(x["ops"] for x in roi_align_calls(spec, h, w)
                             if x["pass"] == "forward")
    return parts


def train_ops_per_image(spec, h, w):
    """Forward + backward of one image.  A trainable layer's backward
    is its weight gradient plus its input gradient, each as many
    operations as its forward; the frozen stem and stages (the
    stop-gradient sits after stage ``freeze_at - 2``) have no backward,
    and the first trainable block's input needs no gradient."""
    freeze = spec["freeze_at"]
    if freeze >= 1:
        head = f"group{freeze - 1}_block0"
        no_input_grad = {head + "/conv1", head + "/convshortcut"}
    else:
        no_input_grad = {"conv0"}
    total = 0
    for name, ops, stage in resnet_layers(spec["resnet_blocks"], h, w):
        if stage + 2 <= freeze:
            total += ops
        else:
            total += ops * (2 if name in no_input_grad else 3)
    parts = forward_parts(spec, h, w)
    for key in ("fpn", "rpn_head", "box_head", "mask_head"):
        total += 3 * parts.get(key, 0)
    total += sum(x["ops"] for x in roi_align_calls(spec, h, w))
    return total


def roi_align_calls(spec, h, w, itemsize=2):
    """Required work of each ROIAlign pass of one image: box (7x7 on
    every sampled ROI) and mask (14x14 on the sampled foreground), each
    forward and backward.  Operations: every output element averages
    2x2 samples of 4 taps, 16 multiply-adds.  Bytes, a floor that any
    implementation pays: forward reads at least one feature vector per
    output bin and writes the bin; backward reads the output gradient
    and writes the dense gradient of the four feature levels."""
    c = spec["fpn_channels"]
    n = spec["frcnn_batch_per_im"]
    heads = [("box", n, 7)]
    if spec["mask"]:
        heads.append(("mask", max(1, int(n * spec["frcnn_fg_ratio"])),
                      spec["mask_resolution"] // 2))
    levels = sum((h // s) * (w // s) for s in spec["strides"][:4])
    calls = []
    for head, rois, out in heads:
        elems = rois * out * out * c
        calls.append({"head": head, "pass": "forward", "ops": 32 * elems,
                      "bytes": 2 * elems * itemsize})
        calls.append({"head": head, "pass": "backward", "ops": 32 * elems,
                      "bytes": (elems + levels * c) * itemsize})
    return calls
