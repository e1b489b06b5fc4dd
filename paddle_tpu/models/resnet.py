"""ResNet family (18/34/50/101/152), NHWC, bfloat16-friendly.

Parity target: the reference's ResNet benchmark config (reference:
benchmark/paddle/image/resnet.py — layer_num in {50,101,152} built from
conv_bn_layer + bottleneck/basic blocks; also the model-zoo resnet in
v1_api_demo/model_zoo/resnet/resnet.py). This is the image model of
the benchmark's `resnet50.train_bs256` cell (BASELINE.json: ResNet-50
imgs/sec/chip).

TPU notes: NHWC keeps the channel dim minor for the MXU; BN statistics are
computed in f32 while conv math can run bf16 via the dtype policy.
"""

from __future__ import annotations

from typing import Optional

from paddle_tpu import nn


def conv_bn(features, kernel, stride, *, activation="relu", name,
            space_to_depth=False):
    """conv + BN (+act) block (reference: benchmark/paddle/image/resnet.py
    conv_bn_layer)."""
    return [
        nn.Conv2D(features, kernel, stride=stride, padding="SAME", use_bias=False,
                  name=f"{name}_conv", space_to_depth=space_to_depth),
        nn.BatchNorm(activation=activation, name=f"{name}_bn"),
    ]


def _shortcut(in_ch: int, out_ch: int, stride: int, name: str) -> Optional[nn.Layer]:
    if in_ch == out_ch and stride == 1:
        return None
    return nn.Sequential(
        conv_bn(out_ch, 1, stride, activation=None, name=f"{name}_proj"),
        name=f"{name}_sc",
    )


def basic_block(in_ch: int, out_ch: int, stride: int, name: str) -> nn.Layer:
    main = nn.Sequential(
        conv_bn(out_ch, 3, stride, name=f"{name}_a")
        + conv_bn(out_ch, 3, 1, activation=None, name=f"{name}_b"),
        name=f"{name}_main",
    )
    return nn.Residual(main, _shortcut(in_ch, out_ch, stride, name),
                       activation="relu", name=name)


def bottleneck_block(in_ch: int, out_ch: int, stride: int, name: str) -> nn.Layer:
    mid = out_ch // 4
    main = nn.Sequential(
        conv_bn(mid, 1, 1, name=f"{name}_a")
        + conv_bn(mid, 3, stride, name=f"{name}_b")
        + conv_bn(out_ch, 1, 1, activation=None, name=f"{name}_c"),
        name=f"{name}_main",
    )
    return nn.Residual(main, _shortcut(in_ch, out_ch, stride, name),
                       activation="relu", name=name)


_SPECS = {
    18: ("basic", (2, 2, 2, 2)),
    34: ("basic", (3, 4, 6, 3)),
    50: ("bottleneck", (3, 4, 6, 3)),
    101: ("bottleneck", (3, 4, 23, 3)),
    152: ("bottleneck", (3, 8, 36, 3)),
}


def resnet(depth: int = 50, num_classes: int = 1000, *, width: int = 64,
           s2d_stem: bool = False,
           remat: Optional[str] = None) -> nn.Sequential:
    """ImageNet-style ResNet (reference: benchmark/paddle/image/resnet.py).

    s2d_stem=True computes the 7x7/s2 stem on a 2x2 space-to-depth
    blocking of the input — same math, same parameters, but the conv
    streams C_in=12 instead of 3, which the TPU tiles better.

    remat wraps every residual block in nn.Remat (same params, same
    math): "conv_out" saves only conv outputs and recomputes BN/ReLU in
    the backward; "full" saves nothing inside a block. Both reduce the
    residual bytes a train step keeps for the backward (counted in
    tests/test_compiled_cost.py). Neither variant nor the s2d stem has
    been timed on the image cell, whose step is device-bound (PERF.md
    section 5); ROADMAP A3 decides whether they stay.
    """
    if remat not in (None, "conv_out", "full"):
        raise ValueError(
            f"remat must be None, 'conv_out' or 'full', got {remat!r}")
    kind, reps = _SPECS[depth]
    block = basic_block if kind == "basic" else bottleneck_block
    expansion = 1 if kind == "basic" else 4

    def wrap(layer):
        if remat is None:
            return layer
        return nn.Remat(layer,
                        policy="conv_out" if remat == "conv_out" else None)

    layers = conv_bn(width, 7, 2, name="stem", space_to_depth=s2d_stem) + [
        nn.MaxPool2D(3, stride=2, padding="SAME", name="stem_pool")]
    in_ch = width
    for stage, n in enumerate(reps):
        out_ch = width * (2 ** stage) * expansion
        for i in range(n):
            stride = 2 if (stage > 0 and i == 0) else 1
            layers.append(
                wrap(block(in_ch, out_ch, stride, name=f"s{stage}_b{i}")))
            in_ch = out_ch
    layers += [
        nn.GlobalAvgPool2D(name="gap"),
        nn.Dense(num_classes, name="logits"),
    ]
    return nn.Sequential(layers, name=f"resnet{depth}")


def resnet_cifar(depth: int = 20, num_classes: int = 10, *, width: int = 16) -> nn.Sequential:
    """CIFAR-style 6n+2 resnet (reference quick-start resnet variant;
    v1_api_demo/quick_start/trainer_config.resnet-lstm.py uses the same
    conv-bn-residual building blocks)."""
    n = (depth - 2) // 6
    layers = conv_bn(width, 3, 1, name="stem")
    in_ch = width
    for stage in range(3):
        out_ch = width * (2 ** stage)
        for i in range(n):
            stride = 2 if (stage > 0 and i == 0) else 1
            layers.append(basic_block(in_ch, out_ch, stride, name=f"s{stage}_b{i}"))
            in_ch = out_ch
    layers += [nn.GlobalAvgPool2D(name="gap"), nn.Dense(num_classes, name="logits")]
    return nn.Sequential(layers, name=f"resnet{depth}_cifar")
