"""Model FLOPs of a ResNet training step, from shapes alone.

Forward multiply-accumulates of every convolution and of the classifier,
counted from the published architecture (He et al., Table 1); a training
step is 3 x forward (forward, gradient of inputs, gradient of weights),
2 FLOPs a multiply-accumulate. Batch norm, ReLU, pooling and the
optimizer are not counted, and neither is anything recomputed.
"""

from __future__ import annotations

STAGES = {18: ("basic", (2, 2, 2, 2)), 34: ("basic", (3, 4, 6, 3)),
          50: ("bottleneck", (3, 4, 6, 3)), 101: ("bottleneck", (3, 4, 23, 3)),
          152: ("bottleneck", (3, 8, 36, 3))}


def _out(n, stride):
    return -(-n // stride)      # SAME padding


def forward_macs_per_image(depth: int, hw: int, classes: int,
                           width: int = 64) -> int:
    kind, reps = STAGES[depth]
    macs = 0
    hw = _out(hw, 2)
    macs += hw * hw * 7 * 7 * 3 * width             # stem
    hw = _out(hw, 2)                                # max pool
    cin = width
    for stage, n in enumerate(reps):
        mid = width * 2 ** stage
        cout = mid * (4 if kind == "bottleneck" else 1)
        for i in range(n):
            stride = 2 if (stage > 0 and i == 0) else 1
            out = _out(hw, stride)
            if kind == "bottleneck":
                macs += hw * hw * cin * mid                 # 1x1
                macs += out * out * 9 * mid * mid           # 3x3 (strided)
                macs += out * out * mid * cout              # 1x1
            else:
                macs += out * out * 9 * cin * mid
                macs += out * out * 9 * mid * cout
            if cin != cout or stride != 1:
                macs += out * out * cin * cout              # projection
            cin, hw = cout, out
    return macs + cin * classes


def train_flops_per_step(config: dict, traffic: dict) -> float:
    m = config["model"]
    fwd = 2 * forward_macs_per_image(m["depth"], m["image_hw"],
                                     m["num_classes"], m["width"])
    return 3.0 * fwd * traffic["batch"]
