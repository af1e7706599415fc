"""Operations ``resnet50-imagenet`` requires, counted from its shapes.

Multiply-accumulates of every convolution and of the classifier at the
configuration's image size; BatchNorm, ReLU and pooling are not counted
(they are bandwidth, not MXU work).  A training step requires forward plus
backward = 3 x forward, at 2 FLOP per MAC; recomputation never counts.
torchvision's resnet50 at 224 px: 4.09 GMAC forward.
"""

from __future__ import annotations


def forward_macs(cfg: dict) -> int:
    size, f0 = cfg["image_size"], cfg["num_filters"]
    hw = (size + 1) // 2                       # 7x7 stride-2 stem
    macs = hw * hw * 7 * 7 * 3 * f0
    hw = (hw + 1) // 2                         # 3x3 stride-2 max pool
    cin = f0
    for i, blocks in enumerate(cfg["stage_sizes"]):
        f = f0 * 2 ** i
        for j in range(blocks):
            stride = 2 if i > 0 and j == 0 else 1
            out = hw // stride
            macs += hw * hw * cin * f                  # 1x1
            macs += out * out * 9 * f * f              # 3x3, carries the stride
            macs += out * out * f * 4 * f              # 1x1 expand
            if j == 0:
                macs += out * out * cin * 4 * f        # projection shortcut
            cin, hw = 4 * f, out
    return macs + cin * cfg["num_classes"]


def train_flops_per_sample(cfg: dict) -> float:
    return 3 * 2 * forward_macs(cfg)


def kernel_costs(cfg: dict, per_chip_batch: int) -> dict:
    """Least bytes and operations of one call of each Pallas kernel in the step."""
    px = per_chip_batch * cfg["image_size"] ** 2 * 3
    logits = per_chip_batch * cfg["num_classes"]
    return {
        # uint8 in, bfloat16 out, a multiply and an add per element
        "tpuframe_normalize": {"bytes": px * (1 + 2), "flops": 2 * px},
        # float32 logits in, one loss per row out; backward reads logits, writes their gradient
        "tpuframe_ce_fwd": {"bytes": 4 * logits + 4 * per_chip_batch, "flops": 4 * logits},
        "tpuframe_ce_bwd": {"bytes": 2 * 4 * logits, "flops": 4 * logits},
    }
