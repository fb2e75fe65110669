"""The paper's ResNet18 as the benchmark counts it: a 3x3 stride-1 stem,
four stages of two basic blocks (a 1x1 projection where the shape
changes), global average pool and fc, ten partitionable units.

Kept with the benchmark so that every PR counts the same work the same
way; a configuration names it by ``model.arch``.
"""
from __future__ import annotations

# the program's model class (``repro.models.cnn``)
PROGRAM_CLASS = "ResNet18"


def _channels(width: float) -> list[int]:
    return [max(8, int(c * width)) for c in (64, 128, 256, 512)]


def _out(hw: int, stride: int) -> int:
    return -(-hw // stride)                 # "SAME" padding


def _blocks(width: float):
    """(stride, cin, cout, has_projection) of the eight basic blocks."""
    ch = _channels(width)
    cin = ch[0]
    for stage, cout in enumerate(ch):
        for blk in range(2):
            stride = 2 if (stage > 0 and blk == 0) else 1
            yield stride, cin, cout, stride != 1 or cin != cout
            cin = cout


def unit_macs(width: float = 1.0, img: int = 224,
              num_classes: int = 1000) -> list[int]:
    """Multiply-accumulates of each unit, per image.  Convolutions and the
    fc only; bias, ReLU and pooling are not counted."""
    ch = _channels(width)
    macs = [img * img * 9 * 3 * ch[0]]
    hw = img
    for stride, cin, cout, proj in _blocks(width):
        o = _out(hw, stride)
        m = o * o * 9 * cin * cout + o * o * 9 * cout * cout
        if proj:
            m += o * o * cin * cout
        macs.append(m)
        hw = o
    macs.append(ch[3] * num_classes)
    return macs


def unit_flops(width: float = 1.0, img: int = 224,
               num_classes: int = 1000) -> list[int]:
    """2 x :func:`unit_macs`, per image."""
    return [2 * m for m in unit_macs(width, img, num_classes)]


def unit_conv_weights(width: float = 1.0) -> list[int]:
    """Elements of each unit's convolution kernels: the int8 weights the
    Pallas ``bitflip`` kernel corrupts once per row and unit (the fc
    weight goes through ``fault_matmul`` instead and counts 0 here)."""
    ch = _channels(width)
    out = [9 * 3 * ch[0]]
    for _, cin, cout, proj in _blocks(width):
        out.append(9 * cin * cout + 9 * cout * cout + (cin * cout if proj
                                                        else 0))
    return out + [0]


def cost_layers(width: float, img: int, num_classes: int) -> list[dict]:
    """The partitioner's cost-model view: per unit its MACs, weight and
    activation bytes (INT16 accounting, 2 bytes each) and the analytic
    sensitivity prior (earlier units propagate corruption further)."""
    ch = _channels(width)
    hw = img
    out = [dict(macs=9 * 3 * ch[0] * hw * hw,
                weight_bytes=9 * 3 * ch[0] * 2,
                act_in_bytes=hw * hw * 3 * 2,
                act_out_bytes=hw * hw * ch[0] * 2)]
    for stride, cin, cout, proj in _blocks(width):
        o = hw // stride
        macs = 9 * cin * cout * o ** 2 + 9 * cout * cout * o ** 2
        wp = 9 * cin * cout + 9 * cout * cout
        if proj:
            macs += cin * cout * o ** 2
            wp += cin * cout
        out.append(dict(macs=macs, weight_bytes=wp * 2,
                        act_in_bytes=hw * hw * cin * 2,
                        act_out_bytes=o ** 2 * cout * 2))
        hw = o
    out.append(dict(macs=ch[3] * num_classes,
                    weight_bytes=ch[3] * num_classes * 2,
                    act_in_bytes=ch[3] * 2,
                    act_out_bytes=num_classes * 2))
    n = len(out)
    for i, li in enumerate(out):
        x = i / max(n - 1, 1)
        li["sensitivity"] = 0.002 * (1.35 - x + 0.25 * x ** 4)
    return out
