"""The benchmark's operation and byte counts against hand counts."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import flops  # noqa: E402

RESNET18 = flops.arch("resnet18")

# ResNet18 at width 0.125 (channels 8, 16, 32, 64), 8x8 images, 10
# classes, counted by hand per unit:
#   stem  8*8 * 9*3*8
#   a stride-1 block at o*o outputs: 2 * o*o * 9*c*c
#   a stride-2 block: o*o * (9*cin*cout + 9*cout*cout + cin*cout)
#   fc 64*10
HAND_MACS = [13824, 73728, 73728, 57344, 73728, 57344, 73728, 57344,
             73728, 640]
HAND_CONV_WEIGHTS = [216, 1152, 1152, 3584, 4608, 14336, 18432, 57344,
                     73728, 0]


def test_resnet18_unit_macs_by_hand():
    assert RESNET18.unit_macs(0.125, 8, 10) == HAND_MACS
    assert RESNET18.unit_flops(0.125, 8, 10) == [
        2 * m for m in HAND_MACS]


def test_resnet18_conv_weights_by_hand():
    assert RESNET18.unit_conv_weights(0.125) == HAND_CONV_WEIGHTS


def test_resnet18_macs_match_the_models_own_count():
    from repro.models.cnn import ResNet18

    infos = ResNet18.layer_infos(num_classes=1000, width=1.0, img=224)
    assert RESNET18.unit_macs(1.0, 224, 1000) == [
        int(li.macs) for li in infos]


def test_architecture_is_found_by_name():
    assert RESNET18.PROGRAM_CLASS == "ResNet18"
    with pytest.raises(KeyError, match="no bench/archs/vgg11.py"):
        flops.arch("vgg11")


def test_cost_layers_match_the_models_layer_infos():
    from repro.models.cnn import ResNet18

    infos = ResNet18.layer_infos(num_classes=10, width=0.125, img=8)
    got = RESNET18.cost_layers(0.125, 8, 10)
    for li, g in zip(infos, got, strict=True):
        assert (g["macs"], g["weight_bytes"], g["act_in_bytes"],
                g["act_out_bytes"]) == (li.macs, li.weight_bytes,
                                        li.act_in_bytes, li.act_out_bytes)


def test_bitflip_cost_by_hand():
    # 1000 int8 elements, 4 planes of 25 integer ops plus 3 per element;
    # one read and one write of each byte
    assert flops.bitflip_cost(1000, 4) == (1000 * (4 * 25 + 3), 2000)
