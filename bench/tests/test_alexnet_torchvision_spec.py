"""Torchvision's AlexNet at 256 px, as the benchmark runs it: its FLOPs and
parameters are counted by hand from the layer shapes."""
import json
import math

from bench import spec
from conftest import ROOT


def test_alexnet_torchvision_forward_macs_and_parameters_by_hand():
    # 256 px: conv0 (k11 s4 p2) -> 63, pool 3/2 -> 31, conv1 -> 31,
    # pool -> 15, conv2-4 -> 15, pool -> 7, which the 6x6 adaptive pool
    # reads: fc0 reads 256*6*6 = 9216 features and the model has
    # torchvision's 61,100,840 parameters.
    conv = [(3, 64, 11, 63), (64, 192, 5, 31), (192, 384, 3, 15),
            (384, 256, 3, 15), (256, 256, 3, 15)]
    fc = [(256 * 6 * 6, 4096), (4096, 4096), (4096, 1000)]
    hand = sum(cin * cout * k * k * s * s for cin, cout, k, s in conv) + \
        sum(a * b for a, b in fc)
    cfg = json.loads(
        (ROOT / "bench/configs/alexnet.torchvision.json").read_text())
    assert spec.load_flops(cfg, ROOT).forward_macs(cfg) == hand
    shapes = spec.load_reference(cfg, ROOT).param_shapes(cfg)
    assert shapes["fc0"]["w"] == (9216, 4096)
    count = sum(math.prod(s) for layer in shapes.values()
                for s in layer.values())
    assert count == cfg["parameters"] == 61_100_840
