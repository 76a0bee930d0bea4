"""The loader finds every cell, configuration and metric by name, and
refuses an incomplete description; FLOPs are counted from the shapes."""
import json

import pytest

from bench import spec
from bench.flops import cnn as cnn_flops
from conftest import ROOT


def test_every_cell_config_and_metric_is_found():
    bench = spec.load_benchmark(ROOT)
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], ROOT)
        assert cell.chips == w["chips"]
        assert cell.config["name"] == w["config"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer, w["name"]
        assert spec.load_reference(cell.config, ROOT).Reference
        assert set(spec.limits(w["name"], ROOT)) == {"loss", "grad",
                                                     "update"}
    for m in bench["per_layer"]:
        assert callable(spec.load_reader(m["name"], ROOT).read)


def test_a_metric_is_reported_only_in_the_cells_it_lists(tiny_root):
    one = spec.load_cell("tiny.flat.b8", tiny_root)
    four = spec.load_cell("tiny.data4.flat.b16", tiny_root)
    assert "collective.exposed_share" not in {m["name"] for m in one.per_layer}
    assert "collective.exposed_share" in {m["name"] for m in four.per_layer}


def test_a_new_cell_is_only_new_files(tiny_root):
    cell = spec.load_cell("tiny.flat.b8", tiny_root)
    assert cell.traffic["batch"] == 8 and cell.config["name"] == "tiny"
    with pytest.raises(spec.SpecError, match="no workload"):
        spec.load_cell("tiny.flat.b8", ROOT)


@pytest.mark.parametrize("key", ["unit", "layer", "moves"])
def test_a_metric_without_its_keys_is_refused(tiny_root, key):
    path = tiny_root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    del bench["per_layer"][0][key]
    path.write_text(json.dumps(bench))
    with pytest.raises(spec.SpecError, match=key):
        spec.load_cell("tiny.flat.b8", tiny_root)


def test_a_metric_without_a_reader_is_refused(tiny_root):
    path = tiny_root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["per_layer"].append(dict(bench["per_layer"][0], name="no.reader"))
    path.write_text(json.dumps(bench))
    with pytest.raises(spec.SpecError, match="no reader"):
        spec.load_cell("tiny.flat.b8", tiny_root)


def test_an_unknown_device_kind_is_refused():
    assert spec.peaks("TPU v5 lite", ROOT)["bf16_flops_per_s"] == 197e12
    with pytest.raises(spec.SpecError, match="no published peaks"):
        spec.peaks("TPU v9 imaginary", ROOT)


def _hand_macs(side, layers, pool_after, fc):
    """Multiply-adds counted layer by layer: (in, out, kernel, out side)."""
    return sum(cin * cout * k * k * s * s for cin, cout, k, s in layers) + \
        sum(a * b for a, b in fc)


def test_vgg16_forward_macs_by_hand():
    conv = [(3, 64, 3, 256), (64, 64, 3, 256), (64, 128, 3, 128),
            (128, 128, 3, 128), (128, 256, 3, 64), (256, 256, 3, 64),
            (256, 256, 3, 64), (256, 512, 3, 32), (512, 512, 3, 32),
            (512, 512, 3, 32), (512, 512, 3, 16), (512, 512, 3, 16),
            (512, 512, 3, 16)]
    fc = [(512 * 8 * 8, 4096), (4096, 4096), (4096, 1000)]
    hand = _hand_macs(256, conv, None, fc)
    cfg = json.loads((ROOT / "bench/configs/vgg16.json").read_text())
    assert cnn_flops.forward_macs(cfg) == hand
    assert round(hand / 1e9, 2) == 20.20
    assert cnn_flops.train_flops_per_sample(cfg) == 6 * hand


def test_alexnet_forward_macs_by_hand():
    # The configuration waits for its four-chip cell (PERF.md, Open
    # questions); its FLOP count is checked against a hand count now.
    # 256 px: conv0 (k11 s4 p2) -> 63, pool 3/2 -> 31, conv1 -> 31,
    # pool -> 15, conv2-4 -> 15, pool -> 7.
    conv = [(3, 64, 11, 63), (64, 192, 5, 31), (192, 384, 3, 15),
            (384, 256, 3, 15), (256, 256, 3, 15)]
    fc = [(256 * 7 * 7, 4096), (4096, 4096), (4096, 1000)]
    hand = _hand_macs(256, conv, None, fc)
    cfg = json.loads((ROOT / "bench/configs/alexnet.json").read_text())
    assert cnn_flops.forward_macs(cfg) == hand
    assert round(hand / 1e9, 3) == 0.941
