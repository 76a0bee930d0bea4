"""Tiny cells of AlexNet's layer list are ``correct`` with each
convolution's per-example gradients on the route it takes on a TPU: conv0
(11x11, stride 4, 48 channels after space to depth) on per-tap dots, conv1-4
by the ``pe_conv_grad`` kernel on bf16 operands; the kernel runs in
interpret mode.  One cell is the layer list of ``bench/configs/alexnet.json``
on a ``data:4`` mesh of four CPU devices, held to the committed limits of
``alexnet.data4.flat.b1024``; the other is torchvision's layout, with its
adaptive average pool (``bench/references/cnn_avgpool.py``), on one device,
held to those of ``alexnet.flat.b256``."""
import json
import shutil

import pytest

from bench import run, spec
from repro.core.tapper import STATS
from repro.kernels import ops as kops

from conftest import DATA, ROOT

CELLS = {
    # cell: (config, committed traffic, its overrides, cell held to)
    "tiny_alexnet.data4.flat.b16": (
        "tiny_alexnet", "data4.flat.b1024", {"batch": 16},
        "alexnet.data4.flat.b1024"),
    "tiny_alexnet_tv.flat.b4": (
        "tiny_alexnet_tv", "flat.b256", {"batch": 4}, "alexnet.flat.b256"),
}


@pytest.fixture
def alexnet_root(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for cell, (config, traffic, over, held_to) in CELLS.items():
        shutil.copy(DATA / f"{config}.config.json",
                    tmp_path / "bench" / "configs" / f"{config}.json")
        bench["configs"].append({"name": config, "source": "tests",
                                 "file": f"bench/configs/{config}.json",
                                 "reduced": [], "why": "tests"})
        tr = json.loads(
            (ROOT / "bench" / "traffic" / f"{traffic}.json").read_text())
        tiny_traffic = cell.split(".", 1)[1]
        (tmp_path / "bench" / "traffic" / f"{tiny_traffic}.json").write_text(
            json.dumps(dict(tr, **over)))
        shutil.copy(ROOT / "bench" / "limits" / f"{held_to}.json",
                    tmp_path / "bench" / "limits" / f"{cell}.json")
        bench["workloads"].append({
            "name": cell, "config": config, "traffic": tiny_traffic,
            "chips": 4 if tr["mesh"] else 1, "why": "tests"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


@pytest.mark.parametrize("name", sorted(CELLS))
def test_space_to_depth_conv0_is_correct(alexnet_root, monkeypatch, name):
    kernel = kops._pc.pe_conv_grad_2d
    monkeypatch.setattr(kops, "on_tpu", lambda: True)
    monkeypatch.setattr(kops._pc, "pe_conv_grad_2d", lambda *a, **k: kernel(
        *a, **dict(k, interpret=True)))
    STATS.reset()
    cell = spec.load_cell(name, alexnet_root)
    result = run.run(cell, 2**31 + 17, 0.3, False, require_tpu=False,
                     root=alexnet_root)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert set(STATS.conv_impls) == {"s2d_taps", "pallas"}, STATS.conv_impls
