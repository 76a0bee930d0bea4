"""The yardstick's tests run on the CPU, with four host devices for the
data-parallel cell; they are run apart from the repository's tests:

    python -m pytest bench/tests
"""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4")

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import json  # noqa: E402
import shutil  # noqa: E402

import pytest  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"
TINY_CELLS = {
    # name: (traffic overrides, the committed cell whose limits it is held to)
    "tiny.flat.b8": ({"batch": 8, "mesh": None}, "vgg16.flat.b32", 1),
    "tiny.data4.flat.b16": ({"batch": 16, "mesh": "data:4"},
                            "vgg16.flat.b32", 4),
}


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout holding this benchmark plus two tiny cells on the toy
    CNN, each held to the committed limits of a cell, and the metric of
    exposed collectives for the one on four devices."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(DATA / "tiny.config.json",
                tmp_path / "bench" / "configs" / "tiny.json")
    bench["configs"].append({"name": "tiny", "source": "toy CNN",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "tests"})
    base = json.loads((DATA / "tiny.traffic.json").read_text())
    for name, (over, held_to, chips) in TINY_CELLS.items():
        traffic = name.split(".", 1)[1]
        (tmp_path / "bench" / "traffic" / f"{traffic}.json").write_text(
            json.dumps(dict(base, **over)))
        shutil.copy(ROOT / "bench" / "limits" / f"{held_to}.json",
                    tmp_path / "bench" / "limits" / f"{name}.json")
        bench["workloads"].append({"name": name, "config": "tiny",
                                   "traffic": traffic, "chips": chips,
                                   "why": "tests"})
    bench["per_layer"].append({
        "name": "collective.exposed_share", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "collectives",
        "moves": "samples_per_s", "workloads": ["tiny.data4.flat.b16"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path
