"""The benchmark's description, found by name.

``BENCHMARK.json`` at the root of the checkout lists configurations,
cells and metrics.  Everything that belongs to one of them sits in files
of its own under ``bench/``, found by the name it has there:

  bench/configs/<config>.json     a configuration: sizes and their source
  bench/traffic/<traffic>.json    a traffic mix: batch, clipping, noise,
                                  optimizer, mesh
  bench/metrics/<metric>.py       a per-layer metric's reader:
                                  ``read(ctx) -> float | None``
  bench/references/<ref>.py       a configuration's plain reference
  bench/limits/<cell>.json        the limit of each number compared for
                                  ``correct`` in a cell
  bench/flops/<family>.py         model FLOPs of a family, from its shapes
  bench/peaks.json                published peaks, keyed by device kind

A new cell, configuration or metric is new files plus entries in
``BENCHMARK.json``; no file here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent

METRIC_KEYS = ("name", "unit", "better", "source")
PER_LAYER_KEYS = METRIC_KEYS + ("layer", "moves")


class SpecError(ValueError):
    """The benchmark's description is incomplete or names a missing file."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple      # metric entries reported with --trace 0
    per_layer: tuple       # metric entries reported with --trace 1


def _read_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise SpecError(f"{path} does not exist")
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return _read_json(pathlib.Path(root) / "BENCHMARK.json")


def _check_metric(m: dict, keys) -> None:
    missing = [k for k in keys if not m.get(k)]
    if missing:
        raise SpecError(f"metric {m.get('name')!r} lacks {missing}")
    if m["better"] not in ("lower", "higher"):
        raise SpecError(f"metric {m['name']!r}: better={m['better']!r}")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell called ``name`` in ``root``'s BENCHMARK.json, with its
    configuration and traffic files read and every metric it reports
    checked for its keys and its reader."""
    root = pathlib.Path(root)
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    config = _read_json(root / configs[w["config"]]["file"])
    traffic = _read_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    for m in bench["end_to_end"]:
        _check_metric(m, METRIC_KEYS)
    for m in bench["per_layer"]:
        _check_metric(m, PER_LAYER_KEYS)
        load_reader(m["name"], root)
    e2e = tuple(m for m in bench["end_to_end"] if _applies(m, name))
    names = {m["name"] for m in e2e}
    per_layer = tuple(m for m in bench["per_layer"]
                      if _applies(m, name) and m["moves"] in names)
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def _load_module(path: pathlib.Path, name: str):
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, root: pathlib.Path = ROOT):
    """The module ``bench/metrics/<metric>.py``; it defines ``read(ctx)``."""
    path = pathlib.Path(root) / "bench" / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise SpecError(f"metric {metric!r} has no reader at {path}")
    mod = _load_module(path, f"bench_metric_{metric.replace('.', '_')}")
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"{path} defines no read(ctx)")
    return mod


def load_reference(config: dict, root: pathlib.Path = ROOT):
    """The configuration's plain reference, ``bench/references/<ref>.py``."""
    path = pathlib.Path(root) / "bench" / "references" / \
        f"{config['reference']}.py"
    if not path.is_file():
        raise SpecError(f"config {config['name']!r} names reference "
                        f"{config['reference']!r}, not found at {path}")
    return _load_module(path, f"bench_reference_{config['reference']}")


def load_flops(config: dict, root: pathlib.Path = ROOT):
    """``bench/flops/<family>.py`` for the configuration's family, which
    counts its model FLOPs from its shapes, or ``None``."""
    path = pathlib.Path(root) / "bench" / "flops" / f"{config['family']}.py"
    if not path.is_file():
        return None
    return _load_module(path, f"bench_flops_{config['family']}")


def limits(cell: str, root: pathlib.Path = ROOT) -> dict:
    """The limit of each number compared for ``cell``,
    ``bench/limits/<cell>.json``, with the readings it was set from."""
    table = _read_json(pathlib.Path(root) / "bench" / "limits" /
                       f"{cell}.json")
    return {name: entry["limit"] for name, entry in table["limits"].items()}


def peaks(device_kind: str, root: pathlib.Path = ROOT) -> dict:
    """Published peaks of one chip of ``device_kind``."""
    table = _read_json(pathlib.Path(root) / "bench" / "peaks.json")
    if device_kind not in table["devices"]:
        raise SpecError(f"no published peaks for device kind "
                        f"{device_kind!r} in bench/peaks.json")
    return table["devices"][device_kind]
