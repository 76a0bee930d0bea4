"""Plain reference of a DP-SGD step on a convolutional network whose
classifier reads torchvision's adaptive average pool.

The configuration is that of ``cnn.py`` plus ``avgpool``, the side of the
pool's output: output i of an axis of n averages inputs floor(i n / avgpool)
to ceil((i + 1) n / avgpool) - 1, as ``AdaptiveAvgPool2d`` does.  Here the
pool is a product with one averaging matrix per axis.  The rest of the step
is ``cnn.py``'s, run from a copy of that module of its own, whose layer
shapes and logits this module replaces.
"""
from __future__ import annotations

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_spec = importlib.util.spec_from_file_location(
    "bench_reference_cnn_avgpool_base",
    pathlib.Path(__file__).with_name("cnn.py"))
_cnn = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cnn)


def _averaging(n: int, out: int) -> np.ndarray:
    """(out, n): row i averages inputs floor(i n / out) to
    ceil((i + 1) n / out) - 1."""
    a = np.zeros((out, n), np.float32)
    for i in range(out):
        lo, hi = i * n // out, -(-(i + 1) * n // out)
        a[i, lo:hi] = 1.0 / (hi - lo)
    return a


_cnn_param_shapes = _cnn.param_shapes


def param_shapes(cfg: dict) -> dict:
    side = cfg["avgpool"]
    shapes = {k: v for k, v in _cnn_param_shapes(cfg).items()
              if k.startswith("conv")}
    cin = shapes[f"conv{len(cfg['convs']) - 1}"]["b"][0]
    dims = [cin * side * side] + list(cfg["fc"]) + [cfg["n_classes"]]
    for j in range(len(dims) - 1):
        shapes[f"fc{j}"] = {"w": (dims[j], dims[j + 1]), "b": (dims[j + 1],)}
    return shapes


def logits(cfg: dict, params, img):
    h = img
    pk, ps = cfg["pool"]["kernel"], cfg["pool"]["stride"]
    for i, (out, k, s, p, pool) in enumerate(cfg["convs"]):
        layer = params[f"conv{i}"]
        h = lax.conv_general_dilated(
            h, layer["w"], (s, s), [(p, p), (p, p)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        h = jax.nn.relu(h + layer["b"][None, :, None, None])
        if pool:
            h = lax.reduce_window(h, -jnp.inf, lax.max,
                                  (1, 1, pk, pk), (1, 1, ps, ps), "VALID")
    ah = jnp.asarray(_averaging(h.shape[2], cfg["avgpool"]), h.dtype)
    aw = jnp.asarray(_averaging(h.shape[3], cfg["avgpool"]), h.dtype)
    h = jnp.einsum("ih,bchw,jw->bcij", ah, h, aw)
    h = h.reshape(h.shape[0], -1)
    n_fc = len(cfg["fc"]) + 1
    for j in range(n_fc):
        h = h @ params[f"fc{j}"]["w"] + params[f"fc{j}"]["b"]
        if j < n_fc - 1:
            h = jax.nn.relu(h)
    return h


_cnn.param_shapes, _cnn.logits = param_shapes, logits
init_params, losses, noise, adamw, Reference = (
    _cnn.init_params, _cnn.losses, _cnn.noise, _cnn.adamw, _cnn.Reference)
