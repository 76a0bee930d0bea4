"""Per-layer sweep of the per-example conv-gradient implementations on the
chip: VGG16's conv0-9 and AlexNet's conv0-1 at 256 px, one jitted call per
layer and implementation that forms the per-example weight gradients and
reduces them as the stash does (squared norm per example, weighted sum
over the batch).

    PYTHONPATH=src python -m benchmarks.pe_conv_sweep [--batch 32]
        [--impls fgc,pallas] [--layers conv0,conv1,...] [--out sweep.json]

Each row gives the route the call took (``tapper.STATS.conv_impls``: on a
TPU ``auto`` takes per-tap dots or the kernel, after space to depth for
AlexNet's strided conv0, and ``pallas`` the kernel), the kernel's row
tiling (``tapper.STATS.row_tiles``: rows, rows a tile, tiles), the median
ms of ``--iters`` calls after a warm-up, the TFLOP/s on the 2·B·T·C·K·D
products the contraction needs (K the layer's own taps), the compiled
program's temporary bytes, and the largest gap of its results to the
first implementation's.  The inputs enter as (H, W, B, C)
f32 arrays, the layout the private step keeps its captures in; a call
alone still pays the layout and type conversions that the step fuses
into the ops making its captures (the temporary bytes show them), so a
layer's time here bounds its time in the step from above.  It runs only
on a TPU.
"""
from __future__ import annotations

import argparse
import json
import statistics
import time

import jax
import jax.numpy as jnp

from repro.core.tapper import STATS
from repro.models import convops

# (C, D, input side, kernel, stride, padding) at 256 px: VGG16's 3x3
# stride-1 convolutions (conv6 and conv9 repeat conv5 and conv8), and
# AlexNet's conv0 (11x11, stride 4) and conv1 (5x5).
LAYERS = {"conv0": (3, 64, 256, 3, 1, 1), "conv1": (64, 64, 256, 3, 1, 1),
          "conv2": (64, 128, 128, 3, 1, 1),
          "conv3": (128, 128, 128, 3, 1, 1),
          "conv4": (128, 256, 64, 3, 1, 1), "conv5": (256, 256, 64, 3, 1, 1),
          "conv7": (256, 512, 32, 3, 1, 1), "conv8": (512, 512, 32, 3, 1, 1),
          "alexnet.conv0": (3, 64, 256, 11, 4, 2),
          "alexnet.conv1": (64, 192, 31, 5, 1, 2)}


def _step(impl: str, k: int, s: int, p: int):
    def f(xt, dyt, w):
        x, dy = xt.transpose(2, 3, 0, 1), dyt.transpose(2, 3, 0, 1)
        g = convops.pe_conv_grad(x, dy, kernel_spatial=(k, k), stride=s,
                                 padding=p, impl=impl)
        return (jnp.sum(jnp.square(g), axis=(1, 2, 3, 4)),
                jnp.einsum("b...,b->...", g, w))
    return jax.jit(f)


def measure(layer: str, impl: str, batch: int, iters: int) -> dict:
    C, D, S, k, s, p = LAYERS[layer]
    So = (S + 2 * p - k) // s + 1
    key = jax.random.PRNGKey(0)
    kx, kd = jax.random.split(key)
    xt = jax.random.normal(kx, (S, S, batch, C), jnp.float32)
    dyt = jax.random.normal(kd, (So, So, batch, D), jnp.float32)
    w = jnp.linspace(0.5, 1.5, batch, dtype=jnp.float32)
    STATS.reset()
    t = time.perf_counter()
    compiled = _step(impl, k, s, p).lower(xt, dyt, w).compile()
    compile_s = time.perf_counter() - t
    route = ",".join(sorted(STATS.conv_impls))
    row_tiles = [list(k) for k in sorted(STATS.row_tiles)]
    out = compiled(xt, dyt, w)
    jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t = time.perf_counter()
        jax.block_until_ready(compiled(xt, dyt, w))
        times.append(time.perf_counter() - t)
    ms = statistics.median(times) * 1e3
    flops = 2.0 * batch * So * So * C * k * k * D
    return {"layer": layer, "impl": impl, "route": route,
            "row_tiles": row_tiles, "ms": ms,
            "tflops": flops / ms / 1e9,
            "temp_mib": compiled.memory_analysis().temp_size_in_bytes / 2**20,
            "compile_s": compile_s}, out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--impls", default="fgc,pallas")
    ap.add_argument("--layers", default=",".join(LAYERS))
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--out", default="")
    a = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX's platform is {dev.platform!r}")
    rows = []
    for layer in a.layers.split(","):
        first = None
        for impl in a.impls.split(","):
            r, out = measure(layer, impl, a.batch, a.iters)
            if first is None:
                first = out
            else:   # the largest gap to the first impl, over its largest
                r["rel_diff"] = max(
                    float(jnp.max(jnp.abs(o - f)) / jnp.max(jnp.abs(f)))
                    for o, f in zip(out, first))
            rows.append(r)
            print(json.dumps(r), flush=True)
    if a.out:
        with open(a.out, "w") as f:
            json.dump({"device": dev.device_kind, "batch": a.batch,
                       "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
