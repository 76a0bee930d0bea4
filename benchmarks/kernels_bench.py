"""Kernel-level benchmark: the ghost-norm Gram reduction and the
per-example conv gradient.

Wall time on CPU compares the *XLA lowerings*; the Pallas kernels target
TPU (here they run in interpret mode, which measures nothing useful), so
the kernel's value is reported analytically: HBM bytes touched by the XLA
chunked-gram path vs the fused VMEM-tiled kernel.

``--calibrate-only`` skips the comparative benchmark and runs the
measured-cost harness (repro.calibrate) instead: flop rate, HBM and
collective bandwidth, plus the pe_conv_grad VMEM_BUDGET sweep.  The
resulting calibration JSON is what ``launch/train.py --calibration``,
``launch/dryrun.py --calibration`` and ``launch/serve.py --calibration``
pre-register, and the sweep winners are merged into BENCH_strategies.json
under the ``kernels@calibration`` key so the benchmark record carries the
measured tile choices alongside the strategy timings.

    PYTHONPATH=src python -m benchmarks.kernels_bench --calibrate-only \
        --calibration-out results/calibration.json [--mesh data:8] [--quick]
"""
from __future__ import annotations

import json
import os
import sys

if __name__ == "__main__":
    # A --mesh data:N calibration on a CPU host needs N devices before
    # the jax backend initializes.
    from repro.launch.mesh import force_host_device_count_for
    force_host_device_count_for(sys.argv)

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_fn
from repro.core import kinds
from repro.core.tapper import LayerMeta
from repro.models import convops


def run():
    rng = np.random.RandomState(0)
    # --- ghost norm: gram vs stream (XLA) + analytic kernel savings
    for (B, T, Di, Do) in [(8, 256, 256, 256), (4, 1024, 512, 512)]:
        x = jnp.array(rng.randn(B, T, Di), jnp.float32)
        dy = jnp.array(rng.randn(B, T, Do), jnp.float32)
        meta = LayerMeta("dense", ("w",))
        f_gram = jax.jit(lambda a, b: kinds.dense_norm_sq(
            meta, {"x": a}, b, method="gram"))
        f_stream = jax.jit(lambda a, b: kinds.dense_norm_sq(
            meta, {"x": a}, b, method="stream"))
        tg = time_fn(f_gram, x, dy)
        ts = time_fn(f_stream, x, dy)
        # XLA gram materializes (B, chunk, T) Gram tiles in HBM twice;
        # the Pallas kernel keeps them in VMEM: HBM traffic = inputs once.
        chunk = min(T, 1024)
        xla_bytes = 4 * B * (2 * chunk * T * (T // chunk)      # two grams
                             + T * (Di + Do))                  # inputs
        kern_bytes = 4 * B * T * (Di + Do)
        emit(f"kernels/gram_norm/B{B}T{T}", tg,
             f"stream_us={ts:.0f};hbm_ratio_xla_vs_pallas="
             f"{xla_bytes / kern_bytes:.1f}")

    # --- fused gram_norm + weighted contribution: one pass over (x, δy)
    # vs the two-kernel sequence.  The separate path times the XLA
    # lowering; the fused kernel runs in interpret mode on CPU (its time
    # here is plumbing, not performance) — the analytic win is the halved
    # HBM read of x/δy.
    from repro.kernels import ops as kops
    B, T, Di, Do = 4, 256, 128, 128
    x = jnp.array(rng.randn(B, T, Di), jnp.float32)
    dy = jnp.array(rng.randn(B, T, Do), jnp.float32)
    w = jnp.array(rng.rand(B), jnp.float32)
    meta = LayerMeta("dense", ("w",))
    f_sep = jax.jit(lambda a, b, c: (
        kinds.dense_norm_sq(meta, {"x": a}, b, method="gram"),
        kinds.dense_contrib(meta, {"x": a}, b, c)))
    t_sep = time_fn(f_sep, x, dy, w)
    sep_bytes = 4 * B * T * (Di + Do) * 2          # x/δy read twice
    fused_bytes = 4 * B * T * (Di + Do)            # read once
    emit(f"kernels/gram_norm_sep/B{B}T{T}", t_sep,
         f"hbm_ratio_sep_vs_fused={sep_bytes / fused_bytes:.1f}")
    f_fused = jax.jit(lambda a, b, c: kops.gram_norm_fused(a, b, c))
    t_fused = time_fn(f_fused, x, dy, w)
    emit(f"kernels/gram_norm_fused/B{B}T{T}", t_fused,
         "interpret_mode_on_cpu")

    # --- per-example conv grad: fgc vs bgc lowering + the kernel's row tile
    for (B, C, D, HW, K) in [(8, 16, 32, 32, 3), (4, 32, 64, 16, 5)]:
        x = jnp.array(rng.randn(B, C, HW, HW), jnp.float32)
        out_sp = HW - K + 1
        dy = jnp.array(rng.randn(B, D, out_sp, out_sp), jnp.float32)
        for impl in ("fgc", "bgc"):
            f = jax.jit(lambda a, b, i=impl: convops.pe_conv_grad(
                a, b, kernel_spatial=(K, K), impl=i))
            t = time_fn(f, x, dy)
            emit(f"kernels/pe_conv/{impl}/B{B}C{C}D{D}", t, "")
        th = kops._pc.row_tile(HW, K, K, HW, kops.vmem_budget())
        emit(f"kernels/pe_conv/pallas_th/B{B}C{C}D{D}", 0.0,
             f"row_tile={th}_of_{HW}")


def calibrate_only(calibration_out: str = "results/calibration.json",
                   mesh_spec: str | None = None, quick: bool = False,
                   bench_out: str = "BENCH_strategies.json") -> dict:
    """Run the measurement harness, persist the calibration JSON, and
    merge the kernel-sweep winners into the strategy benchmark record."""
    from repro import calibrate
    from repro.launch.mesh import make_mesh_from_spec

    mesh = make_mesh_from_spec(mesh_spec) if mesh_spec else None
    calib = calibrate.measure(mesh, quick=quick)
    os.makedirs(os.path.dirname(calibration_out) or ".", exist_ok=True)
    calibrate.save_calibration(calibration_out, calib)
    emit("kernels/calibration/flops_per_second", 0.0,
         f"{calib.flops_per_second:.3e}")
    emit("kernels/calibration/hbm_bytes_per_second", 0.0,
         f"{calib.hbm_bytes_per_second:.3e}")
    for axis, bw in sorted(calib.collective_bytes_per_second.items()):
        emit(f"kernels/calibration/collective/{axis}", 0.0, f"{bw:.3e}")
    pe = calib.kernels.get("pe_conv_grad", {})
    if pe:
        emit("kernels/calibration/pe_conv_vmem_budget", 0.0,
             f"winner={pe['vmem_budget']}_th={pe['th']}")

    results = {}
    if os.path.exists(bench_out):
        results = json.load(open(bench_out))
    results["kernels@calibration"] = {
        "hardware": calib.hardware,
        "digest": calib.digest(),
        "calibration_path": calibration_out,
        "flops_per_second": calib.flops_per_second,
        "hbm_bytes_per_second": calib.hbm_bytes_per_second,
        "collective_bytes_per_second": dict(
            calib.collective_bytes_per_second),
        "kernel_sweeps": {k: dict(v) for k, v in calib.kernels.items()},
    }
    with open(bench_out, "w") as f:
        json.dump(results, f, indent=1)
    print(f"calibration {calib.digest()} -> {calibration_out} "
          f"(sweep winners merged into {bench_out})", flush=True)
    return results


if __name__ == "__main__":
    argv = sys.argv[1:]
    cal_only, out_calib, spec, quick, rest, i = \
        False, "results/calibration.json", None, False, [], 0
    while i < len(argv):
        a = argv[i]
        if a == "--calibrate-only":
            cal_only, i = True, i + 1
        elif a == "--calibration-out":
            out_calib, i = argv[i + 1], i + 2
        elif a.startswith("--calibration-out="):
            out_calib, i = a.split("=", 1)[1], i + 1
        elif a == "--mesh":
            spec, i = argv[i + 1], i + 2
        elif a.startswith("--mesh="):
            spec, i = a.split("=", 1)[1], i + 1
        elif a == "--quick":
            quick, i = True, i + 1
        else:
            rest.append(a)
            i += 1
    if cal_only:
        calibrate_only(out_calib, spec, quick,
                       rest[0] if rest else "BENCH_strategies.json")
    else:
        run()
