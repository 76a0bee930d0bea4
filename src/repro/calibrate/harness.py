"""Microbenchmark harness: measure the constants the planner uses.

One :func:`measure` run produces a :class:`~repro.calibrate.table.Calibration`
for the live (hardware, mesh) pair:

  * dense matmul FLOP rate — the unit every other cost converts into;
  * HBM streaming bandwidth (one read + one write over a large array);
  * per-mesh-axis collective bandwidth: a ring all-reduce over that
    axis's device count, timed at the shard sizes plans actually move
    (stash traffic is MBs per device, not the microscopic latency
    regime), reported as *wire* bytes per device per second — the same
    ``ring(d) * shard_bytes`` convention the cost model charges;
  * Pallas kernel sweeps: ``gram_norm_fused`` wall time and the pending
    ``pe_conv_grad`` VMEM-budget sweep from ``kernels/ops.py`` (the
    winning budget feeds :func:`repro.kernels.ops.vmem_budget`).

Everything is timed through ``jax.jit`` + ``block_until_ready`` with a
compile warmup, min-of-iters.  The harness never guesses: an axis it
cannot measure (more devices than the host has) raises a named
:class:`~repro.calibrate.table.CalibrationMeshMismatch` instead of
inventing a bandwidth.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import costmodel
from repro.calibrate.table import (Calibration, CalibrationMeshMismatch,
                                   hardware_signature)

# Shard sizes (bytes per device) the ring all-reduce is timed at: the
# small end catches latency-bound axes, the large end the stash-traffic
# streaming regime plans actually buy.
COLLECTIVE_SIZES = (1 << 20, 8 << 20)
COLLECTIVE_SIZES_QUICK = (1 << 20,)
# pe_conv_grad VMEM budgets swept (bytes); VMEM_BUDGET's default 48 MiB
# sits in the middle so the sweep can move it either way, and the largest
# stays below the kernel's scoped limit.
VMEM_SWEEP = (12 << 20, 24 << 20, 48 << 20, 72 << 20)


def _time(f, *args, iters: int = 3, warmup: int = 1) -> float:
    for _ in range(warmup):
        jax.block_until_ready(f(*args))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def measure_flops_per_second(*, quick: bool = False) -> float:
    """Dense f32 matmul throughput (the cost model's FLOP unit)."""
    n = 256 if quick else 1024
    a = jnp.ones((n, n), jnp.float32)
    f = jax.jit(lambda x, y: x @ y)
    t = _time(f, a, a, iters=2 if quick else 4)
    return 2.0 * n ** 3 / max(t, 1e-9)


def measure_hbm_bytes_per_second(*, quick: bool = False) -> float:
    """Streaming read+write bandwidth over an array far beyond cache."""
    elems = (4 << 20 if quick else 32 << 20) // 4
    x = jnp.ones((elems,), jnp.float32)
    f = jax.jit(lambda v: v * 1.0000001)
    t = _time(f, x, iters=2 if quick else 4)
    return 2.0 * 4.0 * elems / max(t, 1e-9)


def measure_collective_bytes_per_second(axis: str, size: int, *,
                                        sizes=COLLECTIVE_SIZES) -> float:
    """Ring all-reduce wire bandwidth over ``size`` devices: per-device
    bytes-on-the-wire (``ring(d) * shard_bytes``) per second, the
    convention :mod:`repro.core.costmodel` charges collective traffic
    at.  The best rate over the size sweep is reported (the streaming
    regime, which is what stash traffic sees)."""
    devs = jax.devices()
    if size > len(devs):
        raise CalibrationMeshMismatch(
            f"cannot measure collective bandwidth for mesh axis "
            f"{axis}:{size} — this host has {len(devs)} device(s); "
            f"measure on the target topology")
    if size < 2:
        raise CalibrationMeshMismatch(
            f"mesh axis {axis}:{size} induces no collective traffic; "
            f"nothing to measure")
    sub = devs[:size]
    f = jax.pmap(lambda v: jax.lax.psum(v, "i"), axis_name="i",
                 devices=sub)
    ring = costmodel._ring(size)
    best = 0.0
    for shard_bytes in sizes:
        elems = max(shard_bytes // 4, 1)
        x = jnp.ones((size, elems), jnp.float32)
        t = _time(f, x, iters=3)
        best = max(best, ring * 4.0 * elems / max(t, 1e-9))
    return best


def sweep_pe_conv_vmem(*, quick: bool = False,
                       budgets=VMEM_SWEEP) -> dict:
    """The ``VMEM_BUDGET`` sweep: time ``pe_conv_grad`` under each
    candidate budget's row tile and report the winner.  Budgets that
    resolve to the same tile share one timing."""
    from repro.kernels import ops as kops

    B, C, D, HW, K = (2, 8, 16, 12, 3) if quick else (4, 16, 32, 16, 3)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, C, HW, HW), jnp.float32)
    out_sp = HW - K + 1
    dy = jnp.asarray(rng.randn(B, D, out_sp, out_sp), jnp.float32)
    by_tile: dict[int, float] = {}
    sweep: dict[str, dict] = {}
    for budget in budgets:
        th = kops._pc.row_tile(HW, K, K, HW, budget)
        if th not in by_tile:
            f = jax.jit(lambda a, b, _th=th: kops._pc.pe_conv_grad_2d(
                a, b, KH=K, KW=K, th=_th, interpret=not kops.on_tpu()))
            by_tile[th] = _time(f, x, dy, iters=2 if quick else 3)
        sweep[str(budget)] = {"th": th, "seconds": by_tile[th]}
    winner = min(sweep, key=lambda k: sweep[k]["seconds"])
    return {"vmem_budget": int(winner), "th": sweep[winner]["th"],
            "sweep": sweep}


def time_gram_norm_fused(*, quick: bool = False) -> dict:
    from repro.kernels import ops as kops

    B, T, Dm = (2, 64, 32) if quick else (4, 256, 128)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, T, Dm), jnp.float32)
    dy = jnp.asarray(rng.randn(B, T, Dm), jnp.float32)
    w = jnp.asarray(rng.rand(B), jnp.float32)
    f = jax.jit(lambda a, b, c: kops.gram_norm_fused(a, b, c))
    t = _time(f, x, dy, w, iters=2 if quick else 3)
    return {"seconds": t, "shape": [B, T, Dm]}


def measure(mesh=None, *, quick: bool = False, kernels: bool = True,
            collective_sizes=None) -> Calibration:
    """Run the full harness on the live hardware for ``mesh`` and return
    the resulting :class:`Calibration` (not registered — callers decide;
    see :func:`repro.calibrate.get_or_measure`)."""
    axes = costmodel.mesh_axes(mesh)
    sizes = collective_sizes or (COLLECTIVE_SIZES_QUICK if quick
                                 else COLLECTIVE_SIZES)
    coll = {name: measure_collective_bytes_per_second(name, size,
                                                      sizes=sizes)
            for name, size in axes if size > 1}
    kern = {}
    if kernels:
        kern["pe_conv_grad"] = sweep_pe_conv_vmem(quick=quick)
        kern["gram_norm_fused"] = time_gram_norm_fused(quick=quick)
    return Calibration(
        hardware=hardware_signature(), mesh=axes,
        flops_per_second=measure_flops_per_second(quick=quick),
        hbm_bytes_per_second=measure_hbm_bytes_per_second(quick=quick),
        collective_bytes_per_second=coll, kernels=kern,
        measured_at=time.time(), source="measured")
