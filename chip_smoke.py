#!/usr/bin/env python3
"""Smoke run of the DP-SGD trainer on TPU chips.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded step on a four-chip host

One chip: VGG16 at full width (3x256x256, 1000 classes) trains a few
private steps with noise through ``repro.launch.train.main``; then the
planned clipped gradient sum is checked against a plain float32
``vmap(grad)`` reference, and each Pallas kernel against its jnp
reference.  Four chips: the VGG16 private step on ``data:4`` and on
``data:2,model:2`` against the single-device step of the same batch.

Everything runs in this one process, which holds the chips.  Without a
TPU it exits non-zero before doing anything.  Any failed check exits
non-zero; the last line of stdout is then a JSON object naming the
device only when every phase passed.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

ARCH = "vgg16"
CLIP = 1.0
# Training steps (the first compiles) and batch: the whole-step compile for
# one v5e chip puts batch 16 at 6.4 GiB of the 16 GB.
STEPS, BATCH = 6, 16
EXACT_BATCH = 4
# Planned clipped sum vs the float32 reference, both at "highest" matmul
# precision: max over parameter leaves of max|planned - ref| / max|ref|.
EXACT_RTOL = 1e-3
# Compiled Pallas kernels vs their float32 jnp references:
# max|kernel - ref| / max|ref| per output.  The flash kernel runs in bf16,
# whose rounding alone is ~4e-3.
KERNEL_RTOL = {"f32": 1e-2, "bf16": 5e-2}
# Sharded vs single-device step: max |params| difference after the steps.
SHARD_ATOL = 1e-5


def _device():
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform is {d.platform!r});"
                 f" this script runs only on TPU chips")
    print(f"[device] platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def _check(ok: bool, msg: str):
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def _rel_err(got, want) -> float:
    want = jnp.asarray(want, jnp.float32)
    diff = jnp.max(jnp.abs(jnp.asarray(got, jnp.float32) - want))
    return float(diff / jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))


def _vgg16():
    from repro.configs import get_config
    from repro.models.registry import build_model
    cfg = get_config(ARCH)
    model = build_model(cfg)
    params, axes = model.init(jax.random.PRNGKey(0))
    return cfg, model, params, axes


def phase_train(steps: int, batch: int):
    from repro.calibrate import table
    from repro.core import costmodel
    from repro.launch import train

    # The committed calibration was measured on a CPU: it must not price
    # a plan on the chip.
    path = os.path.join(ROOT, "results", "calibration.json")
    try:
        table.load_calibration(path)
    except table.CalibrationHardwareMismatch as e:
        print(f"[calibration] results/calibration.json rejected: {e}")
    else:
        _check(False, "results/calibration.json (a CPU calibration) was "
                      "accepted on the chip")

    print(f"[train] {ARCH} full width, batch {batch}, {steps} steps, "
          f"strategy auto, clip flat C={CLIP}, noise 0.8")
    run = train.main(["--arch", ARCH, "--full", "--strategy", "auto",
                      "--clip-mode", "flat", "--noise", "0.8",
                      "--clip", str(CLIP), "--steps", str(steps),
                      "--batch", str(batch)])
    cal = run.engine.calibration
    if cal is None:
        print(f"[train] plan priced by the analytic constants "
              f"{costmodel.ANALYTIC_FALLBACK}")
    else:
        print(f"[train] plan priced by calibration {cal.digest()} "
              f"(source={cal.source}, hardware={cal.hardware})")
    _check(len(run.losses) == steps
           and all(math.isfinite(x) for x in run.losses),
           f"losses {run.losses}")
    ms = [round(s * 1e3, 3) for s in run.step_seconds]
    print(f"[train] step ms (first includes compilation): {ms}")
    steady = sorted(ms[1:])
    print(f"[train] steady step ms: median {steady[len(steady) // 2]} "
          f"min {steady[0]} max {steady[-1]} over {len(steady)} steps "
          f"(smoke reading, not a benchmark)")
    stats = jax.devices()[0].memory_stats()
    _check(bool(stats) and "peak_bytes_in_use" in stats,
           "the device reports no memory stats")
    print(f"[train] peak_bytes_in_use {stats['peak_bytes_in_use']} "
          f"({stats['peak_bytes_in_use'] / 2**30:.2f} GiB of "
          f"{stats.get('bytes_limit', 0) / 2**30:.2f} GiB)")


def _reference_clipped_sum(apply_fn, params, batch, clip):
    """Σ_b min(1, C/‖g_b‖)·g_b from vmap(grad) per-example gradients."""
    from repro.core.tapper import Tapper

    def grad_one(ex):
        ex1 = jax.tree.map(lambda a: a[None], ex)
        return jax.grad(lambda p: apply_fn(p, ex1, Tapper())[0])(params)

    g = jax.vmap(grad_one)(batch)
    leaves = jax.tree.leaves(g)
    B = leaves[0].shape[0]
    norms = jnp.sqrt(sum(jnp.sum(jnp.square(l.reshape(B, -1)), axis=1)
                         for l in leaves))
    coef = jnp.minimum(1.0, clip / norms)
    return jax.tree.map(lambda l: jnp.einsum("b...,b->...", l, coef), g)


def phase_exactness(batch: int):
    from repro.core import DPConfig, PrivacyEngine
    from repro.launch.train import make_batch_fn

    cfg, model, params, _ = _vgg16()
    data = jax.tree.map(jnp.asarray, make_batch_fn(cfg, batch, 0)(0))
    with jax.default_matmul_precision("highest"):
        engine = PrivacyEngine(model.apply, params, data,
                               dp=DPConfig(l2_clip=CLIP, noise_multiplier=0.0,
                                           strategy="auto"))
        print(f"[exact] batch {batch}, noise 0, plan:\n{engine.explain()}")
        planned = jax.jit(lambda p, b: engine.noisy_grad(p, b, denom=1)[1])(
            params, data)
        ref = jax.jit(lambda p, b: _reference_clipped_sum(
            model.apply, p, b, CLIP))(params, data)
    errs = jax.tree.leaves(jax.tree.map(_rel_err, planned, ref))
    err = max(errs)
    print(f"[exact] planned clipped sum vs float32 vmap(grad) reference: "
          f"max relative error {err:.3e} (tolerance {EXACT_RTOL:.0e})")
    _check(err <= EXACT_RTOL, f"exactness error {err} > {EXACT_RTOL}")


def phase_kernels():
    """Each Pallas kernel, compiled, at VGG16 widths (batch 2), the
    pe_conv_grad kernel also at AlexNet conv1's (batch 256), and the flash
    kernel at T=4096, against kernels/ref.py.  The kernels run at
    the default matmul precision, as the trainer runs them; the
    references in float32 at "highest"."""
    from repro.kernels import flash_attn, gram_norm, ops, ref

    key = iter(jax.random.split(jax.random.PRNGKey(1), 16))

    def rnd(shape, dtype=jnp.float32):
        return jax.random.normal(next(key), shape, jnp.float32).astype(dtype)

    def check(name, kind, got, reference):
        with jax.default_matmul_precision("highest"):
            want = reference()
        for part, g, r in zip(name.split(","), jax.tree.leaves(got),
                              jax.tree.leaves(want)):
            err = _rel_err(g, r)
            print(f"[kernel] {part}: max relative error {err:.3e} "
                  f"(tolerance {KERNEL_RTOL[kind]:.0e})")
            _check(err <= KERNEL_RTOL[kind], f"{part} error {err}")

    x, dy, w = rnd((2, 256, 4608)), rnd((2, 256, 512)), rnd((2,))
    check("gram_norm_fused norms,gram_norm_fused contrib,"
          "gram_norm_fused bias", "f32",
          gram_norm.gram_norm_fused(x, dy, w, has_bias=True,
                                    interpret=False),
          lambda: ref.gram_norm_fused_ref(x, dy, w, has_bias=True))
    x, dy = rnd((2, 1024, 4608)), rnd((2, 1024, 512))
    check("gram_norm", "f32",
          gram_norm.gram_norm(x, dy, has_bias=True, interpret=False),
          lambda: ref.gram_norm_ref(x, dy, has_bias=True))
    x, dy = rnd((2, 512, 32, 32)), rnd((2, 512, 32, 32))
    check("pe_conv_grad_2d", "f32",
          ops.pe_conv_grad(x, dy, kernel_spatial=(3, 3), padding=1),
          lambda: ref.pe_conv_grad_2d_ref(
              jnp.pad(x, ((0, 0), (0, 0), (1, 1), (1, 1))), dy, 3, 3))
    # AlexNet conv1 at its cell's shape: 31 rows in two tiles, the last
    # reaching a row past the image, whose contents VMEM leaves undefined
    x, dy = rnd((256, 64, 31, 31)), rnd((256, 192, 31, 31))
    check("pe_conv_grad_2d alexnet.conv1", "f32",
          ops.pe_conv_grad(x, dy, kernel_spatial=(5, 5), padding=2),
          lambda: ref.pe_conv_grad_2d_ref(
              jnp.pad(x, ((0, 0), (0, 0), (2, 2), (2, 2))), dy, 5, 5))
    qkv = [rnd((1, 4096, h, 128), jnp.bfloat16) for h in (8, 2, 2)]
    f32 = [a.astype(jnp.float32) for a in qkv]
    check("flash_attention", "bf16",
          flash_attn.flash_attention(*qkv, interpret=False),
          lambda: ref.flash_attention_ref(*f32))

    def grads(fn, args):
        def loss(*a):
            return jnp.sum(jnp.sin(fn(*a).astype(jnp.float32)))
        return jax.grad(loss, argnums=(0, 1, 2))(*args)

    check("flash_attention dq,flash_attention dk,flash_attention dv",
          "bf16",
          grads(lambda *a: flash_attn.flash_attention(*a, interpret=False),
                qkv),
          lambda: grads(ref.flash_attention_ref, f32))


def phase_sharded(batch: int, steps: int = 2):
    from repro.core import DPConfig, PrivacyEngine, costmodel
    from repro.launch.mesh import make_mesh_from_spec
    from repro.launch.train import make_batch_fn
    from repro.optim import sgdm_init

    cfg, model, params, axes = _vgg16()
    batch_fn = make_batch_fn(cfg, batch, 0)
    batches = [jax.tree.map(jnp.asarray, batch_fn(s)) for s in range(steps)]
    dp = DPConfig(l2_clip=CLIP, noise_multiplier=0.8, strategy="auto")

    def run(spec):
        mesh = make_mesh_from_spec(spec) if spec else None
        costmodel.clear_plan_cache()
        engine = PrivacyEngine(model.apply, params, batches[0], dp=dp,
                               optimizer="sgdm", lr=1e-2, mesh=mesh,
                               param_axes=axes, run_seed=7,
                               calibration="analytic")
        print(f"[sharded] {spec or 'single device'} plan:\n"
              f"{engine.explain()}")
        p, o, losses = params, sgdm_init(params), []
        for s in range(steps):
            p, o, loss, _ = engine.private_step(p, o, batches[s], step=s)
            losses.append(float(loss))
        return p, losses

    with jax.default_matmul_precision("highest"):
        p1, l1 = run(None)
        for spec in ("data:4", "data:2,model:2"):
            p, losses = run(spec)
            diff = max(jax.tree.leaves(jax.tree.map(
                lambda a, b: float(jnp.max(jnp.abs(a - b))), p, p1)))
            dloss = max(abs(a - b) for a, b in zip(losses, l1))
            print(f"[sharded] {spec} vs single device after {steps} noised "
                  f"steps: max |params| diff {diff:.3e}, max |loss| diff "
                  f"{dloss:.3e} (tolerance {SHARD_ATOL:.0e})")
            _check(diff <= SHARD_ATOL, f"{spec} params differ by {diff}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    device = _device()
    _check(device["count"] >= args.chips,
           f"{args.chips} chips asked for, {device['count']} found")
    from repro.launch.compile_cache import use_compile_cache
    print(f"[cache] compiled programs persist in {use_compile_cache()}")
    if args.chips == 4:
        phase_sharded(BATCH)
    else:
        phase_train(STEPS, BATCH)
        phase_exactness(EXACT_BATCH)
        phase_kernels()
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
