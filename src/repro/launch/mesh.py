"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Target: TPU v5e pods, 256 chips each, mesh
(data=16, model=16); the multi-pod mesh adds a leading "pod" axis that the
launchers treat as an extra pure-data axis.
"""
from __future__ import annotations

import math
import os

import jax

# Mesh axes treated as pure data parallelism (batch-sharded); every other
# axis is model parallelism.
DATA_AXIS_NAMES = ("pod", "data", "batch")


def make_auto_mesh(shape, names, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the engine's shardings
    are written for the compiler to propagate, not for explicit-sharding
    typing (which ``jax.make_mesh`` defaults to)."""
    return jax.make_mesh(tuple(shape), tuple(names), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(jax.devices())} — "
            "the dry-run launcher must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import")
    return make_auto_mesh(shape, axes, devices=devices)


def make_host_mesh(model_par: int = 1):
    """Small mesh over whatever devices exist (tests, CPU training)."""
    n = len(jax.devices())
    data = n // model_par
    return make_auto_mesh((data, model_par), ("data", "model"),
                          devices=jax.devices()[: data * model_par])


def force_host_device_count_for(argv):
    """Pre-main hook for CLI entry points: when ``argv`` carries a
    ``--mesh data:N`` spec and ``XLA_FLAGS`` is unset, force the host
    platform to N devices.  Must run before jax initializes its backend
    (merely having imported jax is fine — the device count locks at
    first use)."""
    if "XLA_FLAGS" in os.environ:
        return
    specs = []
    for i, a in enumerate(argv):
        if a.startswith("--mesh="):
            specs.append(a.split("=", 1)[1])
        elif a == "--mesh":
            # Multi-valued form (dpcheck lanes): consume every value up
            # to the next flag; the host must cover the *largest* lane.
            j = i + 1
            while j < len(argv) and not argv[j].startswith("--"):
                specs.append(argv[j])
                j += 1
    n = max((math.prod(int(p.split(":")[1]) for p in s.split(",")
                       if ":" in p)
             for s in specs), default=1)
    if n <= 1:
        return
    os.environ["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={n}"


def make_mesh_from_spec(spec: str):
    """Build a live ``jax.sharding.Mesh`` from a planner mesh spec like
    ``"data:8"`` or ``"data:4,model:2"`` (see ``costmodel.mesh_axes``) over
    this process's devices.  The device count must cover the mesh; on a
    CPU host set ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    before any jax import."""
    from repro.core.costmodel import format_mesh, mesh_axes

    axes = mesh_axes(spec)
    if not axes:
        return None
    shape = tuple(s for _, s in axes)
    names = tuple(n for n, _ in axes)
    n = math.prod(shape)
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"mesh {format_mesh(axes)} needs {n} devices, have "
            f"{len(jax.devices())} — on CPU hosts set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} before "
            "any jax import")
    return make_auto_mesh(shape, names, devices=jax.devices()[:n])
