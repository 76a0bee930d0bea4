"""jit'd public wrappers + platform dispatch for the Pallas kernels.

On TPU the kernels are compiled; on the CPU (tests, and the multi-device
dry-run on the host platform) ``interpret=True`` executes the kernel body
for correctness, or the pure-jnp reference is used where the interpreter
would be too slow.  ``on_tpu()`` centralizes the decision.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import flash_attn as _fa
from repro.kernels import gram_norm as _gn
from repro.kernels import pe_conv_grad as _pc
from repro.kernels import ref as _ref
from repro.launch.mesh import DATA_AXIS_NAMES

# VMEM the pe_conv_grad row tile is sized against (double-buffering
# included), below the kernel's scoped limit ``pe_conv_grad.VMEM_LIMIT``.
# The *analytic* default — vmem_budget() prefers the measured sweep
# winner from a registered calibration, and REPRO_VMEM_BUDGET overrides
# both.
VMEM_BUDGET = 48 << 20


# Matmul precisions at which the TPU multiplies f32 operands in one bf16
# MXU pass (jax_default_matmul_precision; None is the default).
ONE_BF16_PASS = (None, "default", "fastest", "bfloat16")


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def vmem_budget() -> int:
    """The VMEM budget pe_conv_grad autotunes against, by precedence:
    ``REPRO_VMEM_BUDGET`` env override > the ``pe_conv_grad`` sweep
    winner in the registered calibration for the live hardware (see
    ``repro.calibrate.harness.sweep_pe_conv_vmem``) > the analytic
    :data:`VMEM_BUDGET`.  Read per call, outside the autotune cache, so
    registering a calibration mid-process takes effect."""
    env = os.environ.get("REPRO_VMEM_BUDGET")
    if env:
        return max(int(env), 1)
    try:
        from repro.calibrate import table as _ct
    except ImportError:       # pragma: no cover - calibrate always ships
        return VMEM_BUDGET
    for calib in _ct.registered():
        if calib.hardware != _ct.hardware_signature():
            continue
        budget = calib.kernels.get("pe_conv_grad", {}).get("vmem_budget")
        if budget:
            return int(budget)
    return VMEM_BUDGET


def gram_norm(x, dy, *, has_bias: bool = False, bt: int = 256):
    return _gn.gram_norm(x, dy, has_bias=has_bias, bt=bt,
                         interpret=not on_tpu())


def gram_norm_fused(x, dy, w, *, has_bias: bool = False, bt: int = 256):
    """Fused ghost-norm + weighted contribution (see gram_norm.py).

    On TPU the Pallas kernel forms each example's gradient tile in VMEM
    and feeds both outputs from it; elsewhere the pure-jnp reference
    realizes the same
    contract — the interpreter would dominate any wall-clock the fused
    path is supposed to save (kernel/ref agreement is pinned in
    tests/test_kernels.py)."""
    if on_tpu():
        return _gn.gram_norm_fused(x, dy, w, has_bias=has_bias, bt=bt,
                                   interpret=False)
    return _ref.gram_norm_fused_ref(x, dy, w, has_bias=has_bias)


def _as_tuple(v, n: int) -> tuple:
    return tuple(v) if isinstance(v, (tuple, list)) else (v,) * n


def kernel_takes(kernel_spatial, *, stride=1, dilation=1, padding=0,
                 groups: int = 1) -> bool:
    """Whether the pe_conv_grad kernel computes this convolution: rank 1
    or 2, stride 1, undilated, ungrouped, padding below the kernel size
    (the kernel reads an unpadded capture)."""
    rank = len(kernel_spatial)
    return (rank in (1, 2) and groups == 1
            and _as_tuple(stride, rank) == (1,) * rank
            and _as_tuple(dilation, rank) == (1,) * rank
            and all(0 <= p < k for p, k in
                    zip(_as_tuple(padding, rank), kernel_spatial)))


def per_example(fn, *args):
    """``fn(*args)`` for a function that maps each argument's leading axis,
    the examples, to its result's.  Under a mesh (the engine traces its
    step under its own) ``fn`` runs on each device's examples in a
    ``shard_map`` over the data axes, since the SPMD partitioner cannot
    split a Pallas kernel."""
    from jax.sharding import PartitionSpec as P
    mesh = jax.sharding.get_abstract_mesh()
    axes = tuple(a for a in DATA_AXIS_NAMES if a in mesh.axis_names)
    if not axes:
        return fn(*args)
    spec = P(axes)
    return jax.shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)(*args)


def pe_conv_grad(x, dy, *, kernel_spatial, padding=0):
    """The pe_conv_grad kernel for a convolution it takes
    (:func:`kernel_takes`), with the row tile sized to the VMEM budget
    (tallied in ``tapper.STATS.row_tiles``), over each device's examples
    (:func:`per_example`); interpret mode off the TPU."""
    from repro.core.tapper import STATS
    if on_tpu() and jax.config.jax_default_matmul_precision in ONE_BF16_PASS:
        # The MXU rounds f32 operands to bf16 at this precision.  Rounding
        # them where they are made lets the compiler keep bf16 copies of
        # the capture and cotangent for the kernel, as it does for the
        # grouped convolutions, and halves what the kernel reads.
        x, dy = x.astype(jnp.bfloat16), dy.astype(jnp.bfloat16)
    rank = len(kernel_spatial)
    p = _as_tuple(padding, rank)
    interp = not on_tpu()
    eb = _pc.examples_per_step(_pc.operand_dtype(x.dtype, dy.dtype))
    H = x.shape[2]
    if rank == 1:
        K = kernel_spatial[0]
        th = _pc.row_tile(H, K, 1, 1, vmem_budget(), eb)
        fn = functools.partial(_pc.pe_conv_grad_1d, K=K, padding=p[0], th=th,
                               interpret=interp)
    else:
        KH, KW = kernel_spatial
        th = _pc.row_tile(H, KH, KW, x.shape[3], vmem_budget(), eb)
        fn = functools.partial(_pc.pe_conv_grad_2d, KH=KH, KW=KW, padding=p,
                               th=th, interpret=interp)
    STATS.row_tiles[H, th, -(-H // th)] += 1
    return per_example(fn, x, dy)


def flash_attention(q, k, v, *, causal: bool = True, bq: int = 512,
                    bk: int = 512):
    """Differentiable flash dispatch: the Pallas kernel (custom_vjp
    blockwise backward) on TPU, the interpreter for small CPU shapes,
    and the chunked-XLA reference beyond that — autodiff through the
    chunk loop keeps the backward's score working set one query chunk
    wide, matching the kernel's memory contract."""
    if on_tpu():
        return _fa.flash_attention(q, k, v, causal=causal, bq=bq, bk=bk,
                                   interpret=False)
    # CPU: the interpreter is correct but slow; keep it for small shapes,
    # use the chunked reference beyond that.
    if q.shape[1] * k.shape[1] <= 1 << 20:
        return _fa.flash_attention(q, k, v, causal=causal,
                                   bq=min(bq, q.shape[1]),
                                   bk=min(bk, k.shape[1]), interpret=True)
    return _ref.flash_attention_chunked_ref(q, k, v, causal=causal,
                                            chunk=bq)
