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

# VMEM the pe_conv_grad autotuner plans one grid step against (padded
# tiles, double-buffering included): half of the 16 MiB a v5e kernel may
# scope by default.
# The *analytic* default — vmem_budget() prefers the measured sweep
# winner from a registered calibration, and REPRO_VMEM_BUDGET overrides
# both.
VMEM_BUDGET = 8 << 20


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


def _legal_bds(D: int) -> list:
    """Output-channel tiles the kernel's blocks allow, largest first: D
    itself, or a multiple of 8 dividing D."""
    return [d for d in range(D, 0, -1)
            if D % d == 0 and (d == D or d % 8 == 0)]


def _geometry(x_spatial: tuple, dy_spatial: tuple, k_spatial: tuple):
    """(W, H', KH, KW) of the kernel's flattened layout; a 1-D conv is the
    2-D kernel with W = 1."""
    if len(k_spatial) == 1:
        return 1, dy_spatial[0], k_spatial[0], 1
    return x_spatial[1], dy_spatial[0], k_spatial[0], k_spatial[1]


@functools.lru_cache(maxsize=256)
def _autotune_bd(D: int, C: int, x_spatial: tuple, dy_spatial: tuple,
                 k_spatial: tuple, budget: int = VMEM_BUDGET) -> int:
    """Output-channel tile for the pe_conv_grad grid: the largest legal
    tile whose padded VMEM working set (``pe_conv_grad.vmem_bytes``, at
    the row tile that then fits) stays within the budget; the smallest
    legal tile when none does."""
    W, Hp, KH, KW = _geometry(x_spatial, dy_spatial, k_spatial)
    bds = _legal_bds(D)
    for bd in bds:
        th = _pc.row_tile(bd, C, Hp, W, KH, KW, budget)
        if _pc.vmem_bytes(bd, C, W, th, KH, KW) <= budget:
            return bd
    return bds[-1]


def pick_bd(D: int, C: int, x_spatial: tuple, dy_spatial: tuple,
            k_spatial: tuple, budget: int = VMEM_BUDGET) -> int:
    """Analytic bd choice, overridable with REPRO_PE_CONV_BD (rounded down
    to a legal tile, see ``_legal_bds``).  The env var is read here,
    outside the cache, so mid-process sweeps work."""
    env = os.environ.get("REPRO_PE_CONV_BD")
    if env:
        want = max(1, min(int(env), D))
        legal = _legal_bds(D)
        return next((d for d in legal if d <= want), legal[-1])
    return _autotune_bd(D, C, x_spatial, dy_spatial, k_spatial, budget)


def pe_conv_grad(x, dy, *, kernel_spatial, stride=1, dilation=1, padding=0,
                 groups: int = 1):
    """Pallas path for Algorithm 2, with bd-tiled grid autotuning.  Plain
    convs (stride=dilation=1, groups=1) hit the kernel; anything else
    falls back to the XLA grouped-conv lowering (still the paper's
    algorithm)."""
    from repro.models import convops

    def _as_tuple(v, n):
        return tuple(v) if isinstance(v, (tuple, list)) else (v,) * n

    rank = len(kernel_spatial)
    plain = (groups == 1 and _as_tuple(stride, rank) == (1,) * rank
             and _as_tuple(dilation, rank) == (1,) * rank)
    interp = not on_tpu()
    if plain and rank in (1, 2):
        p = _as_tuple(padding, rank)
        if any(p):
            cfg = [(0, 0), (0, 0)] + [(pi, pi) for pi in p]
            x = jnp.pad(x, cfg)
        budget = vmem_budget()
        x_sp, dy_sp = tuple(x.shape[2:]), tuple(dy.shape[2:])
        C, k_sp = x.shape[1], tuple(kernel_spatial)
        bd = pick_bd(dy.shape[1], C, x_sp, dy_sp, k_sp, budget=budget)
        W, Hp, KH, KW = _geometry(x_sp, dy_sp, k_sp)
        th = _pc.row_tile(bd, C, Hp, W, KH, KW, budget)
        if rank == 1:
            return _pc.pe_conv_grad_1d(x, dy, K=KH, bd=bd, th=th,
                                       interpret=interp)
        return _pc.pe_conv_grad_2d(x, dy, KH=KH, KW=KW, bd=bd, th=th,
                                   interpret=interp)
    return convops.pe_conv_grad(x, dy, kernel_spatial=kernel_spatial,
                                stride=stride, dilation=dilation,
                                padding=padding, groups=groups, impl="fgc")


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
