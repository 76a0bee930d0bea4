"""Convolution forward + the paper's per-example conv-gradient trick.

Layout is NC(spatial) for inputs, (D, C/groups, *K) for weights — the
paper's (PyTorch) convention.  Works for 1-D/2-D/3-D convolutions.

``pe_conv_grad`` implements Algorithm 2 of Rochette et al. (2019) on XLA:

  * ``impl="fgc"`` — the paper-faithful lowering: the per-example
    convolution ``x ⊛ δy`` is expressed as a grouped convolution with
    ``feature_group_count = B·Γ``, one *extra* spatial dimension holding
    the layer's input channels, ``stride`` and ``dilation`` swapped, and
    the output truncated to the kernel size.
  * ``impl="bgc"`` — the XLA-native variant using ``batch_group_count``
    (the mechanism XLA itself uses for conv weight gradients); no input
    reshape of the batch into channels is required.  XLA allows only one
    group count > 1, so layer groups Γ fold into the batch groups.
  * ``impl="pallas"`` — the TPU kernel in :mod:`repro.kernels.pe_conv_grad`
    (interpret mode off the TPU): the contraction as MXU matmuls over the
    capture's own layout, for the convolutions the kernel takes
    (``kernels.ops.kernel_takes``: rank 1 or 2, stride 1, undilated,
    ungrouped, padding below the kernel size); ``fgc`` for the others.
  * ``impl="auto"`` — on a TPU, for a convolution the kernel takes,
    ``pallas`` where the input has at least ``MXU_MIN_CHANNELS`` channels
    and one batched MXU dot per kernel tap (tallied ``taps``) where it has
    fewer; ``fgc`` otherwise.

Each is validated against the brute-force oracle in ``kernels/ref.py``
and against autodiff (summed over the batch).
"""
from __future__ import annotations

import itertools

import numpy as np
import jax.numpy as jnp
from jax import lax


# Input channels from which ``impl="auto"`` takes the MXU kernel on a TPU;
# a narrower convolution takes one batched dot per kernel tap.  The kernel
# pads channels to 128 lanes, at most doubling its reads from 64 on; a
# narrower capture (an RGB image) the TPU also keeps in another layout,
# which the kernel would have to copy, 128/C times the size.
MXU_MIN_CHANNELS = 64


def _tup(v, rank: int):
    if isinstance(v, (tuple, list)):
        assert len(v) == rank, (v, rank)
        return tuple(int(x) for x in v)
    return (int(v),) * rank


def _dn(rank: int) -> lax.ConvDimensionNumbers:
    """NC(spatial) everywhere, as explicit index tuples (any rank)."""
    spec = (0, 1) + tuple(range(2, 2 + rank))
    return lax.ConvDimensionNumbers(spec, spec, spec)


def conv_forward(x, w, *, stride=1, dilation=1, padding=0, groups: int = 1):
    """y[b,d,t] = Σ_{c,k} x[b, c, s·t + r·k] · w[d,c,k]  (+ groups)."""
    rank = x.ndim - 2
    s, r, p = _tup(stride, rank), _tup(dilation, rank), _tup(padding, rank)
    return lax.conv_general_dilated(
        x, w, window_strides=s, padding=tuple((pi, pi) for pi in p),
        rhs_dilation=r, dimension_numbers=_dn(rank),
        feature_group_count=groups)


def unfold_patches(x, kernel_spatial, *, stride=1, dilation=1, padding=0):
    """im2col: x (B, C, *S) -> (B, C·K, T) patch matrix, K = prod(kernel),
    T = prod(out_spatial).  Channel ordering is input-channel major /
    filter-position minor, so per-group feature blocks stay contiguous."""
    rank = len(kernel_spatial)
    s, r, p = _tup(stride, rank), _tup(dilation, rank), _tup(padding, rank)
    patches = lax.conv_general_dilated_patches(
        x, filter_shape=tuple(int(k) for k in kernel_spatial),
        window_strides=s, padding=tuple((pi, pi) for pi in p),
        rhs_dilation=r)
    return patches.reshape(x.shape[0], patches.shape[1], -1)


def conv_output_spatial(in_spatial, kernel_spatial, stride, dilation, padding):
    rank = len(kernel_spatial)
    s, r, p = _tup(stride, rank), _tup(dilation, rank), _tup(padding, rank)
    return tuple(
        (t + 2 * pi - ri * (k - 1) - 1) // si + 1
        for t, k, si, ri, pi in zip(in_spatial, kernel_spatial, s, r, p))


def _pe_conv_grad_taps(x, dy, kernel_spatial, padding):
    """A stride-1, undilated, ungrouped convolution's per-example weight
    gradients as one batched dot per kernel tap: δy against the padded
    input's window at that tap, contracting the output positions, f32
    sums."""
    rank = len(kernel_spatial)
    p = _tup(padding, rank)
    xp = jnp.pad(x, ((0, 0), (0, 0)) + tuple((pi, pi) for pi in p))
    space = tuple(range(2, 2 + rank))
    taps = []
    for tap in itertools.product(*map(range, kernel_spatial)):
        window = xp[(slice(None), slice(None)) + tuple(
            slice(t, t + n) for t, n in zip(tap, dy.shape[2:]))]
        taps.append(lax.dot_general(dy, window, ((space, space), ((0,), (0,))),
                                    preferred_element_type=jnp.float32))
    return jnp.stack(taps, -1).reshape(dy.shape[:2] + x.shape[1:2]
                                       + tuple(kernel_spatial))


def pe_conv_grad(x, dy, *, kernel_spatial, stride=1, dilation=1, padding=0,
                 groups: int = 1, impl: str = "auto"):
    """Per-example convolution-weight gradients (Algorithm 2).

    x: (B, C, *S); dy: (B, D, *S').  Returns (B, D, C/Γ, *K).  Each call
    tallies the implementation it took in ``tapper.STATS.conv_impls``.
    """
    from repro.core.tapper import STATS
    from repro.kernels import ops as kops
    if impl not in ("auto", "pallas", "fgc", "bgc"):
        raise ValueError(f"unknown impl {impl!r}")
    if impl in ("auto", "pallas"):
        plain = kops.kernel_takes(kernel_spatial, stride=stride,
                                  dilation=dilation, padding=padding,
                                  groups=groups)
        if not plain:
            impl = "fgc"
        elif impl == "auto":
            impl = ("fgc" if not kops.on_tpu() else "pallas"
                    if x.shape[1] >= MXU_MIN_CHANNELS else "taps")
    STATS.conv_impls[impl] += 1
    if impl == "pallas":
        return kops.pe_conv_grad(x, dy, kernel_spatial=kernel_spatial,
                                 padding=padding)
    if impl == "taps":
        return _pe_conv_grad_taps(x, dy, kernel_spatial, padding)
    rank = len(kernel_spatial)
    B, C = x.shape[:2]
    D = dy.shape[1]
    s, r, p = _tup(stride, rank), _tup(dilation, rank), _tup(padding, rank)
    g = groups

    if impl == "fgc":
        lhs = x.reshape((1, B * g, C // g) + x.shape[2:])
        fgc, bgc = B * g, 1
    else:
        lhs = x.reshape((B * g, 1, C // g) + x.shape[2:])
        fgc, bgc = 1, B * g

    rhs = dy.reshape((B * D, 1, 1) + dy.shape[2:])
    out = lax.conv_general_dilated(
        lhs, rhs,
        window_strides=(1,) + r,                 # stride <- dilation
        padding=((0, 0),) + tuple((pi, pi) for pi in p),
        rhs_dilation=(1,) + s,                   # dilation <- stride
        dimension_numbers=_dn(rank + 1),
        feature_group_count=fgc, batch_group_count=bgc)
    # out: (1, B*D, C/Γ, *K⁺) — truncate the floor-induced extra taps.
    out = out[0]
    out = out[(slice(None), slice(None))
              + tuple(slice(0, k) for k in kernel_spatial)]
    return out.reshape((B, D, C // g) + tuple(kernel_spatial))
