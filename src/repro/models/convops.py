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
    ungrouped, padding below the kernel size), strided ones through space
    to depth (below); ``fgc`` for the others.
  * ``impl="auto"`` — on a TPU, for a convolution the kernel takes,
    ``pallas`` where the input has at least ``MXU_MIN_CHANNELS`` channels
    and one batched MXU dot per kernel tap (tallied ``taps``) where it has
    fewer; ``fgc`` otherwise.
  * Strided rank-1 and rank-2 convolutions (undilated, ungrouped) go
    through space to depth under ``auto`` on a TPU and under ``pallas``:
    the padded input split into its stride phases is a stride-1 input of
    prod(s)·C channels, whose per-example gradients with ⌈k/s⌉ taps per
    axis the kernel or the per-tap dots form (tallied ``s2d_pallas``,
    ``s2d_taps``); the s·⌈k/s⌉ taps per axis they give are cropped to k.

Each is validated against the brute-force oracle in ``kernels/ref.py``
and against autodiff (summed over the batch).
"""
from __future__ import annotations

import itertools

import numpy as np
import jax.numpy as jnp
from jax import lax


# Input channels from which ``impl="auto"`` takes the MXU kernel on a TPU
# (prod(s)·C for a strided convolution through space to depth); a narrower
# convolution takes one batched dot per kernel tap.  The kernel
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


def _space_to_depth_takes(kernel_spatial, stride, dilation,
                          groups: int) -> bool:
    """Whether a strided convolution's per-example gradients go through
    space to depth: rank 1 or 2, a stride above 1, undilated, ungrouped."""
    rank = len(kernel_spatial)
    return (rank in (1, 2) and groups == 1
            and _tup(dilation, rank) == (1,) * rank
            and max(_tup(stride, rank)) > 1)


def pe_conv_route(kernel_spatial, channels: int, *, stride=1, dilation=1,
                  padding=0, groups: int = 1, impl: str = "auto") -> str:
    """The route :func:`pe_conv_grad` takes under ``impl`` for a
    convolution of ``channels`` input channels, the key it is tallied
    under: ``pallas``, ``taps``, ``s2d_pallas``, ``s2d_taps``, ``fgc`` or
    ``bgc``."""
    from repro.kernels import ops as kops
    if impl not in ("auto", "pallas", "fgc", "bgc"):
        raise ValueError(f"unknown impl {impl!r}")
    if impl in ("fgc", "bgc"):
        return impl
    prefix = ""
    if _space_to_depth_takes(kernel_spatial, stride, dilation, groups):
        channels *= int(np.prod(_tup(stride, len(kernel_spatial))))
        prefix = "s2d_"
    elif not kops.kernel_takes(kernel_spatial, stride=stride,
                               dilation=dilation, padding=padding,
                               groups=groups):
        return "fgc"
    if impl == "auto":
        if not kops.on_tpu():
            return "fgc"
        impl = "pallas" if channels >= MXU_MIN_CHANNELS else "taps"
    return prefix + impl


def route_taps(route: str, kernel_spatial, stride=1) -> int:
    """Kernel taps per example that ``route`` computes: prod(s·⌈k/s⌉)
    through space to depth, whose padded taps are cropped away, else
    prod(k)."""
    if not route.startswith("s2d_"):
        return int(np.prod(kernel_spatial))
    s = _tup(stride, len(kernel_spatial))
    return int(np.prod([si * -(-k // si)
                        for k, si in zip(kernel_spatial, s)]))


def _pe_conv_grad_s2d(x, dy, kernel_spatial, stride, padding, inner):
    """A strided, undilated, ungrouped convolution's per-example weight
    gradients through space to depth.  With k = s·q + r (0 <= r < s), tap
    k of output t reads x_pad[s·(t+q) + r], which is tap q of a stride-1
    convolution over phase r of x_pad: x_pad (B, C, s·n) becomes x'
    (B, s·C, n) with n = T'+⌈k/s⌉-1, and ``inner`` (``pallas`` or
    ``taps``) forms its per-example gradients with ⌈k/s⌉ taps, unpadded;
    of the s·⌈k/s⌉ taps per axis that gives, those from k on are cropped."""
    from repro.kernels import ops as kops
    rank = len(kernel_spatial)
    s, p = _tup(stride, rank), _tup(padding, rank)
    q = tuple(-(-k // si) for k, si in zip(kernel_spatial, s))
    n = tuple(t + qi - 1 for t, qi in zip(dy.shape[2:], q))
    # Pad by the convolution's padding, then pad or crop each axis to s·n.
    xp = lax.pad(x, jnp.zeros((), x.dtype), ((0, 0, 0), (0, 0, 0)) + tuple(
        (pi, si * ni - h - pi, 0)
        for h, pi, si, ni in zip(x.shape[2:], p, s, n)))
    B, C = x.shape[:2]
    split = (B, C) + tuple(d for ni, si in zip(n, s) for d in (ni, si))
    xs = xp.reshape(split).transpose(
        (0,) + tuple(3 + 2 * i for i in range(rank)) + (1,)
        + tuple(2 + 2 * i for i in range(rank)))
    xs = xs.reshape((B, int(np.prod(s)) * C) + n)
    if inner == "pallas":
        g = kops.pe_conv_grad(xs, dy, kernel_spatial=q, padding=0)
    else:
        g = _pe_conv_grad_taps(xs, dy, q, 0)
    # (B, D, *s, C, *q) -> (B, D, C, q0, s0, q1, s1, ...) -> crop to k.
    D = dy.shape[1]
    g = g.reshape((B, D) + s + (C,) + q).transpose(
        (0, 1, 2 + rank) + tuple(d for i in range(rank)
                                 for d in (3 + rank + i, 2 + i)))
    g = g.reshape((B, D, C) + tuple(qi * si for qi, si in zip(q, s)))
    return g[(slice(None),) * 3 + tuple(slice(0, k) for k in kernel_spatial)]


def pe_conv_grad(x, dy, *, kernel_spatial, stride=1, dilation=1, padding=0,
                 groups: int = 1, impl: str = "auto"):
    """Per-example convolution-weight gradients (Algorithm 2).

    x: (B, C, *S); dy: (B, D, *S').  Returns (B, D, C/Γ, *K).  Each call
    tallies the implementation it took in ``tapper.STATS.conv_impls``.
    """
    from repro.core.tapper import STATS
    from repro.kernels import ops as kops
    impl = pe_conv_route(kernel_spatial, x.shape[1], stride=stride,
                         dilation=dilation, padding=padding, groups=groups,
                         impl=impl)
    STATS.conv_impls[impl] += 1
    if impl.startswith("s2d_"):
        return _pe_conv_grad_s2d(x, dy, kernel_spatial, stride, padding,
                                 impl[4:])
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
