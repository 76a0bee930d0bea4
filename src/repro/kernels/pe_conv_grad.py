"""Pallas TPU kernel: per-example convolution weight gradients.

The paper's Algorithm 2 as a direct TPU kernel instead of a grouped-conv
lowering: for each example b (and output-channel tile),

    δh[b,d,c,kh,kw] = Σ_{h,w} x[b,c,h+kh,w+kw] δy[b,d,h,w]

The wrapper lays both operands out so that every kernel window is a
contiguous row slice.  x goes channels-last and row-flattened, (H·W, C);
δy goes to (D, H'·W) with its columns zero-padded from W' to W.  The window
(kh, kw) of output position p = h·W + w is then x row p + kh·W + kw, and
positions with w ≥ W' (which wrap into the next image row) meet zeros in
δy.  Each grid cell (b, d-tile, row-tile) issues KH·KW MXU matmuls
(bd, rows)·(rows, C) over static row offsets — no gather, no in-kernel
reshape — and accumulates the (KH·KW, bd, C) output tile over the row
tiles.  Stride/dilation/padding are handled by the wrapper in ops.py
(padding x), which falls back to the XLA grouped-conv lowering for exotic
configurations.  The 1-D kernel is the 2-D one with W = 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.mxu import nn


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _layout(W: int, th: int, KH: int, KW: int) -> tuple[int, int]:
    """(rw, L): the δy tile's lane-padded row count and the x window rows
    one row tile of ``th`` output rows reads."""
    rw = _round_up(th * W, 128)
    return rw, _round_up(rw + (KH - 1) * W + KW - 1, 8)


def vmem_bytes(bd: int, C: int, W: int, th: int, KH: int, KW: int) -> int:
    """VMEM of one grid step at 4 bytes an element, (8, 128)-tile padding
    and double-buffering included: the x window, the δy tile and the
    output tile."""
    rw, L = _layout(W, th, KH, KW)
    c_lanes = _round_up(C, 128)
    x_tile = L * c_lanes
    dy_tile = _round_up(bd, 8) * rw
    out_tile = KH * KW * _round_up(bd, 8) * c_lanes
    return 2 * 4 * (x_tile + dy_tile + out_tile)


def row_tile(bd: int, C: int, Hp: int, W: int, KH: int, KW: int,
             budget: int) -> int:
    """Output rows per grid step: all of them (rounded up to 8) when they
    fit ``budget``, else halved down to a multiple of 8."""
    th = _round_up(Hp, 8)
    while th > 8 and vmem_bytes(bd, C, W, th, KH, KW) > budget:
        th = _round_up(th // 2, 8)
    return th


def _kernel(x_ref, dy_ref, o_ref, *, KH: int, KW: int, W: int, rw: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    dy = dy_ref[0, 0]                                   # (bd, rw)
    for kh in range(KH):
        for kw in range(KW):
            xs = x_ref[0, 0, pl.ds(kh * W + kw, rw), :]   # (rw, C)
            o_ref[0, kh * KW + kw] += nn(dy, xs)


@functools.partial(jax.jit,
                   static_argnames=("KH", "KW", "bd", "th", "interpret"))
def pe_conv_grad_2d(x, dy, *, KH: int, KW: int, bd: int = 0, th: int = 0,
                    interpret: bool = True):
    """x (B,C,H,W), dy (B,D,H',W') -> (B,D,C,KH,KW) fp32; ``bd`` output
    channels (D or a multiple of 8 dividing D) and ``th`` output rows (a
    multiple of 8) per grid step, all of them when 0."""
    B, C, H, W = x.shape
    _, D, Hp, Wp = dy.shape
    bd = bd or D
    th = th or _round_up(Hp, 8)
    assert D % bd == 0 and th % 8 == 0
    n_r = -(-Hp // th)
    rw, L = _layout(W, th, KH, KW)
    # δy: (B, n_r, D, rw), each row tile flattened and lane-padded.
    g = jnp.pad(dy, ((0, 0), (0, 0), (0, n_r * th - Hp), (0, W - Wp)))
    g = g.reshape(B, D, n_r, th * W)
    g = jnp.pad(g, ((0, 0), (0, 0), (0, 0), (0, rw - th * W)))
    g = g.transpose(0, 2, 1, 3)
    # x: (B, n_r, L, C) overlapping row windows of the flattened image.
    xf = x.transpose(0, 2, 3, 1).reshape(B, H * W, C)
    xf = jnp.pad(xf, ((0, 0), (0, (n_r - 1) * th * W + L - H * W), (0, 0)))
    xw = jnp.stack([xf[:, r * th * W: r * th * W + L] for r in range(n_r)],
                   axis=1)
    out = pl.pallas_call(
        functools.partial(_kernel, KH=KH, KW=KW, W=W, rw=rw),
        grid=(B, D // bd, n_r),
        in_specs=[
            pl.BlockSpec((1, 1, L, C), lambda b, d, r: (b, r, 0, 0)),
            pl.BlockSpec((1, 1, bd, rw), lambda b, d, r: (b, r, d, 0)),
        ],
        out_specs=pl.BlockSpec((1, KH * KW, bd, C),
                               lambda b, d, r: (b, 0, d, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KH * KW, D, C), jnp.float32),
        interpret=interpret,
        name="pe_conv_grad",
    )(xw, g)
    return out.reshape(B, KH, KW, D, C).transpose(0, 3, 4, 1, 2)


@functools.partial(jax.jit, static_argnames=("K", "bd", "th", "interpret"))
def pe_conv_grad_1d(x, dy, *, K: int, bd: int = 0, th: int = 0,
                    interpret: bool = True):
    """x (B,C,T), dy (B,D,T') -> (B,D,C,K); stride=dilation=1, groups=1."""
    out = pe_conv_grad_2d(x[..., None], dy[..., None], KH=K, KW=1, bd=bd,
                          th=th, interpret=interpret)
    return out[..., 0]
