"""Pallas TPU kernels: per-example ghost norms and the fused norm+contribution.

``gram_norm`` computes, per example b,

    out[b] = Σ_{t,t'} (x_{b,t}·x_{b,t'}) (δy_{b,t}·δy_{b,t'})   [+ bias term]

i.e. ‖δy_bᵀ x_b‖²_F without materializing either the per-example gradient
(T·Din·Dout) or the full (T,T) Gram matrices in HBM.  XLA realizes the same
contraction as two (B,T,T) batched matmuls with an HBM round-trip between
them; here the (bt × bt) Gram tiles live only in VMEM and feed the MXU
twice per tile pair.  Grid (B, T/bt, T/bt); each example's (1, 1) output
tile is revisited across the two inner grid dims and accumulated in place.

``gram_norm_fused`` returns the norms *and* the weighted contribution
Σ_b w_b x_bᵀ δy_b.  It tiles the (Din, Dout) contribution: grid
(Din/tdi, Dout/tdo, B, T/bt), and each example's gradient tile
x_b[:, di]ᵀ δy_b[:, do] is accumulated over T in VMEM.  That tile is what
the contribution needs anyway (Σ_b w_b · tile), and the squared norm is the
sum over tiles of ‖tile‖²_F — so the norm costs no matmul beyond the
contribution's own, and the per-example gradient never reaches HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mxu import nt, tn

DEFAULT_BT = 256
# VMEM the row tiles of one grid step may take, double-buffering
# included: under the 16 MiB a v5e kernel may scope by default.
VMEM_TILE_BUDGET = 12 << 20
# Largest contribution tile edge (f32 tile of 1 MiB at 512 x 512).
MAX_FEATURE_TILE = 512


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _sum11(a):
    return jnp.sum(a, axis=(0, 1), keepdims=True)


def _row_tile(T: int, bt: int, widths: int, itemsize: int) -> int:
    """The T tile: at most ``bt`` and T rounded up to 8 rows, halved until
    the four double-buffered (bt, width) input tiles fit the budget."""
    bt = min(bt, _round_up(T, 8))
    while bt > 8 and 4 * bt * widths * itemsize > VMEM_TILE_BUDGET:
        bt = _round_up(bt // 2, 8)
    return bt


def _pad_axis(a, axis: int, size: int):
    pad = size - a.shape[axis]
    if not pad:
        return a
    cfg = [(0, 0)] * a.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(a, cfg)


def _gram_kernel(x_i, x_j, y_i, y_j, o_ref, *, has_bias: bool):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when((i == 0) & (j == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    gx = nt(x_i[0], x_j[0])
    gy = nt(y_i[0], y_j[0])
    prod = gx * gy
    if has_bias:
        prod = prod + gy
    o_ref[0] += _sum11(prod)


@functools.partial(jax.jit,
                   static_argnames=("has_bias", "bt", "interpret"))
def gram_norm(x, dy, *, has_bias: bool = False, bt: int = DEFAULT_BT,
              interpret: bool = True):
    """x (B,T,Din), dy (B,T,Dout) -> (B,) fp32 squared per-example norms."""
    B, T, Di = x.shape
    Do = dy.shape[-1]
    bt = _row_tile(T, bt, _round_up(Di, 128) + _round_up(Do, 128),
                   x.dtype.itemsize)
    Tp = _round_up(T, bt)
    x, dy = _pad_axis(x, 1, Tp), _pad_axis(dy, 1, Tp)
    out = pl.pallas_call(
        functools.partial(_gram_kernel, has_bias=has_bias),
        grid=(B, Tp // bt, Tp // bt),
        in_specs=[
            pl.BlockSpec((1, bt, Di), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bt, Di), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bt, Do), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bt, Do), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1), lambda b, i, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, 1), jnp.float32),
        interpret=interpret,
        name="gram_norm",
    )(x, x, dy, dy)
    return out[:, 0, 0]


def _feature_tile(D: int) -> tuple[int, int]:
    """(tile, padded D): the whole dim when it is small, else a lane-aligned
    tile of at most MAX_FEATURE_TILE over D padded to 128."""
    if D <= MAX_FEATURE_TILE:
        return D, D
    Dp = _round_up(D, 128)
    tile = next(t for t in range(MAX_FEATURE_TILE, 0, -128) if Dp % t == 0)
    return tile, Dp


def _fused_kernel(w_ref, x_ref, y_ref, n_ref, c_ref, cb_ref, g_scr, gb_scr,
                  *, has_bias: bool):
    di, b, t = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when((b == 0) & (t == 0))
    def _init_contrib():
        c_ref[...] = jnp.zeros_like(c_ref)
        cb_ref[...] = jnp.zeros_like(cb_ref)

    @pl.when(t == 0)
    def _init_example():
        g_scr[...] = jnp.zeros_like(g_scr)
        gb_scr[...] = jnp.zeros_like(gb_scr)

    y = y_ref[0]
    g_scr[...] += tn(x_ref[0], y)
    if has_bias:
        gb_scr[...] += jnp.sum(y.astype(jnp.float32), axis=0, keepdims=True)

    @pl.when(t == pl.num_programs(3) - 1)
    def _finish():
        w = w_ref[b]
        g = g_scr[...]
        sq = _sum11(g * g)
        c_ref[...] += w * g
        if has_bias:
            # The bias gradient is the same for every Din tile: count it
            # on the first.
            gb = jnp.where(di == 0, gb_scr[...], 0.0)
            sq = sq + _sum11(gb * gb)
            cb_ref[0] += w * gb
        n_ref[0, 0, 0] = sq


@functools.partial(jax.jit, static_argnames=("has_bias", "bt", "interpret"))
def gram_norm_fused(x, dy, w, *, has_bias: bool = False,
                    bt: int = DEFAULT_BT, interpret: bool = True):
    """Fused per-example norm + weighted contribution in one pass.

    x (B,T,Din), dy (B,T,Dout), w (B,) ->
        norms_sq (B,) fp32, contrib (Din,Dout) = Σ_b w_b·x_bᵀδy_b fp32,
        bias contrib (Dout,) = Σ_b w_b·Σ_t δy_bt (zeros unless has_bias).

    Requires the weights to be known entering the pass — i.e. the
    book-keeping sum phase, stale-coefficient pipelines, or per-layer
    clipping (where a layer's coefficient depends only on its own norm).
    """
    B, T, Di = x.shape
    Do = dy.shape[-1]
    tdi, Dip = _feature_tile(Di)
    tdo, Dop = _feature_tile(Do)
    bt = _row_tile(T, bt, (_round_up(tdi, 128) + _round_up(tdo, 128)) // 2,
                   x.dtype.itemsize)
    Tp = _round_up(T, bt)
    x = _pad_axis(_pad_axis(x, 1, Tp), 2, Dip)
    dy = _pad_axis(_pad_axis(dy, 1, Tp), 2, Dop)
    n_di, n_do = Dip // tdi, Dop // tdo
    norms, contrib, cb = pl.pallas_call(
        functools.partial(_fused_kernel, has_bias=has_bias),
        grid=(n_di, n_do, B, Tp // bt),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bt, tdi), lambda i, o, b, t: (b, t, i)),
            pl.BlockSpec((1, bt, tdo), lambda i, o, b, t: (b, t, o)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, 1, 1),
                         lambda i, o, b, t: (i, o, b, 0, 0)),
            pl.BlockSpec((tdi, tdo), lambda i, o, b, t: (i, o)),
            pl.BlockSpec((1, 1, tdo), lambda i, o, b, t: (i, 0, o)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_di, n_do, B, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((Dip, Dop), jnp.float32),
            jax.ShapeDtypeStruct((n_di, 1, Dop), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((tdi, tdo), jnp.float32),
                        pltpu.VMEM((1, tdo), jnp.float32)],
        interpret=interpret,
        name="gram_norm_fused",
    )(w.astype(jnp.float32), x, dy)
    return (jnp.sum(norms, axis=(0, 1))[:, 0, 0], contrib[:Di, :Do],
            cb[0, 0, :Do])
