"""Pallas TPU kernel: per-example convolution weight gradients.

The paper's Algorithm 2 as a direct TPU kernel instead of a grouped-conv
lowering: for each example b,

    δh[b,d,c,kh,kw] = Σ_{i,j} x[b,c,i,j] δy[b,d,i-kh+P,j-kw+Q]

as KH·KW MXU matmuls per example and tile of input rows, reading the
operands where they lie.  On the TPU a convolution's activations and
cotangents are kept with the image's rows and columns outermost and
(examples, channels) as the tiled minor pair, i.e. as (H, W, B, C)
arrays; the kernel takes them in that layout, so the transposes in front
of it are layout changes and no copy of a capture is made.  The row
tiles need not divide H: the last one may reach past the image, and its
rows off the image are zeroed in VMEM, like the columns past it.  Each grid
step holds ``th`` rows of x and the th+KH-1 rows of δy they meet, for
one tile's examples (8 in f32, 16 in bf16), flattened to positions
p = row·Wx + col with Wx = W+KW-1 columns, the ones past the image
zeroed.  δy's window is copied into a VMEM scratch at the row its first
output row belongs to, so the first and last row tiles, whose window is
clamped to the image, line up like the others, and its rows outside the
image are zeroed there: tap (kh, kw) then pairs x position p with window
position p + (KH-1-kh)·Wx + Q-kw in every grid step, and one body serves
them all.  One example's positions (a bf16 pair's, which share 32-bit
words) are every eighth 32-bit row of the flattened block, read with a
strided load at the tap's static offset.  Channels are tiled by 128 lanes
(a narrower operand is padded to 128 in VMEM and its padding never
reaches the stored result).  The 1-D kernel is the 2-D one with W = 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mxu import nn

WORDS = 8       # 32-bit sublanes of a tile: 8 f32 or 16 bf16 examples
LANES = 128     # channels per grid step
# Scoped VMEM the kernel may use; row tiles are sized to a budget below it.
VMEM_LIMIT = 100 << 20


def operand_dtype(x_dtype, dy_dtype):
    """The type the kernel multiplies in: bf16 if both operands are bf16,
    else f32."""
    both_bf16 = x_dtype == dy_dtype == jnp.bfloat16
    return jnp.bfloat16 if both_bf16 else jnp.float32


def examples_per_step(dtype) -> int:
    """Examples a grid step holds: a tile's 32-bit sublanes of them."""
    return WORDS * 4 // jnp.dtype(dtype).itemsize


def _out_buffers(eb: int, KH: int, KW: int) -> int:
    """Output buffers: two, so a block's write-back overlaps the next
    block's products, unless one block alone takes over 16 MiB (a large
    kernel's taps)."""
    return 1 if 4 * eb * KH * KW * LANES * LANES > 16 << 20 else 2


def vmem_bytes(th: int, KH: int, KW: int, W: int, eb: int = WORDS) -> int:
    """VMEM of one grid step at ``th`` input rows and ``eb`` examples (8
    f32 or 16 bf16): the double-buffered x tile and δy window, the δy
    scratch, the f32 output tile(s), and the strided operands in
    flight."""
    Wx = W + KW - 1
    n = th * Wx
    blocks = (th + th + KH - 1) * Wx * WORDS * LANES
    scratch = (th + 3 * KH - 2) * Wx * WORDS * LANES
    out = eb * KH * KW * LANES * LANES * _out_buffers(eb, KH, KW)
    return 4 * (2 * blocks + scratch + out + 4 * n * LANES)


def row_tile(H: int, KH: int, KW: int, W: int, budget: int,
             eb: int = WORDS) -> int:
    """Input rows per grid step: the fewest row tiles whose working set
    fits ``budget``, balanced, so the last tile reaches fewer rows past
    ``H`` than there are tiles (1 when no tile fits)."""
    fit = next((th for th in range(H, 0, -1)
                if vmem_bytes(th, KH, KW, W, eb) <= budget), 1)
    tiles = -(-H // fit)
    return -(-H // tiles)


def _examples(ref2, pos, n, k, packed):
    """The examples that word row ``k`` of ``n`` consecutive positions
    from ``pos`` holds, as the MXU's operand type: one f32 example, or a
    bf16 pair (rows 2k, 2k+1 share a 32-bit word).  Written with lax ops:
    the kernel body is traced once per layer shape as the step is traced,
    and jnp's wrappers each trace a jit of their own."""
    u = ref2[pl.ds(lax.add(k, pos * WORDS), n, stride=WORDS), :]
    if not packed:
        return [u]
    hi = lax.bitwise_and(u, np.uint32(0xFFFF0000))
    return [lax.convert_element_type(pltpu.bitcast(w, jnp.float32),
                                     jnp.bfloat16)
            for w in (lax.shift_left(u, np.uint32(16)), hi)]


def _kernel(x_ref, dy_ref, o_ref, w_ref, *, KH, KW, P, Q, H, W, Ho, top):
    th, Wx = x_ref.shape[:2]
    R, Wo = dy_ref.shape[:2]
    U = KH - 1 - P
    nt = -(-H // th)
    r = pl.program_id(3)

    @pl.when(r == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    if Wx > W:
        x_ref[:, W:] = jnp.zeros((th, Wx - W) + x_ref.shape[2:], x_ref.dtype)
    if nt * th > H:
        @pl.when(r == nt - 1)
        def _rows():    # the last tile's rows past the image
            x_ref[H - (nt - 1) * th:] = jnp.zeros(
                (nt * th - H,) + x_ref.shape[1:], x_ref.dtype)

    # The window's row m holds output row r*th - U + m and lies at scratch
    # row 1 + U + m; the block holds output rows from its clamped start.
    start = jnp.clip(r * th - U, 0, top)
    if Wx > Wo:
        w_ref[:, Wo:] = jnp.zeros((w_ref.shape[0], Wx - Wo) + w_ref.shape[2:],
                                  w_ref.dtype)
    w_ref[pl.ds(1 + 2 * U + start - r * th, R), :Wo] = dy_ref[...]
    for t in range(nt):     # zero the window's rows off the image
        lo = min(max(U - t * th, 0), R)
        hi = max(min(Ho + U - t * th, R), lo)
        if (lo, hi) == (0, R):
            continue

        @pl.when(r == t)
        def _edge(lo=lo, hi=hi):
            for a, b in ((0, lo), (hi, R)):
                if b > a:
                    w_ref[1 + U + a:1 + U + b] = jnp.zeros(
                        (b - a,) + w_ref.shape[1:], w_ref.dtype)

    packed = x_ref.dtype == jnp.bfloat16
    if packed:
        x_ref, w_ref = x_ref.bitcast(jnp.uint32), w_ref.bitcast(jnp.uint32)
    x2 = x_ref.reshape(th * Wx * WORDS, LANES)
    w2 = w_ref.reshape(w_ref.shape[0] * Wx * WORDS, LANES)
    bc, bd = o_ref.shape[2], o_ref.shape[3]
    # x's last KW-1 positions are zeroed columns, which no tap needs.
    n = th * Wx - (KW - 1)

    def word_row(k, carry):
        xts = [lax.transpose(g, (1, 0)) for g in _examples(x2, 0, n, k, packed)]
        for kh in range(KH):
            for kw in range(KW):
                off = (1 + U + KH - 1 - kh) * Wx + Q - kw
                ds = _examples(w2, off, n, k, packed)
                for i, (g, d) in enumerate(zip(xts, ds)):
                    acc = nn(g, d)
                    if acc.shape != (bc, bd):
                        acc = lax.slice(acc, (0, 0), (bc, bd))
                    at = (lax.add(lax.mul(k, len(ds)), i), kh * KW + kw)
                    o_ref[at] = lax.add(o_ref[at], acc)
        return carry

    lax.fori_loop(0, WORDS, word_row, 0)


@functools.partial(jax.jit, static_argnames=("KH", "KW", "padding", "th",
                                             "interpret"))
def pe_conv_grad_2d(x, dy, *, KH: int, KW: int, padding=(0, 0), th: int = 0,
                    interpret: bool = True):
    """x (B,C,H,W), dy (B,D,H',W') -> (B,D,C,KH,KW) fp32, for a stride-1,
    undilated, ungrouped convolution with ``padding`` (P, Q) rows and
    columns on each side, 0 <= P < KH and 0 <= Q < KW.  ``th`` input rows
    per grid step, all of them when 0; the last of the ceil(H/th) row
    tiles may reach past H, and no operand is padded in HBM.  bf16 operands
    (both) are multiplied as bf16 on the MXU, anything else as f32; the
    sums are f32."""
    B, C, H, W = x.shape
    _, D, Ho, Wo = dy.shape
    P, Q = padding
    assert 0 <= P < KH and 0 <= Q < KW, (padding, KH, KW)
    assert (Ho, Wo) == (H + 2 * P - KH + 1, W + 2 * Q - KW + 1)
    th = th or H
    nt = -(-H // th)
    U, R, Wx = KH - 1 - P, th + KH - 1, W + KW - 1
    # Where the last tile's δy block starts: on the image's last R rows,
    # unless the scratch, whose first row is output row r*th - 2U - 1,
    # begins below them (a ragged last tile); then there, past the image.
    top = max(Ho - R, (nt - 1) * th - 2 * U - 1, 0)
    dtype = operand_dtype(x.dtype, dy.dtype)
    eb = examples_per_step(dtype)
    nb, nd, nc = -(-B // eb), -(-D // LANES), -(-C // LANES)
    E = pl.Element
    b_blk = E(eb, (0, nb * eb - B))
    xt = x.astype(dtype).transpose(2, 3, 0, 1)
    dyt = dy.astype(dtype).transpose(2, 3, 0, 1)
    out = pl.pallas_call(
        functools.partial(_kernel, KH=KH, KW=KW, P=P, Q=Q, H=H, W=W, Ho=Ho,
                          top=top),
        grid=(nb, nd, nc, nt),
        in_specs=[
            pl.BlockSpec((E(th, (0, nt * th - H)), E(Wx, (0, Wx - W)), b_blk,
                          E(LANES, (0, nc * LANES - C))),
                         lambda e, d, c, r: (r * th, 0, e * eb, c * LANES)),
            pl.BlockSpec((E(R, (0, top + R - Ho)), E(Wo), b_blk,
                          E(LANES, (0, nd * LANES - D))),
                         lambda e, d, c, r: (
                             jnp.clip(r * th - U, 0, top), 0,
                             e * eb, d * LANES)),
        ],
        out_specs=pl.BlockSpec((eb, KH * KW, min(C, LANES), min(D, LANES)),
                               lambda e, d, c, r: (e, 0, c, d),
                               pipeline_mode=(
                                   None if _out_buffers(eb, KH, KW) == 2
                                   else pl.Buffered(1))),
        out_shape=jax.ShapeDtypeStruct((B, KH * KW, C, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1 + 2 * U + R, Wx, eb, LANES), dtype)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="pe_conv_grad",
    )(xt, dyt)
    return out.reshape(B, KH, KW, C, D).transpose(0, 4, 3, 1, 2)


@functools.partial(jax.jit, static_argnames=("K", "padding", "th",
                                             "interpret"))
def pe_conv_grad_1d(x, dy, *, K: int, padding: int = 0, th: int = 0,
                    interpret: bool = True):
    """x (B,C,T), dy (B,D,T') -> (B,D,C,K); stride=dilation=1, groups=1."""
    out = pe_conv_grad_2d(x[..., None], dy[..., None], KH=K, KW=1,
                          padding=(padding, 0), th=th, interpret=interpret)
    return out[..., 0]
