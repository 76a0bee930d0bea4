"""Pallas TPU kernel: blockwise online-softmax (flash) attention.

Causal GQA attention without materializing the (T, S) score matrix in HBM.
Forward grid (B, H, T/bq, S/bk); the last grid dim is sequential and
carries the online-softmax state (row max m, row sum l, output
accumulator) in VMEM scratch.  GQA is handled in the k/v index maps
(h -> h // rep) so the shared KV heads are never physically repeated.

The forward also emits the log-sum-exp rows (L = m + log l), which makes
the backward a pure recomputation pass: ``jax.custom_vjp`` wires in two
blockwise kernels — dq on the forward grid, dk/dv on a (B, Hkv, S/bk,
rep*T/bq) grid whose sequential last dim accumulates over both query
blocks and the GQA head group — so per-example attention gradients never
materialize either.  That differentiability is what lets the DP path run
ghost norms *through* an attention block (the tap cotangents of the
wq/wk/wv/wo projections come out of one ordinary backward).

Used by the serving prefill path (32k-sequence attention is memory-bound;
the score tensor alone would be T²·H·4 bytes) and by training whenever
``models.attention.attend`` dispatches ``impl="flash"``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mxu import nn, nt, tn

NEG = -1e30


class FlashShapeError(ValueError):
    """Sequence/block geometry ``flash_attention`` cannot run (named, so
    32k-prefill callers get a message instead of a stripped ``assert``)."""


def _causal_mask(s, i, j, bq, bk):
    qi = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kj = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(kj <= qi, s, NEG)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                  acc_scr, *, scale: float, bq: int, bk: int, causal: bool):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                       # (bq, hd)
    k = k_ref[0, 0]                       # (bk, hd)
    v = v_ref[0, 0]
    s = nt(q, k) * scale

    if causal:
        s = _causal_mask(s, i, j, bq, bk)

    # Row statistics stay (bq, 1) columns: the TPU lays 2-D values out in
    # (8, 128) tiles, and a (bq,) vector would need a relayout.
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + nn(p.astype(v.dtype), v)
    m_scr[...] = m_new

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[...] + jnp.log(l)


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, dq_scr, *, scale: float, bq: int, bk: int,
                     causal: bool):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = do_ref[0, 0]
    s = nt(q, k) * scale
    if causal:
        s = _causal_mask(s, i, j, bq, bk)
    p = jnp.exp(s - lse_ref[0, 0])
    ds = p * (nt(do, v) - delta_ref[0, 0]) * scale
    dq_scr[...] += nn(ds.astype(k.dtype), k)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                      bq: int, bk: int, causal: bool, n_tq: int):
    jk, t = pl.program_id(2), pl.program_id(3)
    i = t % n_tq                          # query-block index within a head

    @pl.when(t == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = do_ref[0, 0]
    s = nt(q, k) * scale
    if causal:
        s = _causal_mask(s, i, jk, bq, bk)
    p = jnp.exp(s - lse_ref[0, 0])
    dv_scr[...] += tn(p.astype(do.dtype), do)
    ds = p * (nt(do, v) - delta_ref[0, 0]) * scale
    dk_scr[...] += tn(ds.astype(q.dtype), q)

    @pl.when(t == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _fwd_call(q, k, v, causal, bq, bk, interpret):
    """(o, lse) on (B,T,H,hd)/(B,S,Hkv,hd) inputs; lse is (B,H,T,1) f32."""
    B, T, H, hd = q.shape
    S = k.shape[1]
    rep = H // k.shape[2]
    scale = hd ** -0.5
    qt = jnp.moveaxis(q, 2, 1)            # (B,H,T,hd)
    kt = jnp.moveaxis(k, 2, 1)            # (B,Hkv,S,hd)
    vt = jnp.moveaxis(v, 2, 1)

    scratch = [pltpu.VMEM((bq, 1), jnp.float32),
               pltpu.VMEM((bq, 1), jnp.float32),
               pltpu.VMEM((bq, hd), jnp.float32)]

    out, lse = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, bq=bq, bk=bk,
                          causal=causal),
        grid=(B, H, T // bq, S // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, rep=rep: (b, h // rep, j, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, rep=rep: (b, h // rep, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, H, T, hd), q.dtype),
                   jax.ShapeDtypeStruct((B, H, T, 1), jnp.float32)],
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_fwd",
    )(qt, kt, vt)
    return jnp.moveaxis(out, 1, 2), lse


def _bwd_call(q, k, v, o, lse, do, causal, bq, bk, interpret):
    """(dq, dk, dv) by blockwise recomputation from the saved lse rows."""
    B, T, H, hd = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    n_tq = T // bq
    scale = hd ** -0.5
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    dot = jnp.moveaxis(do, 2, 1)          # (B,H,T,hd)
    # D_i = rowsum(dO ∘ O): the softmax-jacobian correction, cheap in XLA.
    delta = jnp.sum(dot.astype(jnp.float32)
                    * jnp.moveaxis(o, 2, 1).astype(jnp.float32), axis=-1,
                    keepdims=True)

    dq_scr = [pltpu.VMEM((bq, hd), jnp.float32)]
    dkv_scr = [pltpu.VMEM((bk, hd), jnp.float32),
               pltpu.VMEM((bk, hd), jnp.float32)]

    q_spec = pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, hd),
                           lambda b, h, i, j, rep=rep: (b, h // rep, j, 0))
    row_spec = pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0))
    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, scale=scale, bq=bq, bk=bk,
                          causal=causal),
        grid=(B, H, T // bq, S // bk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, T, hd), q.dtype),
        scratch_shapes=dq_scr,
        interpret=interpret,
        name="flash_dq",
    )(qt, kt, vt, dot, lse, delta)

    # dk/dv: sequential last dim walks (head-group r, query block i) pairs
    # so each (b, hkv, key-block) accumulates over every query that saw it.
    def _qi(b, hkv, jk, t, rep=rep, n_tq=n_tq):
        return (b, hkv * rep + t // n_tq, t % n_tq, 0)

    def _rows(b, hkv, jk, t, rep=rep, n_tq=n_tq):
        return (b, hkv * rep + t // n_tq, t % n_tq, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, scale=scale, bq=bq, bk=bk,
                          causal=causal, n_tq=n_tq),
        grid=(B, Hkv, S // bk, rep * n_tq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), _qi),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, jk, t: (b, h, jk, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, jk, t: (b, h, jk, 0)),
            pl.BlockSpec((1, 1, bq, hd), _qi),
            pl.BlockSpec((1, 1, bq, 1), _rows),
            pl.BlockSpec((1, 1, bq, 1), _rows),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, jk, t: (b, h, jk, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, jk, t: (b, h, jk, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, S, hd), k.dtype),
                   jax.ShapeDtypeStruct((B, Hkv, S, hd), v.dtype)],
        scratch_shapes=dkv_scr,
        interpret=interpret,
        name="flash_dkv",
    )(qt, kt, vt, dot, lse, delta)
    return (jnp.moveaxis(dq, 1, 2), jnp.moveaxis(dk, 1, 2),
            jnp.moveaxis(dv, 1, 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, bq, bk, interpret):
    o, _ = _fwd_call(q, k, v, causal, bq, bk, interpret)
    return o


def _flash_fwd_rule(q, k, v, causal, bq, bk, interpret):
    o, lse = _fwd_call(q, k, v, causal, bq, bk, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, bq, bk, interpret, res, do):
    q, k, v, o, lse = res
    return _bwd_call(q, k, v, o, lse, do, causal, bq, bk, interpret)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, bq: int = 512,
                    bk: int = 512, interpret: bool | None = None):
    """q (B,T,H,hd); k,v (B,S,Hkv,hd) with H % Hkv == 0 -> (B,T,H,hd).

    Differentiable (``jax.custom_vjp`` over the blockwise backward).
    ``interpret=None`` derives the Pallas interpret flag from the backend
    (compiled on TPU, interpreted elsewhere).  Query lengths that don't
    divide ``bq`` are zero-padded and sliced back (padded rows are dead:
    each query row is independent); key lengths that don't divide ``bk``
    raise :class:`FlashShapeError` — padding keys would corrupt every
    real row's softmax normalizer.
    """
    B, T, H, hd = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    if Hkv == 0 or H % Hkv:
        raise FlashShapeError(
            f"flash_attention: {H} query heads are not a multiple of "
            f"{Hkv} kv heads")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bq, bk = min(bq, T), min(bk, S)
    if S % bk:
        raise FlashShapeError(
            f"flash_attention: key length S={S} does not divide into key "
            f"blocks of bk={bk}; pass a bk dividing S (zero-padding keys "
            f"would corrupt the softmax normalizer)")
    pad = -T % bq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    out = _flash(q, k, v, causal, bq, bk, interpret)
    return out[:, :T] if pad else out
