"""Pure-jnp oracles for every Pallas kernel (the correctness contracts)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def gram_norm_ref(x, dy, *, has_bias: bool = False):
    """out[b] = ‖δy_bᵀ x_b‖²_F  (+ ‖Σ_t δy‖² if has_bias)."""
    g = jnp.einsum("bti,bto->bio", x.astype(jnp.float32),
                   dy.astype(jnp.float32))
    n = jnp.sum(g * g, axis=(1, 2))
    if has_bias:
        bg = jnp.sum(dy.astype(jnp.float32), axis=1)
        n = n + jnp.sum(bg * bg, axis=1)
    return n


def gram_norm_fused_ref(x, dy, w, *, has_bias: bool = False):
    """Fused ghost-norm + weighted contribution:
    (‖δy_bᵀx_b‖²_F [+ bias], Σ_b w_b·x_bᵀδy_b, Σ_b w_b·Σ_t δy_bt).

    Matches the kernel's cost shape: the norm via the T×T Gram identity
    (never materializing the (B, Din, Dout) per-example products — in
    the Gram regime that materialization costs orders of magnitude more
    FLOPs than the norm itself) and the contribution as one direct
    (B·T)-row contraction."""
    xf, gf = x.astype(jnp.float32), dy.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    sx = jnp.einsum("bti,bsi->bts", xf, xf)
    sy = jnp.einsum("bto,bso->bts", gf, gf)
    n = jnp.einsum("bts,bts->b", sx, sy)
    c = jnp.einsum("b,bti,bto->io", wf, xf, gf)
    cb = jnp.zeros((dy.shape[-1],), jnp.float32)
    if has_bias:
        n = n + jnp.sum(sy, axis=(1, 2))
        cb = jnp.einsum("b,bto->o", wf, gf)
    return n, c, cb


def pe_conv_grad_1d_ref(x, dy, K: int):
    """Brute-force: δh[b,d,c,k] = Σ_t x[b,c,t+k] dy[b,d,t]."""
    B, C, T = x.shape
    _, D, Tp = dy.shape
    xs = jnp.stack([x[:, :, k:k + Tp] for k in range(K)], axis=-1)  # (B,C,Tp,K)
    return jnp.einsum("bctk,bdt->bdck", xs.astype(jnp.float32),
                      dy.astype(jnp.float32))


def pe_conv_grad_2d_ref(x, dy, KH: int, KW: int):
    B, C, H, W = x.shape
    _, D, Hp, Wp = dy.shape
    out = []
    for kh in range(KH):
        row = []
        for kw in range(KW):
            xs = x[:, :, kh:kh + Hp, kw:kw + Wp]
            row.append(jnp.einsum("bchw,bdhw->bdc", xs.astype(jnp.float32),
                                  dy.astype(jnp.float32)))
        out.append(jnp.stack(row, axis=-1))
    return jnp.stack(out, axis=-2)  # (B,D,C,KH,KW)


def flash_attention_ref(q, k, v, *, causal: bool = True):
    B, T, H, hd = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    kr = jnp.repeat(k, rep, axis=2)
    vr = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, kr,
                   preferred_element_type=jnp.float32) * hd ** -0.5
    if causal:
        mask = jnp.tril(jnp.ones((T, S), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p.astype(v.dtype), vr)


def flash_attention_chunked_ref(q, k, v, *, causal: bool = True,
                                chunk: int = 512):
    """Chunked-XLA flash oracle: the (T, S) score matrix exists one query
    chunk at a time, never whole, and autodiff through the chunk loop
    gives the same memory shape backward — the CPU/interpret dispatch
    target for long sequences where ``flash_attention_ref`` would
    materialize T²·H scores (and its backward twice that)."""
    B, T, H, hd = q.shape
    S = k.shape[1]
    rep = H // k.shape[2]
    kr = jnp.repeat(k, rep, axis=2)
    vr = jnp.repeat(v, rep, axis=2)
    chunk = min(chunk, T)
    pad = -T % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    ks = jnp.arange(S)

    def one(args):
        qc, t0 = args                     # (B,chunk,H,hd), scalar start
        s = jnp.einsum("bthd,bshd->bhts", qc, kr,
                       preferred_element_type=jnp.float32) * hd ** -0.5
        if causal:
            qi = t0 + jnp.arange(chunk)
            s = jnp.where((ks[None, :] <= qi[:, None])[None, None],
                          s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhts,bshd->bthd", p.astype(v.dtype), vr)

    n = (T + pad) // chunk
    qs = q.reshape(B, n, chunk, H, hd).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(one, (qs, jnp.arange(n) * chunk))
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, T + pad, H, hd)
    return out[:, :T] if pad else out
