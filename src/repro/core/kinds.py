"""Per-layer-kind gradient algebra.

Given a layer's captured input ``x_b`` and output cotangent ``δy_b`` (from
:mod:`repro.core.tapper`), each *kind* knows three operations:

  * ``pe_grad``  — materialize per-example gradients (B, *param)  [crb]
  * ``norm_sq``  — per-example squared grad norms (B,) without
                   materialization where structure allows               [ghost]
  * ``contrib``  — weighted sum Σ_b w_b g_b at parameter shape          [bk]

For a dense layer with a sequence axis the ghost norm uses the Gram
identity  ``‖g_b‖² = Σ_{t,t'} (x_t·x_{t'}) (δy_t·δy_{t'})``  which costs
``T²(Din+Dout)`` instead of materializing ``T·Din·Dout`` — the analytic
generalization of the paper's empirical crb-vs-multi crossover.  The
choice between the two is made by :mod:`repro.core.costmodel`.

All reductions accumulate in float32 regardless of capture dtype.

Tensor parallelism (2D data x model meshes) needs **no algebra change**
here: the kinds are written in the global view, and when the engine
partitions a layer's params over the ``model`` axis (out-features for
dense, out-channels for conv, vocab rows for embed), GSPMD shards the
same contractions — each device's Gram/ghost contraction runs over its
local out-feature slice, and because ``‖g_b‖²`` is a sum over
out-features the per-example norms XLA assembles are exactly the psum
of the partial-Gram terms.  ``contrib``'s weighted sums shard the same
way (each shard owns its slice of the clipped sum).  The per-axis
collective cost of those psums is priced by
:mod:`repro.core.costmodel` (``LayerPlan.coll_bytes_by_axis``).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.analysis.markers import tag
from repro.core import costmodel
from repro.core.tapper import (STATS, TAP_KEY, LayerMeta, Tapper,
                               get_subtree, set_subtree)

F32 = jnp.float32


def _realized(n, meta: LayerMeta, method: str):
    """Mark a realized per-example norm so the static verifier can
    cross-check the executed realization against the ExecPlan."""
    return tag(n, kind="realization", layer_kind=meta.kind, method=method,
               path="/".join(str(p) for p in meta.path))


def _fused_marker(n, meta: LayerMeta, method: str):
    return tag(n, kind="fused_impl", method=method,
               path="/".join(str(p) for p in meta.path))


def _ee(*args, **kw):
    """einsum with fp32 accumulation."""
    return jnp.einsum(*args, preferred_element_type=F32, **kw)


def _sumsq(tree):
    """Σ leaf² per example: every leaf has leading B."""
    leaves = jax.tree.leaves(tree)
    tot = 0.0
    for leaf in leaves:
        tot = tot + jnp.sum(
            jnp.square(leaf.astype(F32)),
            axis=tuple(range(1, leaf.ndim)))
    return tot


def _flatten_seq(x):
    """(B, *S, D) -> (B, T, D) with T = prod(S) (possibly 1)."""
    B, D = x.shape[0], x.shape[-1]
    return x.reshape(B, -1, D)


# ---------------------------------------------------------------------------
# Dense (batched)


def dense_pe_grad(meta: LayerMeta, cap, dy):
    x, g = _flatten_seq(cap["x"]), _flatten_seq(dy)
    if meta.w_transposed:
        w_grad = _ee("bto,bti->boi", g, x)
    else:
        w_grad = _ee("bti,bto->bio", x, g)
    out = {meta.param_key: w_grad}
    if meta.bias_key:
        out[meta.bias_key] = _ee("bto->bo", g)
    return out


def dense_norm_sq(meta: LayerMeta, cap, dy, method: str = "auto"):
    x, g = _flatten_seq(cap["x"]), _flatten_seq(dy)
    B, T, Di = x.shape
    Do = g.shape[-1]
    if method == "auto":
        # Only the attention block's inner taps pass "auto" this far; the
        # plan names every other layer's method.
        method = costmodel.dense_norm_method(T, Di, Do, B)
    if method == "rank1" and T != 1:
        method = "gram"
    if method == "pallas":
        # VMEM-tiled Gram kernel (TPU; interpret elsewhere) — the (T,T)
        # tiles never touch HBM.
        from repro.kernels import ops as kops
        return _realized(kops.gram_norm(x, g, has_bias=bool(meta.bias_key)),
                         meta, "pallas")
    if method == "rank1":
        n = _ee("bti,bti->b", x, x) * _ee("bto,bto->b", g, g)
        if meta.bias_key:
            n = n + _ee("bto,bto->b", g, g)
        return _realized(n, meta, "rank1")
    if method == "stream":
        pe = dense_pe_grad(meta, cap, dy)
        return _realized(_sumsq(pe), meta, "stream")
    # gram, chunked over rows to bound the (B, T, T) intermediate
    chunk = costmodel.GRAM_CHUNK
    need_bias = bool(meta.bias_key)

    def chunk_norm(xc, gc):
        sx = _ee("bci,bti->bct", xc, x)
        sy = _ee("bco,bto->bct", gc, g)
        n = _ee("bct,bct->b", sx, sy)
        if need_bias:
            n = n + jnp.sum(sy, axis=(1, 2))
        return n

    if T <= chunk:
        return _realized(chunk_norm(x, g), meta, "gram")
    n_chunks, rem = divmod(T, chunk)
    xs = x[:, : n_chunks * chunk].reshape(B, n_chunks, chunk, Di)
    gs = g[:, : n_chunks * chunk].reshape(B, n_chunks, chunk, Do)

    def body(acc, xg):
        xc, gc = xg
        return acc + chunk_norm(xc, gc), None

    n, _ = jax.lax.scan(body, jnp.zeros((B,), F32),
                        (jnp.moveaxis(xs, 1, 0), jnp.moveaxis(gs, 1, 0)))
    if rem:
        n = n + chunk_norm(x[:, n_chunks * chunk:], g[:, n_chunks * chunk:])
    return _realized(n, meta, "gram")


def dense_norm_and_contrib(meta: LayerMeta, cap, dy, w, *,
                           method: str = "pallas"):
    """Fused phase: per-example squared norms *and* the weighted sum
    Σ_b w_b·g_b in one pass over (x, δy).

    ``method="pallas"`` routes through the fused kernel (each example's
    gradient tile is formed in VMEM and feeds both the norm and the
    contribution; it never reaches HBM).  ``method="stream"`` is the
    materializing realization: per-example grads are formed once and serve
    both reductions — this is what the planner's ``stash`` path exploits.
    Requires the weights to be known entering the pass (bk phase 2,
    stale-coefficient or per-layer-clipped pipelines).
    """
    if method == "pallas":
        from repro.kernels import ops as kops
        STATS.fused += 1
        x, g = _flatten_seq(cap["x"]), _flatten_seq(dy)
        n, cw, cb = kops.gram_norm_fused(x, g, w,
                                         has_bias=bool(meta.bias_key))
        out = {meta.param_key: cw.T if meta.w_transposed else cw}
        if meta.bias_key:
            out[meta.bias_key] = cb
        return _fused_marker(n, meta, "pallas"), out
    pe = dense_pe_grad(meta, cap, dy)
    n = _sumsq(pe)
    contrib = jax.tree.map(
        lambda leaf: _ee("b...,b->...", leaf, w.astype(F32)), pe)
    return _fused_marker(n, meta, "stream"), contrib


def dense_contrib(meta: LayerMeta, cap, dy, w):
    x, g = _flatten_seq(cap["x"]), _flatten_seq(dy)
    if meta.w_transposed:
        w_grad = _ee("b,bto,bti->oi", w, g, x)
    else:
        w_grad = _ee("b,bti,bto->io", w, x, g)
    out = {meta.param_key: w_grad}
    if meta.bias_key:
        out[meta.bias_key] = _ee("b,bto->o", w, g)
    return out


# ---------------------------------------------------------------------------
# Dense (segmented: MoE expert slots with explicit example ids)


def _seg_flatten(meta, cap, dy):
    """Returns x (G,S,Di), g (G,S,Do), seg (G,S), n_examples B."""
    x, g, seg = cap["x"], dy, cap["seg"]
    Di, Do, S = x.shape[-1], g.shape[-1], x.shape[-2]
    x = x.reshape(-1, S, Di)
    g = g.reshape(-1, S, Do)
    seg = seg.reshape(-1, S)
    return x, g, seg, meta.static["n_examples"]


def seg_dense_pe_grad(meta: LayerMeta, cap, dy):
    x, g, seg, B = _seg_flatten(meta, cap, dy)
    oh = jax.nn.one_hot(seg, B, dtype=x.dtype)                 # (G,S,B)
    w_grad = _ee("gsb,gsi,gso->bgio", oh, x, g)
    w_grad = w_grad.reshape((B,) + cap["x"].shape[:-2] + w_grad.shape[-2:])
    out = {meta.param_key: w_grad}
    if meta.bias_key:
        bg = _ee("gsb,gso->bgo", oh, g)
        out[meta.bias_key] = bg.reshape((B,) + cap["x"].shape[:-2] + bg.shape[-1:])
    return out


def seg_dense_norm_sq(meta: LayerMeta, cap, dy, method: str = "gram"):
    x, g, seg, B = _seg_flatten(meta, cap, dy)
    # Both methods scan over the group (expert) axis so peak extra memory
    # is one group's worth: (B,Di,Do) for stream, (S,S) for gram.
    if method == "stream":
        def body(acc, xgs):
            xg, gg, sg = xgs
            oh = jax.nn.one_hot(sg, B, dtype=xg.dtype)          # (S,B)
            pe = _ee("sb,si,so->bio", oh, xg, gg)
            acc = acc + jnp.sum(jnp.square(pe), axis=(1, 2))
            if meta.bias_key:
                peb = _ee("sb,so->bo", oh, gg)
                acc = acc + jnp.sum(jnp.square(peb), axis=1)
            return acc, None
    else:  # gram over slots with same-example masking
        def body(acc, xgs):
            xg, gg, sg = xgs
            p = _ee("si,ti->st", xg, xg) * _ee("so,to->st", gg, gg)
            if meta.bias_key:
                p = p + _ee("so,to->st", gg, gg)
            oh = jax.nn.one_hot(sg, B, dtype=F32)               # (S,B)
            acc = acc + _ee("sb,st,tb->b", oh, p, oh)
            return acc, None

    n, _ = jax.lax.scan(body, jnp.zeros((B,), F32), (x, g, seg))
    return _realized(n, meta, method)


def seg_dense_contrib(meta: LayerMeta, cap, dy, w):
    x, g, seg, B = _seg_flatten(meta, cap, dy)
    ws = w[seg]                                                 # (G,S)
    w_grad = _ee("gs,gsi,gso->gio", ws, x, g)
    w_grad = w_grad.reshape(cap["x"].shape[:-2] + w_grad.shape[-2:])
    out = {meta.param_key: w_grad}
    if meta.bias_key:
        bg = _ee("gs,gso->go", ws, g)
        out[meta.bias_key] = bg.reshape(cap["x"].shape[:-2] + bg.shape[-1:])
    return out


# ---------------------------------------------------------------------------
# Embedding (gather)


def embed_pe_grad(meta: LayerMeta, cap, dy, vocab: int):
    ids, g = cap["ids"], dy
    B = ids.shape[0]
    ids2 = ids.reshape(B, -1)
    g2 = g.reshape(B, ids2.shape[1], -1).astype(F32)
    out = jnp.zeros((B, vocab, g2.shape[-1]), F32)
    bidx = jnp.arange(B)[:, None]
    out = out.at[bidx, ids2].add(g2)
    return {meta.param_key: out}


def embed_norm_sq(meta: LayerMeta, cap, dy, method: str = "segsum",
                  vocab: int | None = None):
    """Embedding-gather ghost norm: ‖g_b‖² = Σ_v ‖Σ_{t: id_t=v} δy_t‖².

    ``segsum`` (default): sort tokens, segment-sum cotangent rows, square —
    O(T·logT + T·D).  ``gram``: same-token-masked T×T Gram — O(T²·D); at
    T=4096 the gram costs ~2.4× the *whole model's* training FLOPs, which
    the dry-run FLOP parser exposed (EXPERIMENTS.md §Perf iteration 1).
    ``pe``: materialize the (B, V, D) per-example grad and reduce — the
    sort-free winner for small tables (see costmodel.embed_norm_method).
    """
    ids, g = cap["ids"], dy
    B = ids.shape[0]
    ids2 = ids.reshape(B, -1)
    T = ids2.shape[1]
    g2 = g.reshape(B, T, -1)
    if method == "pe":
        return _realized(_sumsq(embed_pe_grad(meta, cap, dy, vocab)),
                         meta, "pe")
    if method == "gram":
        sy = _ee("btd,bsd->bts", g2, g2)
        m = (ids2[:, :, None] == ids2[:, None, :]).astype(F32)
        return _realized(_ee("bts,bts->b", m, sy), meta, "gram")
    # segsum
    order = jnp.argsort(ids2, axis=1)
    ids_s = jnp.take_along_axis(ids2, order, axis=1)
    g_s = jnp.take_along_axis(g2, order[..., None], axis=1).astype(F32)
    newseg = jnp.cumsum(
        jnp.concatenate([jnp.zeros((B, 1), jnp.int32),
                         (ids_s[:, 1:] != ids_s[:, :-1]).astype(jnp.int32)],
                        axis=1), axis=1)
    summed = jax.vmap(
        lambda gg, ss: jax.ops.segment_sum(gg, ss, num_segments=T))(
        g_s, newseg)
    return _realized(jnp.sum(jnp.square(summed), axis=(1, 2)),
                     meta, "segsum")


def embed_contrib(meta: LayerMeta, cap, dy, w, vocab: int):
    ids, g = cap["ids"], dy
    B = ids.shape[0]
    ids2 = ids.reshape(B, -1)
    g2 = g.reshape(B, ids2.shape[1], -1).astype(F32)
    g2 = g2 * w[:, None, None]
    out = jnp.zeros((vocab, g2.shape[-1]), F32)
    out = out.at[ids2.reshape(-1)].add(g2.reshape(-1, g2.shape[-1]))
    return {meta.param_key: out}


# ---------------------------------------------------------------------------
# Scale / bias (elementwise affine)


def _scale_reduce_axes(x, gshape):
    """Axes of x (beyond batch) over which the g-broadcast reduces."""
    nd, ng = x.ndim, len(gshape)
    axes = []
    for ax in range(1, nd):
        gax = ax - (nd - ng)
        if gax < 0 or gshape[gax] == 1:
            axes.append(ax)
    return tuple(axes)


def scale_pe_grad(meta: LayerMeta, cap, dy, gshape):
    x, g = cap["x"], dy
    axes = _scale_reduce_axes(x, gshape)
    pg = jnp.sum((x * g).astype(F32), axis=axes)
    out = {meta.param_key: pg.reshape((x.shape[0],) + tuple(gshape))}
    if meta.bias_key:
        pb = jnp.sum(g.astype(F32), axis=axes)
        out[meta.bias_key] = pb.reshape((x.shape[0],) + tuple(gshape))
    return out


def scale_norm_sq(meta: LayerMeta, cap, dy, gshape):
    return _realized(_sumsq(scale_pe_grad(meta, cap, dy, gshape)),
                     meta, "pe")


def scale_contrib(meta: LayerMeta, cap, dy, w, gshape):
    pe = scale_pe_grad(meta, cap, dy, gshape)
    wb = w.reshape((-1,) + (1,) * len(gshape))
    return {k: jnp.sum(v * wb, axis=0) for k, v in pe.items()}


# ---------------------------------------------------------------------------
# Convolution (the paper's contribution — Algorithms 1 & 2)


def conv_pe_grad(meta: LayerMeta, cap, dy, impl: str = "auto"):
    from repro.models import convops
    st = meta.static
    w_grad = convops.pe_conv_grad(
        cap["x"], dy, kernel_spatial=st["kernel_shape"][2:],
        stride=st["stride"], dilation=st["dilation"], padding=st["padding"],
        groups=st["groups"], impl=impl)
    out = {meta.param_key: w_grad}
    if meta.bias_key:
        g = dy
        out[meta.bias_key] = jnp.sum(
            g.astype(F32), axis=tuple(range(2, g.ndim)))
    return out


def conv_norm_sq_ghost(meta: LayerMeta, cap, dy, *, use_pallas: bool = False):
    """Conv ghost norm without materializing per-example weight grads:
    im2col the input to x̃ (B, T, C·K/g per group) and apply the dense Gram
    identity  ‖g_b‖² = Σ_{t,t'} (x̃_t·x̃_{t'}) (δy_t·δy_{t'})  per group —
    the per-layer "ghost clipping" of Bu et al. (2022) generalized to
    stride/dilation/padding/groups.  Cost 2·B·T²·(C·K/g + D/g)·g vs the
    materializing path's 4·B·T·(C·K/g)·(D/g)·g: wins exactly where the
    cost model says (small output spatial T, wide channels)."""
    from repro.models.convops import unfold_patches
    st = meta.static
    x = cap["x"]
    g = max(st.get("groups", 1), 1)
    patches = unfold_patches(x, st["kernel_shape"][2:], stride=st["stride"],
                             dilation=st["dilation"], padding=st["padding"])
    B, CK, T = patches.shape
    D = dy.shape[1]
    gy = dy.reshape(B, D, T)
    method = "pallas" if use_pallas else "gram"
    if g == 1:
        meta_d = LayerMeta("dense", meta.path, bias_key=meta.bias_key)
        return dense_norm_sq(meta_d, {"x": patches.transpose(0, 2, 1)},
                             gy.transpose(0, 2, 1), method=method)
    Fg, Dg = CK // g, D // g
    xt = patches.reshape(B, g, Fg, T).transpose(0, 1, 3, 2) \
        .reshape(B * g, T, Fg)
    gt = gy.reshape(B, g, Dg, T).transpose(0, 1, 3, 2).reshape(B * g, T, Dg)
    meta_d = LayerMeta("dense", meta.path)
    n = dense_norm_sq(meta_d, {"x": xt}, gt, method=method)
    n = jnp.sum(n.reshape(B, g), axis=1)
    if meta.bias_key:
        sb = jnp.sum(gy.astype(F32), axis=2)
        n = n + jnp.sum(jnp.square(sb), axis=1)
    return n


def conv_norm_sq(meta: LayerMeta, cap, dy, impl: str = "auto",
                 method: str = "pe"):
    if method in ("ghost", "pallas"):
        return _realized(conv_norm_sq_ghost(
            meta, cap, dy, use_pallas=(method == "pallas")), meta, method)
    return _realized(_sumsq(conv_pe_grad(meta, cap, dy, impl=impl)),
                     meta, "pe")


def conv_norm_and_contrib(meta: LayerMeta, cap, dy, w, *,
                          use_pallas: bool = True):
    """Fused conv ghost-norm + weighted weight gradient: im2col the input
    and run the dense fused pass per group — the contribution
    Σ_b w_b x̃_bᵀ δy_b *is* the weighted conv weight gradient in patch
    space (channel-major / filter-position-minor, matching the
    (D, C/g, *K) weight layout), so the reshape back is free.  Requires
    the weights to be known entering the pass (stale-coefficient
    pipelines)."""
    from repro.models.convops import unfold_patches
    st = meta.static
    x = cap["x"]
    g = max(st.get("groups", 1), 1)
    kshape = st["kernel_shape"]
    patches = unfold_patches(x, kshape[2:], stride=st["stride"],
                             dilation=st["dilation"], padding=st["padding"])
    B, CK, T = patches.shape
    D = dy.shape[1]
    gy = dy.reshape(B, D, T)
    method = "pallas" if use_pallas else "stream"
    if g == 1:
        meta_d = LayerMeta("dense", meta.path, param_key=meta.param_key,
                           bias_key=meta.bias_key)
        n, out = dense_norm_and_contrib(
            meta_d, {"x": patches.transpose(0, 2, 1)},
            gy.transpose(0, 2, 1), w, method=method)
        out[meta.param_key] = out[meta.param_key].T.reshape(kshape)
        return n, out
    Fg, Dg = CK // g, D // g
    xg = patches.reshape(B, g, Fg, T)
    gg = gy.reshape(B, g, Dg, T)
    meta_d = LayerMeta("dense", meta.path, param_key=meta.param_key)
    n = jnp.zeros((B,), F32)
    w_parts = []
    for gi in range(g):
        n_i, out = dense_norm_and_contrib(
            meta_d, {"x": xg[:, gi].transpose(0, 2, 1)},
            gg[:, gi].transpose(0, 2, 1), w, method=method)
        n = n + n_i
        w_parts.append(out[meta.param_key].T.reshape((Dg,) + tuple(kshape[1:])))
    res = {meta.param_key: jnp.concatenate(w_parts, axis=0)}
    if meta.bias_key:
        sb = jnp.sum(gy.astype(F32), axis=2)                    # (B, D)
        n = n + jnp.sum(jnp.square(sb), axis=1)
        res[meta.bias_key] = _ee("b,bo->o", w.astype(F32), sb)
    return n, res


def conv_contrib(meta: LayerMeta, cap, dy, w):
    from repro.models.convops import conv_forward
    st = meta.static
    x = cap["x"] * w.reshape((-1,) + (1,) * (cap["x"].ndim - 1)).astype(cap["x"].dtype)
    kshape = st["kernel_shape"]

    def f(wk):
        return conv_forward(x, wk, stride=st["stride"], dilation=st["dilation"],
                            padding=st["padding"], groups=st["groups"])

    _, vjp = jax.vjp(f, jnp.zeros(kshape, cap["x"].dtype))
    (w_grad,) = vjp(dy.astype(cap["x"].dtype))
    out = {meta.param_key: w_grad.astype(F32)}
    if meta.bias_key:
        g = dy.astype(F32) * w.reshape((-1,) + (1,) * (dy.ndim - 1))
        out[meta.bias_key] = jnp.sum(g, axis=(0,) + tuple(range(2, g.ndim)))
    return out


# ---------------------------------------------------------------------------
# Attention blocks (GQA / MLA, tapped as one "attn" layer)
#
# The block tap captures only the block *input* x_b and receives the block
# *output* cotangent δy_b from the model backward.  A layer-local recompute
# under an inner Tapper then recovers every projection's (x, δy) pair:
# differentiating  Σ_b ⟨y_b, δy_b⟩  w.r.t. the inner taps yields exactly the
# chain-rule cotangents of the true loss at each projection output (δy is
# constant w.r.t. the taps), after which each projection applies its own
# dense/scale algebra — the ghost norm never materializes per-example
# attention gradients, matching the paper's conv derivation ported to the
# attention contraction.  Like local_vjp this is layer-local recompute, not
# a whole-model pass: no STATS ticks, the census stays 1 fwd + 1 bwd.


def _attn_parts(meta: LayerMeta, cap, dy, params_sub):
    """Recompute the block, returning (inner_metas, caps, dtaps) with each
    inner tap's captures and output cotangents.  Inner tap names are rooted
    at the fixed "blk" prefix (see gqa_apply/mla_apply), so the relative
    param path of an inner layer is ``meta.path[1:]``."""
    x = cap["x"]
    inner_metas: dict[str, LayerMeta] = {}

    def probe_fn(p, xin):
        tp = Tapper(None, "probe", metas=inner_metas)
        y = meta.fn(tp, p, xin)
        return y, tp.captures

    _, cap_sh = jax.eval_shape(probe_fn, params_sub, x)
    taps = {n: jnp.zeros(c[TAP_KEY].shape, c[TAP_KEY].dtype)
            for n, c in cap_sh.items() if TAP_KEY in c}
    dyf = dy.astype(F32)

    def from_taps(t):
        tp = Tapper(t, "capture", metas={})
        y = meta.fn(tp, params_sub, x)
        return jnp.sum(y.astype(F32) * dyf), tp.captures

    (_, caps), dtaps = jax.value_and_grad(from_taps, has_aux=True)(taps)
    return inner_metas, caps, dtaps


def _attn_each(meta: LayerMeta, params_sub, inner_metas):
    """Yield (name, flat inner meta re-rooted under meta.path, rel path,
    param subtree) per inner tap, in deterministic order."""
    for iname in sorted(inner_metas):
        im = inner_metas[iname]
        rel = im.path[1:]
        imf = dataclasses.replace(im, path=meta.path + rel, scanned=0,
                                  shared=False)
        yield iname, imf, rel, get_subtree(params_sub, rel)


def attn_pe_grad(meta: LayerMeta, cap, dy, params_sub):
    inner_metas, caps, dtaps = _attn_parts(meta, cap, dy, params_sub)
    out: dict = {}
    for iname, imf, rel, psub_i in _attn_each(meta, params_sub, inner_metas):
        part = _apply_flat("pe_grad", imf, caps[iname], dtaps[iname],
                           params_sub=psub_i, weights=None,
                           norm_method="auto", conv_impl="fgc")
        for k2, v2 in part.items():
            out = set_subtree(out, rel + (k2,), v2)
    return out


def attn_norm_sq(meta: LayerMeta, cap, dy, params_sub,
                 method: str = "ghost"):
    if method == "pe":
        return _realized(_sumsq(attn_pe_grad(meta, cap, dy, params_sub)),
                         meta, "pe")
    inner_metas, caps, dtaps = _attn_parts(meta, cap, dy, params_sub)
    n = jnp.zeros((cap["x"].shape[0],), F32)
    for iname, imf, rel, psub_i in _attn_each(meta, params_sub, inner_metas):
        n = n + _apply_flat("norm_sq", imf, caps[iname], dtaps[iname],
                            params_sub=psub_i, weights=None,
                            norm_method="auto", conv_impl="fgc")
    return _realized(n, meta, "ghost")


def attn_contrib(meta: LayerMeta, cap, dy, w, params_sub):
    inner_metas, caps, dtaps = _attn_parts(meta, cap, dy, params_sub)
    out: dict = {}
    for iname, imf, rel, psub_i in _attn_each(meta, params_sub, inner_metas):
        part = _apply_flat("contrib", imf, caps[iname], dtaps[iname],
                           params_sub=psub_i, weights=w,
                           norm_method="auto", conv_impl="fgc")
        for k2, v2 in part.items():
            out = set_subtree(out, rel + (k2,), v2)
    return out


# ---------------------------------------------------------------------------
# Generic local-VJP kind (SSM scans, routers, anything else)


def _local_vjp_pe(meta: LayerMeta, cap, dy, params_sub):
    def one(inputs_b, dy_b):
        def f(p):
            return meta.fn(p, *jax.tree.map(lambda a: a[None], inputs_b))
        y, vjp = jax.vjp(f, params_sub)
        (g,) = vjp(dy_b[None].astype(y.dtype))
        return g
    return jax.vmap(one)(cap["inputs"], dy)


def local_vjp_pe_grad(meta: LayerMeta, cap, dy, params_sub):
    return _local_vjp_pe(meta, cap, dy, params_sub)


def local_vjp_norm_sq(meta: LayerMeta, cap, dy, params_sub):
    return _realized(_sumsq(_local_vjp_pe(meta, cap, dy, params_sub)),
                     meta, "vjp")


def local_vjp_contrib(meta: LayerMeta, cap, dy, w, params_sub):
    pe = _local_vjp_pe(meta, cap, dy, params_sub)
    return jax.tree.map(
        lambda leaf: jnp.einsum(
            "b...,b->...", leaf.astype(F32), w.astype(F32)), pe)


# ---------------------------------------------------------------------------
# Stacked-layer handling: fold meta.scanned leading axes


def _split_stack(meta: LayerMeta, cap, dy):
    """Flatten the stacked-layer axes into one leading G axis."""
    k = meta.scanned

    def flat(a):
        return a.reshape((-1,) + a.shape[k:])

    stack_shape = dy.shape[:k]
    return jax.tree.map(flat, cap), flat(dy), stack_shape


def _fold_into_seq(meta: LayerMeta, cap, dy):
    """For shared params: fold stacked axes into the sequence axis so the
    per-example gradient is summed over applications *before* norms."""
    k = meta.scanned
    if k == 0:
        return cap, dy

    def fold(a):
        # (S1..Sk, B, *rest, D) -> (B, S*prod(rest_mid), D) handled by
        # downstream _flatten_seq; here just move stack axes after batch.
        a = jnp.moveaxis(a.reshape((-1,) + a.shape[k:]), 0, 1)
        return a
    return jax.tree.map(fold, cap), jax.tree.map(fold, dy)


def apply_kind(op: str, meta: LayerMeta, cap, dy, *, params_sub=None,
               weights=None, norm_method: str = "auto",
               conv_impl: str = "auto", embed_method: str = "segsum",
               conv_norm: str = "pe", attn_norm: str = "ghost"):
    """Dispatch `op` in {"pe_grad","norm_sq","contrib"} over any kind,
    handling stacked (scanned) axes and shared parameters."""
    kind = meta.kind

    if meta.shared and meta.scanned and kind in ("dense", "scale") \
            and not meta.segmented:
        # Fold applications into the sequence axis: the per-example gradient
        # of a shared parameter is the sum over applications, and the fold
        # makes every op (incl. the Gram norm with its cross terms) exact.
        cap, dy = _fold_into_seq(meta, cap, dy)
        return _apply_flat(op, _unscanned(meta), cap, dy,
                           params_sub=params_sub, weights=weights,
                           norm_method=norm_method, conv_impl=conv_impl,
                           embed_method=embed_method, conv_norm=conv_norm,
                           attn_norm=attn_norm)

    if meta.shared and meta.scanned and op == "norm_sq":
        # Generic shared fallback: materialize the summed per-example grad
        # (exact cross terms), then take norms.
        pe = apply_kind("pe_grad", meta, cap, dy, params_sub=params_sub,
                        conv_impl=conv_impl)
        return _realized(_sumsq(pe), meta, "pe")

    if meta.scanned and meta.segmented:
        # Segmented (MoE) kinds natively reduce over their leading group
        # axis with a memory-bounded internal scan — just flatten stacks.
        cap_f, dy_f, stack_shape = _split_stack(meta, cap, dy)
        res = _apply_flat(op, _unscanned(meta), cap_f, dy_f,
                          params_sub=params_sub, weights=weights,
                          norm_method=norm_method, conv_impl=conv_impl,
                          embed_method=embed_method, conv_norm=conv_norm,
                          attn_norm=attn_norm)
        if op == "norm_sq":
            return res
        if op == "contrib":
            return jax.tree.map(
                lambda a: a.reshape(stack_shape + a.shape[1:]), res)
        return jax.tree.map(  # pe_grad: (B, G, ...) -> (B, *stack, ...)
            lambda a: a.reshape((a.shape[0],) + stack_shape + a.shape[2:]),
            res)

    if meta.scanned:
        cap_f, dy_f, stack_shape = _split_stack(meta, cap, dy)
        meta_f = _unscanned(meta)
        psub = params_sub
        shared_p = psub if (psub is not None and meta.shared) else None
        if psub is not None and not meta.shared:
            psub = jax.tree.map(
                lambda a: a.reshape((-1,) + a.shape[meta.scanned:]), psub)
        else:
            psub = None

        def one(xs):
            c, d, p = xs
            return _apply_flat(op, meta_f, c, d,
                               params_sub=shared_p if shared_p is not None
                               else p,
                               weights=weights, norm_method=norm_method,
                               conv_impl=conv_impl,
                               embed_method=embed_method,
                               conv_norm=conv_norm, attn_norm=attn_norm)

        # Sequential over the stacked axis: bounds peak memory to one
        # layer's worth (vmap would batch every layer's intermediates).
        res = jax.lax.map(one, (cap_f, dy_f, psub))

        if op == "norm_sq":
            return jnp.sum(res, axis=0)
        if op == "contrib":
            if meta.shared:
                return jax.tree.map(lambda a: jnp.sum(a, axis=0), res)
            return jax.tree.map(
                lambda a: a.reshape(stack_shape + a.shape[1:]), res)
        # pe_grad: (G, B, *p) -> (B, *stack, *p)
        if meta.shared:
            return jax.tree.map(lambda a: jnp.sum(a, axis=0), res)
        return jax.tree.map(
            lambda a: jnp.moveaxis(
                a.reshape(stack_shape + a.shape[1:]), len(stack_shape), 0),
            res)

    return _apply_flat(op, meta, cap, dy, params_sub=params_sub,
                       weights=weights, norm_method=norm_method,
                       conv_impl=conv_impl, embed_method=embed_method,
                       conv_norm=conv_norm, attn_norm=attn_norm)


def apply_norm_contrib(meta: LayerMeta, cap, dy, *, weights,
                       params_sub=None, fused: bool = True,
                       conv_impl: str = "auto", norm_method: str = "auto",
                       embed_method: str = "segsum",
                       conv_norm: str = "pe", attn_norm: str = "ghost"):
    """Per-example squared norms *and* the weighted sum Σ_b w_b·g_b from
    one pass over the captures.  Valid whenever the weights are known
    entering the pass (stale-coefficient clipping).

    Dense (non-segmented) and conv layers route to the fused
    ``gram_norm_fused`` realizations when ``fused``; every other kind —
    and the non-fused request — falls back to its norm_sq + contrib pair
    (still a single capture pass of the model: no extra forward or
    backward, just two reductions over the same tensors)."""
    if fused and meta.kind == "dense" and not meta.segmented:
        if meta.shared and meta.scanned:
            cap2, dy2 = _fold_into_seq(meta, cap, dy)
            return dense_norm_and_contrib(_unscanned(meta), cap2, dy2,
                                          weights, method="pallas")
        if not meta.scanned:
            return dense_norm_and_contrib(meta, cap, dy, weights,
                                          method="pallas")
        cap_f, dy_f, stack_shape = _split_stack(meta, cap, dy)
        meta_f = _unscanned(meta)

        def one(xs):
            c, d = xs
            return dense_norm_and_contrib(meta_f, c, d, weights,
                                          method="pallas")

        n, contrib = jax.lax.map(one, (cap_f, dy_f))
        n = jnp.sum(n, axis=0)
        contrib = jax.tree.map(
            lambda a: a.reshape(stack_shape + a.shape[1:]), contrib)
        return n, contrib
    if fused and meta.kind == "conv" and not meta.scanned:
        return conv_norm_and_contrib(meta, cap, dy, weights, use_pallas=True)
    n = apply_kind("norm_sq", meta, cap, dy, params_sub=params_sub,
                   norm_method=norm_method, conv_impl=conv_impl,
                   embed_method=embed_method, conv_norm=conv_norm,
                   attn_norm=attn_norm)
    c = apply_kind("contrib", meta, cap, dy, params_sub=params_sub,
                   weights=weights, conv_impl=conv_impl)
    return n, c


def _unscanned(meta: LayerMeta) -> LayerMeta:
    import dataclasses as dc
    return dc.replace(meta, scanned=0, shared=False)


def _apply_flat(op, meta, cap, dy, *, params_sub, weights, norm_method,
                conv_impl, embed_method="segsum", conv_norm="pe",
                attn_norm="ghost"):
    kind = meta.kind
    if kind == "dense" and not meta.segmented:
        if op == "pe_grad":
            return dense_pe_grad(meta, cap, dy)
        if op == "norm_sq":
            return dense_norm_sq(meta, cap, dy, method=norm_method)
        return dense_contrib(meta, cap, dy, weights)
    if kind == "dense" and meta.segmented:
        if op == "pe_grad":
            return seg_dense_pe_grad(meta, cap, dy)
        if op == "norm_sq":
            return seg_dense_norm_sq(meta, cap, dy, method=norm_method)
        return seg_dense_contrib(meta, cap, dy, weights)
    if kind == "embed":
        vocab = (params_sub[meta.param_key].shape[-2]
                 if params_sub is not None else meta.static.get("vocab"))
        if op == "pe_grad":
            return embed_pe_grad(meta, cap, dy, vocab)
        if op == "norm_sq":
            return embed_norm_sq(meta, cap, dy, method=embed_method,
                                 vocab=vocab)
        return embed_contrib(meta, cap, dy, weights, vocab)
    if kind == "scale":
        gshape = tuple(params_sub[meta.param_key].shape)
        if op == "pe_grad":
            return scale_pe_grad(meta, cap, dy, gshape)
        if op == "norm_sq":
            return scale_norm_sq(meta, cap, dy, gshape)
        return scale_contrib(meta, cap, dy, weights, gshape)
    if kind == "conv":
        if op == "pe_grad":
            return conv_pe_grad(meta, cap, dy, impl=conv_impl)
        if op == "norm_sq":
            return conv_norm_sq(meta, cap, dy, impl=conv_impl,
                                method=conv_norm)
        return conv_contrib(meta, cap, dy, weights)
    if kind == "local_vjp":
        if op == "pe_grad":
            return local_vjp_pe_grad(meta, cap, dy, params_sub)
        if op == "norm_sq":
            return local_vjp_norm_sq(meta, cap, dy, params_sub)
        return local_vjp_contrib(meta, cap, dy, weights, params_sub)
    if kind == "attn":
        if op == "pe_grad":
            return attn_pe_grad(meta, cap, dy, params_sub)
        if op == "norm_sq":
            return attn_norm_sq(meta, cap, dy, params_sub, method=attn_norm)
        return attn_contrib(meta, cap, dy, weights, params_sub)
    raise ValueError(f"unknown kind {kind}")


# ---------------------------------------------------------------------------
# Tied-parameter cross term: <g_embed_b, g_head_b> for weight-tied LM heads


def tied_embed_head_cross(cap_e, dy_e, cap_d, dy_d):
    """2·⟨g_in, g_out⟩ per example for a parameter used both as an embedding
    table (gather) and, transposed, as the LM head (dense w_transposed).

      g_in[v,d]  = Σ_t 1[id_t=v] δe[t,d]
      g_out[v,d] = Σ_s δl[s,v] h[s,d]
      ⟨g_in,g_out⟩ = Σ_{t,s} δl[s, id_t] · (δe[t]·h[s])
    """
    ids = cap_e["ids"]
    B = ids.shape[0]
    ids2 = ids.reshape(B, -1)                      # (B, T)
    de = dy_e.reshape(B, ids2.shape[1], -1)        # (B, T, D)
    h = _flatten_seq(cap_d["x"])                   # (B, S, D)
    dl = dy_d.reshape(B, h.shape[1], -1)           # (B, S, V)
    a = _ee("btd,bsd->bts", de, h)                 # (B, T, S)
    idx = jnp.broadcast_to(ids2[:, None, :], (B, h.shape[1], ids2.shape[1]))
    dl_at = jnp.take_along_axis(dl, idx, axis=2)   # (B, S, T)
    inner = _ee("bts,bst->b", a, dl_at)
    return 2.0 * inner
