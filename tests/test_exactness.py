"""Differential-testing oracle suite.

Every norm / contrib realization in :mod:`repro.core.kinds` — dense
gram/stream/rank1, segmented dense (MoE slots), embed segsum/gram/pe,
conv ghost/materialize (incl. stride + dilation + groups, fgc and bgc
impls), scale — is checked against a naive autodiff oracle: the jacobian
of the per-example loss vector (vmap-of-vjp semantics, valid even for
segmented layers where examples do not own contiguous batch rows).  Runs
across float32 and bfloat16.

The deterministic geometry grid always runs; when ``hypothesis`` is
available (CI installs requirements-dev.txt) randomized property tests
widen the geometry coverage.  The sharded pipeline must pass the same
oracle — see the ``multidevice``-marked test at the bottom, which the
multi-device CI lane runs on a forced 8-device host.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import true_norms_sq
from repro.core import (ClipPolicy, clipped_grad_sum,
                        clipped_grad_sum_detailed, ghost_norms,
                        resolve_budgets)
from repro.core.strategies import clip_coefficients
from repro.core.tapper import Tapper
from repro.launch.mesh import make_auto_mesh

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
    settings.register_profile("exactness", max_examples=15, deadline=None)
    settings.load_profile("exactness")
except ImportError:                       # container without dev extras:
    HAVE_HYPOTHESIS = False               # the deterministic grid still runs

DTYPES = (jnp.float32, jnp.bfloat16)


def _tol(dtype):
    """Comparison tolerance per capture dtype.  bf16 has ~8 mantissa bits:
    inputs/cotangents are quantized before the f32-accumulated reductions,
    so realizations legitimately differ at the ~1e-2 relative level."""
    return (dict(rtol=3e-4, atol=1e-6) if dtype == jnp.float32
            else dict(rtol=6e-2, atol=2e-3))


def oracle_pe_grads(apply_fn, params, batch):
    """Naive per-example gradients: rows of the Jacobian of the (B,)
    per-example loss vector — one VJP per example, no layer algebra."""
    return jax.jacrev(lambda p: apply_fn(p, batch, Tapper()))(params)


def _assert_norms_match(apply_fn, params, batch, dtype, **norm_kw):
    want = np.asarray(true_norms_sq(oracle_pe_grads(apply_fn, params, batch)))
    _, got, _ = ghost_norms(apply_fn, params, batch, **norm_kw)
    np.testing.assert_allclose(np.asarray(got), want, **_tol(dtype))


def _sum_tol(dtype, scale):
    """Clipped-sum tolerance: the norm error propagates into the clip
    coefficients, so sums are a notch looser than the norms themselves."""
    if dtype == jnp.float32:
        return dict(rtol=3e-3, atol=3e-4 * scale)
    return dict(rtol=1.2e-1, atol=2e-2 * scale)


def _oracle_clipped_sum(apply_fn, params, batch, C):
    pe = oracle_pe_grads(apply_fn, params, batch)
    coef = clip_coefficients(true_norms_sq(pe), C)
    return jax.tree.map(
        lambda g: jnp.einsum("b...,b->...", g.astype(jnp.float32), coef), pe)


def _assert_clipped_sum_matches(apply_fn, params, batch, dtype, C=0.1,
                                **kw):
    want = _oracle_clipped_sum(apply_fn, params, batch, C)
    _, got, _ = clipped_grad_sum(apply_fn, params, batch, l2_clip=C,
                                 check=True, **kw)
    scale = max(max(float(jnp.abs(w).max())
                    for w in jax.tree.leaves(want)), 1.0)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            **_sum_tol(dtype, scale))


def _group_pe(pe_grads):
    """Split oracle per-example grads by top-level parameter group, in
    sorted-key order — the same deterministic group order the pipeline's
    budgets and per-layer norms use."""
    return [(k, pe_grads[k]) for k in sorted(pe_grads)]


def _oracle_per_layer_clipped_sum(apply_fn, params, batch, C,
                                  budgets=None):
    """Per-layer Jacobian-clip oracle: each parameter group clipped
    against its own budget and its own (naive-Jacobian) norm."""
    pe = oracle_pe_grads(apply_fn, params, batch)
    groups = _group_pe(pe)
    if budgets is None:
        budgets = np.full(len(groups), C / np.sqrt(len(groups)))
    out = {}
    for (key, sub), b in zip(groups, np.asarray(budgets)):
        coef = clip_coefficients(true_norms_sq(sub), b)
        out[key] = jax.tree.map(
            lambda g: jnp.einsum("b...,b->...", g.astype(jnp.float32), coef),
            sub)
    return out


def _assert_per_layer_matches(apply_fn, params, batch, dtype, C=0.1,
                              strategy="bk", **kw):
    want = _oracle_per_layer_clipped_sum(apply_fn, params, batch, C)
    _, got, _, detail = clipped_grad_sum_detailed(
        apply_fn, params, batch, l2_clip=C,
        strategy=strategy, clip_policy=ClipPolicy(mode="per_layer"),
        check=(strategy == "auto"), **kw)
    scale = max(max(float(jnp.abs(w).max())
                    for w in jax.tree.leaves(want)), 1.0)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            **_sum_tol(dtype, scale))
    # The budgets the pipeline resolved must satisfy the sensitivity
    # invariant the oracle assumed.
    np.testing.assert_allclose(
        float(jnp.sum(jnp.square(detail["budgets"]))), C * C, rtol=1e-5)


# ---------------------------------------------------------------------------
# Single-kind model builders


def dense_seq_model(dtype, B=3, T=6, Di=5, Do=4, seed=0):
    rng = np.random.RandomState(seed)
    params = {"fc": {"w": jnp.asarray(rng.randn(Di, Do), dtype) * 0.5,
                     "b": jnp.asarray(rng.randn(Do), dtype) * 0.1}}

    def apply_fn(p, batch, tp):
        y = tp.dense("fc", batch["x"], p["fc"]["w"], p["fc"]["b"])
        return jnp.sum(jnp.tanh(y.astype(jnp.float32)) ** 2, axis=(1, 2))

    batch = {"x": jnp.asarray(rng.randn(B, T, Di), dtype)}
    return apply_fn, params, batch


def dense_novec_model(dtype, B=4, Di=6, Do=5, seed=1):
    rng = np.random.RandomState(seed)
    params = {"fc": {"w": jnp.asarray(rng.randn(Di, Do), dtype) * 0.5}}

    def apply_fn(p, batch, tp):
        y = tp.dense("fc", batch["x"], p["fc"]["w"])
        return jnp.sum(y.astype(jnp.float32) ** 2, axis=1)

    batch = {"x": jnp.asarray(rng.randn(B, Di), dtype)}
    return apply_fn, params, batch


def seg_dense_model(dtype, B=4, E=3, S=5, Di=4, Do=3, seed=2):
    """MoE-style dispatched slots: (E, S) slots with explicit example ids;
    an example's loss is the sum over its slots across all experts."""
    rng = np.random.RandomState(seed)
    params = {"ex": {"w": jnp.asarray(rng.randn(E, Di, Do), dtype) * 0.5}}
    seg = jnp.asarray(rng.randint(0, B, (E, S)))

    def apply_fn(p, batch, tp):
        y = tp.dense_segmented("ex", batch["x"], p["ex"]["w"], batch["seg"],
                               n_examples=B)
        v = jnp.sum(jnp.tanh(y.astype(jnp.float32)) ** 2, axis=-1)  # (E, S)
        return jnp.zeros((B,), jnp.float32).at[
            batch["seg"].reshape(-1)].add(v.reshape(-1))

    batch = {"x": jnp.asarray(rng.randn(E, S, Di), dtype), "seg": seg}
    return apply_fn, params, batch


def embed_model(dtype, B=3, T=7, V=13, D=4, seed=3):
    rng = np.random.RandomState(seed)
    params = {"emb": {"emb": jnp.asarray(rng.randn(V, D), dtype) * 0.5}}

    def apply_fn(p, batch, tp):
        e = tp.embed("emb", p["emb"]["emb"], batch["ids"])
        return jnp.sum(jnp.tanh(e.astype(jnp.float32)) ** 2, axis=(1, 2))

    # repeated ids per example exercise the same-token cross terms
    batch = {"ids": jnp.asarray(rng.randint(0, V, (B, T)))}
    return apply_fn, params, batch


CONV_GEOMS = [
    # (C, D, HW, K, stride, padding, dilation, groups)
    (3, 4, 8, 3, 1, 1, 1, 1),     # vanilla
    (4, 6, 9, 3, 2, 1, 1, 1),     # strided
    (4, 6, 9, 3, 1, 2, 2, 1),     # dilated
    (4, 8, 8, 3, 1, 1, 1, 4),     # grouped
    (6, 6, 9, 3, 2, 2, 2, 2),     # strided + dilated + grouped
]


def conv_model(dtype, geom, B=3, seed=4):
    C, D, HW, K, s, p_, dil, g = geom
    rng = np.random.RandomState(seed)
    params = {"c": {"w": jnp.asarray(rng.randn(D, C // g, K, K), dtype) * 0.3,
                    "b": jnp.asarray(rng.randn(D), dtype) * 0.1}}

    def apply_fn(p, batch, tp):
        y = tp.conv("c", batch["x"], p["c"]["w"], p["c"]["b"], stride=s,
                    padding=p_, dilation=dil, groups=g)
        return jnp.sum(jnp.tanh(y.astype(jnp.float32)) ** 2,
                       axis=tuple(range(1, y.ndim)))

    batch = {"x": jnp.asarray(rng.randn(B, C, HW, HW), dtype)}
    return apply_fn, params, batch


def scale_model(dtype, B=4, T=5, D=6, seed=5):
    rng = np.random.RandomState(seed)
    params = {"s": {"g": jnp.asarray(1 + 0.3 * rng.randn(D), dtype),
                    "b": jnp.asarray(rng.randn(D), dtype) * 0.1}}

    def apply_fn(p, batch, tp):
        y = tp.scale("s", batch["x"], p["s"]["g"], p["s"]["b"])
        return jnp.sum(jnp.tanh(y.astype(jnp.float32)) ** 2, axis=(1, 2))

    batch = {"x": jnp.asarray(rng.randn(B, T, D), dtype)}
    return apply_fn, params, batch


# ---------------------------------------------------------------------------
# Dense: gram / stream / rank1


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
@pytest.mark.parametrize("method", ("gram", "stream", "auto"))
def test_dense_norms_match_oracle(method, dtype):
    apply_fn, params, batch = dense_seq_model(dtype)
    _assert_norms_match(apply_fn, params, batch, dtype, norm_method=method)


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
def test_dense_rank1_norms_match_oracle(dtype):
    apply_fn, params, batch = dense_novec_model(dtype)
    _assert_norms_match(apply_fn, params, batch, dtype, norm_method="rank1")


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
@pytest.mark.parametrize("strategy", ("bk", "auto"))
def test_dense_clipped_sum_matches_oracle(strategy, dtype):
    apply_fn, params, batch = dense_seq_model(dtype)
    _assert_clipped_sum_matches(apply_fn, params, batch, dtype,
                                strategy=strategy)


# ---------------------------------------------------------------------------
# Segmented dense (MoE expert slots)


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
@pytest.mark.parametrize("method", ("gram", "stream"))
def test_seg_dense_norms_match_oracle(method, dtype):
    apply_fn, params, batch = seg_dense_model(dtype)
    _assert_norms_match(apply_fn, params, batch, dtype, norm_method=method)


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
def test_seg_dense_clipped_sum_matches_oracle(dtype):
    apply_fn, params, batch = seg_dense_model(dtype)
    _assert_clipped_sum_matches(apply_fn, params, batch, dtype,
                                strategy="bk")


# ---------------------------------------------------------------------------
# Embedding: segsum / gram / pe


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
@pytest.mark.parametrize("method", ("segsum", "gram", "pe"))
def test_embed_norms_match_oracle(method, dtype):
    apply_fn, params, batch = embed_model(dtype)
    _assert_norms_match(apply_fn, params, batch, dtype, embed_method=method)


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
def test_embed_clipped_sum_matches_oracle(dtype):
    apply_fn, params, batch = embed_model(dtype)
    _assert_clipped_sum_matches(apply_fn, params, batch, dtype,
                                strategy="bk")


# ---------------------------------------------------------------------------
# Conv: ghost (im2col Gram) vs materialize, across geometry and impls


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
@pytest.mark.parametrize("method", ("ghost", "pe"))
@pytest.mark.parametrize("geom", CONV_GEOMS,
                         ids=[f"C{c}D{d}s{s}d{dl}g{g}"
                              for c, d, _, _, s, _, dl, g in CONV_GEOMS])
def test_conv_norms_match_oracle(geom, method, dtype):
    apply_fn, params, batch = conv_model(dtype, geom)
    _assert_norms_match(apply_fn, params, batch, dtype, conv_norm=method)


@pytest.mark.parametrize("impl", ("fgc", "bgc"))
@pytest.mark.parametrize("geom", (CONV_GEOMS[1], CONV_GEOMS[4]),
                         ids=("strided", "mixed"))
def test_conv_pe_grad_impls_match_oracle(geom, impl):
    apply_fn, params, batch = conv_model(jnp.float32, geom)
    want = oracle_pe_grads(apply_fn, params, batch)
    from repro.core.strategies import crb_per_example_grads
    _, got = crb_per_example_grads(apply_fn, params, batch, conv_impl=impl)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=3e-4, atol=3e-5)


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
def test_conv_clipped_sum_matches_oracle(dtype):
    apply_fn, params, batch = conv_model(dtype, CONV_GEOMS[4])
    _assert_clipped_sum_matches(apply_fn, params, batch, dtype,
                                strategy="auto")


# ---------------------------------------------------------------------------
# Scale (elementwise affine)


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
def test_scale_norms_match_oracle(dtype):
    apply_fn, params, batch = scale_model(dtype)
    _assert_norms_match(apply_fn, params, batch, dtype)


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
def test_scale_clipped_sum_matches_oracle(dtype):
    apply_fn, params, batch = scale_model(dtype)
    _assert_clipped_sum_matches(apply_fn, params, batch, dtype,
                                strategy="bk")


# ---------------------------------------------------------------------------
# Clipping modes: per-layer Jacobian-clip oracle and exactly-as-specified
# stale semantics, for every norm realization.  Per-layer clipping on a
# one-group model degenerates to flat (C_1 = C), so each kind-under-test
# is paired with a dense head — two parameter groups, two budgets.


def _head_loss(tp, p, feat):
    o = tp.dense("head", feat, p["head"]["w"])
    return jnp.sum(jnp.tanh(o.astype(jnp.float32)) ** 2, axis=1)


def _head_params(rng, Din, dtype, Do=3):
    return {"w": jnp.asarray(rng.randn(Din, Do), dtype) * 0.4}


def dense_plus_head_model(dtype, B=3, T=6, Di=5, Do=4, seed=10):
    rng = np.random.RandomState(seed)
    params = {"fc": {"w": jnp.asarray(rng.randn(Di, Do), dtype) * 0.5,
                     "b": jnp.asarray(rng.randn(Do), dtype) * 0.1},
              "head": _head_params(rng, Do, dtype)}

    def apply_fn(p, batch, tp):
        y = tp.dense("fc", batch["x"], p["fc"]["w"], p["fc"]["b"])
        return _head_loss(tp, p, jnp.tanh(y.astype(jnp.float32)).mean(1))

    return apply_fn, params, {"x": jnp.asarray(rng.randn(B, T, Di), dtype)}


def seg_dense_plus_head_model(dtype, B=4, E=3, S=5, Di=4, Do=3, seed=11):
    rng = np.random.RandomState(seed)
    params = {"ex": {"w": jnp.asarray(rng.randn(E, Di, Do), dtype) * 0.5},
              "head": _head_params(rng, Di, dtype)}
    seg = jnp.asarray(rng.randint(0, B, (E, S)))

    def apply_fn(p, batch, tp):
        y = tp.dense_segmented("ex", batch["x"], p["ex"]["w"], batch["seg"],
                               n_examples=B)
        v = jnp.sum(jnp.tanh(y.astype(jnp.float32)) ** 2, axis=-1)
        seg_loss = jnp.zeros((B,), jnp.float32).at[
            batch["seg"].reshape(-1)].add(v.reshape(-1))
        return seg_loss + _head_loss(tp, p, batch["h"])

    batch = {"x": jnp.asarray(rng.randn(E, S, Di), dtype), "seg": seg,
             "h": jnp.asarray(rng.randn(B, Di), dtype)}
    return apply_fn, params, batch


def embed_plus_head_model(dtype, B=3, T=7, V=13, D=4, seed=12):
    rng = np.random.RandomState(seed)
    params = {"emb": {"emb": jnp.asarray(rng.randn(V, D), dtype) * 0.5},
              "head": _head_params(rng, D, dtype)}

    def apply_fn(p, batch, tp):
        e = tp.embed("emb", p["emb"]["emb"], batch["ids"])
        return _head_loss(tp, p, jnp.tanh(e.astype(jnp.float32)).mean(1))

    return apply_fn, params, {"ids": jnp.asarray(rng.randint(0, V, (B, T)))}


def conv_plus_head_model(dtype, geom, B=3, seed=13):
    C, D, HW, K, s, p_, dil, g = geom
    rng = np.random.RandomState(seed)
    params = {"c": {"w": jnp.asarray(rng.randn(D, C // g, K, K), dtype) * 0.3,
                    "b": jnp.asarray(rng.randn(D), dtype) * 0.1},
              "head": _head_params(rng, D, dtype)}

    def apply_fn(p, batch, tp):
        y = tp.conv("c", batch["x"], p["c"]["w"], p["c"]["b"], stride=s,
                    padding=p_, dilation=dil, groups=g)
        return _head_loss(
            tp, p, jnp.tanh(y.astype(jnp.float32)).mean(axis=(2, 3)))

    return apply_fn, params, {"x": jnp.asarray(rng.randn(B, C, HW, HW),
                                               dtype)}


def scale_plus_head_model(dtype, B=4, T=5, D=6, seed=14):
    rng = np.random.RandomState(seed)
    params = {"s": {"g": jnp.asarray(1 + 0.3 * rng.randn(D), dtype),
                    "b": jnp.asarray(rng.randn(D), dtype) * 0.1},
              "head": _head_params(rng, D, dtype)}

    def apply_fn(p, batch, tp):
        y = tp.scale("s", batch["x"], p["s"]["g"], p["s"]["b"])
        return _head_loss(tp, p, jnp.tanh(y.astype(jnp.float32)).mean(1))

    return apply_fn, params, {"x": jnp.asarray(rng.randn(B, T, D), dtype)}


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
@pytest.mark.parametrize("method", ("gram", "stream", "rank1"))
def test_per_layer_dense_matches_oracle(method, dtype):
    # rank1 needs no sequence axis: mean-pool the input first.
    T = 1 if method == "rank1" else 6
    apply_fn, params, batch = dense_plus_head_model(dtype, T=T)
    _assert_per_layer_matches(apply_fn, params, batch, dtype,
                              norm_method=method)


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
@pytest.mark.parametrize("method", ("gram", "stream"))
def test_per_layer_seg_dense_matches_oracle(method, dtype):
    apply_fn, params, batch = seg_dense_plus_head_model(dtype)
    _assert_per_layer_matches(apply_fn, params, batch, dtype,
                              norm_method=method)


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
@pytest.mark.parametrize("method", ("segsum", "gram", "pe"))
def test_per_layer_embed_matches_oracle(method, dtype):
    apply_fn, params, batch = embed_plus_head_model(dtype)
    _assert_per_layer_matches(apply_fn, params, batch, dtype,
                              embed_method=method)


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
@pytest.mark.parametrize("method", ("ghost", "pe"))
@pytest.mark.parametrize("geom", (CONV_GEOMS[0], CONV_GEOMS[4]),
                         ids=("vanilla", "mixed"))
def test_per_layer_conv_matches_oracle(geom, method, dtype):
    apply_fn, params, batch = conv_plus_head_model(dtype, geom)
    _assert_per_layer_matches(apply_fn, params, batch, dtype,
                              conv_norm=method)


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
def test_per_layer_scale_matches_oracle(dtype):
    apply_fn, params, batch = scale_plus_head_model(dtype)
    _assert_per_layer_matches(apply_fn, params, batch, dtype)


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
@pytest.mark.parametrize("build", (dense_plus_head_model,
                                   embed_plus_head_model,
                                   scale_plus_head_model),
                         ids=("dense", "embed", "scale"))
def test_per_layer_planned_matches_oracle(build, dtype):
    """The planned (auto) pipeline under per-layer clipping, with the
    planner choosing realizations."""
    apply_fn, params, batch = build(dtype)
    _assert_per_layer_matches(apply_fn, params, batch, dtype,
                              strategy="auto")


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
def test_per_layer_weighted_budgets_match_oracle(dtype):
    """A non-uniform {glob: weight} split: the oracle clips with the same
    resolved budgets the pipeline uses."""
    apply_fn, params, batch = conv_plus_head_model(dtype, CONV_GEOMS[0])
    C = 0.1
    policy = ClipPolicy(mode="per_layer", budgets={"c": 3.0, "head": 1.0})
    budgets = resolve_budgets(policy, C, ("c", "head"))
    want = _oracle_per_layer_clipped_sum(apply_fn, params, batch, C,
                                         budgets=np.asarray(budgets))
    _, got, _, _ = clipped_grad_sum_detailed(
        apply_fn, params, batch, l2_clip=C, strategy="auto",
        clip_policy=policy, check=True)
    scale = max(max(float(jnp.abs(w).max())
                    for w in jax.tree.leaves(want)), 1.0)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            **_sum_tol(dtype, scale))


STALE_BUILDERS = (
    ("dense", dense_plus_head_model),
    ("seg_dense", seg_dense_plus_head_model),
    ("embed", embed_plus_head_model),
    ("conv", lambda dtype: conv_plus_head_model(dtype, CONV_GEOMS[4])),
    ("scale", scale_plus_head_model),
)


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
@pytest.mark.parametrize("build", [b for _, b in STALE_BUILDERS],
                         ids=[n for n, _ in STALE_BUILDERS])
def test_stale_bitwise_reproduces_flat(build, dtype):
    """Exactly-as-specified-stale: fed the previous step's norms (here:
    the flat run's own norms on the same batch), a stale step with the
    fused realizations disabled is *bitwise* the flat step — same
    computation, lagged coefficients — and returns bitwise the same
    current norms for the next step."""
    apply_fn, params, batch = build(dtype)
    C = 0.1
    _, want, prev_ns, _ = clipped_grad_sum_detailed(
        apply_fn, params, batch, l2_clip=C, strategy="auto")
    _, got, cur_ns, _ = clipped_grad_sum_detailed(
        apply_fn, params, batch, l2_clip=C, strategy="auto",
        clip_policy=ClipPolicy(mode="stale", fused=False),
        prev_norms_sq=prev_ns)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and bool(jnp.all(g == w)), \
            "stale(fused=False) must be bitwise the flat result"
    assert bool(jnp.all(cur_ns == prev_ns))


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
@pytest.mark.parametrize("build", [b for _, b in STALE_BUILDERS],
                         ids=[n for n, _ in STALE_BUILDERS])
def test_stale_fused_matches_oracle(build, dtype):
    """The fused single-pass realizations (gram_norm_fused where the plan
    marks them) reproduce the oracle's clipped sum when fed the oracle's
    norms — same tolerance bar as every other realization."""
    apply_fn, params, batch = build(dtype)
    C = 0.1
    pe = oracle_pe_grads(apply_fn, params, batch)
    prev_ns = true_norms_sq(pe)
    coef = clip_coefficients(prev_ns, C)
    want = jax.tree.map(
        lambda g: jnp.einsum("b...,b->...", g.astype(jnp.float32), coef), pe)
    _, got, cur_ns, _ = clipped_grad_sum_detailed(
        apply_fn, params, batch, l2_clip=C, strategy="auto",
        clip_policy=ClipPolicy(mode="stale", fused=True),
        prev_norms_sq=jnp.asarray(prev_ns))
    scale = max(max(float(jnp.abs(w).max())
                    for w in jax.tree.leaves(want)), 1.0)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            **_sum_tol(dtype, scale))
    # the pass's own norms (next step's coefficients) stay oracle-exact
    np.testing.assert_allclose(np.asarray(cur_ns), np.asarray(prev_ns),
                               **_tol(dtype))


# ---------------------------------------------------------------------------
# Hypothesis-driven geometry sweeps (CI installs requirements-dev.txt)


if HAVE_HYPOTHESIS:

    @given(st.integers(2, 12), st.integers(2, 8), st.integers(2, 8),
           st.integers(0, 99), st.sampled_from(["gram", "stream"]))
    def test_dense_norm_property(T, Di, Do, seed, method):
        apply_fn, params, batch = dense_seq_model(
            jnp.float32, B=3, T=T, Di=Di, Do=Do, seed=seed)
        _assert_norms_match(apply_fn, params, batch, jnp.float32,
                            norm_method=method)

    @given(st.integers(1, 2), st.integers(1, 2), st.integers(0, 2),
           st.sampled_from([1, 2]), st.integers(0, 99))
    def test_conv_ghost_norm_property(stride, dilation, padding, groups,
                                      seed):
        C = 4 * groups
        D = 2 * groups
        geom = (C, D, 8, 3, stride, padding, dilation, groups)
        apply_fn, params, batch = conv_model(jnp.float32, geom, seed=seed)
        _assert_norms_match(apply_fn, params, batch, jnp.float32,
                            conv_norm="ghost")

    @given(st.integers(2, 10), st.integers(2, 6), st.integers(5, 16),
           st.integers(0, 99), st.sampled_from(["segsum", "gram", "pe"]))
    def test_embed_norm_property(T, D, V, seed, method):
        apply_fn, params, batch = embed_model(jnp.float32, B=3, T=T, V=V,
                                              D=D, seed=seed)
        _assert_norms_match(apply_fn, params, batch, jnp.float32,
                            embed_method=method)

    @given(st.integers(2, 10), st.integers(2, 8), st.integers(2, 8),
           st.integers(0, 99), st.sampled_from(["gram", "stream"]))
    def test_per_layer_dense_property(T, Di, Do, seed, method):
        apply_fn, params, batch = dense_plus_head_model(
            jnp.float32, B=3, T=T, Di=Di, Do=Do, seed=seed)
        _assert_per_layer_matches(apply_fn, params, batch, jnp.float32,
                                  norm_method=method)

    @given(st.integers(1, 2), st.integers(1, 2), st.sampled_from([1, 2]),
           st.integers(0, 99))
    def test_stale_fused_conv_property(stride, dilation, groups, seed):
        C_in = 4 * groups
        D = 2 * groups
        geom = (C_in, D, 8, 3, stride, 1, dilation, groups)
        apply_fn, params, batch = conv_plus_head_model(jnp.float32, geom,
                                                       seed=seed)
        _, want, prev_ns, _ = clipped_grad_sum_detailed(
            apply_fn, params, batch, l2_clip=0.1, strategy="auto")
        _, got, _, _ = clipped_grad_sum_detailed(
            apply_fn, params, batch, l2_clip=0.1, strategy="auto",
            clip_policy=ClipPolicy(mode="stale", fused=True),
            prev_norms_sq=prev_ns)
        scale = max(max(float(jnp.abs(w).max())
                        for w in jax.tree.leaves(want)), 1.0)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(w, np.float32),
                **_sum_tol(jnp.float32, scale))


# ---------------------------------------------------------------------------
# The sharded pipeline passes the same oracle (8-device CI lane)


def _grad_extracting_optimizer(grads, state, params, *, lr, weight_decay):
    """Identity 'optimizer' that surfaces the pipeline's gradient as the
    new params, so the sharded jitted step's output IS the gradient."""
    return grads, state


@pytest.mark.multidevice
@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=8")
@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
def test_sharded_engine_passes_oracle(dtype):
    """The mesh-planned, explicitly sharded private step must reproduce
    the naive oracle's clipped mean gradient — same exactness bar as the
    single-device realizations above."""
    from repro.core import DPConfig, PrivacyEngine

    apply_fn, params, batch = conv_model(dtype, CONV_GEOMS[1], B=8, seed=7)
    mesh = make_auto_mesh((8,), ("data",))
    C = 0.1
    engine = PrivacyEngine(apply_fn, params, batch, dp=DPConfig(l2_clip=C),
                           optimizer=_grad_extracting_optimizer, mesh=mesh)
    got_grad, _, _, _ = engine.private_step(params, {"step": jnp.zeros(())},
                                            batch)
    B = batch["x"].shape[0]
    want = _oracle_clipped_sum(apply_fn, params, batch, C)
    want_grad = jax.tree.map(lambda g: g / B, want)
    scale = max(max(float(jnp.abs(w).max())
                    for w in jax.tree.leaves(want_grad)), 1e-3)
    for g, w in zip(jax.tree.leaves(got_grad), jax.tree.leaves(want_grad)):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   **_sum_tol(dtype, scale))


@pytest.mark.multidevice
@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=8")
@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
def test_sharded_per_layer_passes_oracle(dtype):
    """Per-layer clipping under the explicitly sharded step: per-layer
    per-example norms reduce over the data axes under SPMD (each group's
    coefficients see the psum'd group norm) and the result matches the
    per-layer Jacobian-clip oracle."""
    from repro.core import ClipPolicy, DPConfig, PrivacyEngine

    apply_fn, params, batch = conv_plus_head_model(dtype, CONV_GEOMS[1],
                                                   B=8, seed=7)
    mesh = make_auto_mesh((8,), ("data",))
    C = 0.1
    engine = PrivacyEngine(
        apply_fn, params, batch,
        dp=DPConfig(l2_clip=C, clipping=ClipPolicy(mode="per_layer")),
        optimizer=_grad_extracting_optimizer, mesh=mesh)
    got_grad, _, _, aux = engine.private_step(
        params, {"step": jnp.zeros(())}, batch)
    B = batch["x"].shape[0]
    want = _oracle_per_layer_clipped_sum(apply_fn, params, batch, C)
    want_grad = jax.tree.map(lambda g: g / B, want)
    assert aux["per_layer_clip_fraction"].shape == (2,)
    scale = max(max(float(jnp.abs(w).max())
                    for w in jax.tree.leaves(want_grad)), 1e-3)
    for g, w in zip(jax.tree.leaves(got_grad), jax.tree.leaves(want_grad)):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   **_sum_tol(dtype, scale))


@pytest.mark.multidevice
@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=8")
@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
def test_sharded_stale_passes_oracle(dtype):
    """Stale clipping under the sharded step: the bootstrap step clips
    exactly (flat oracle), and the steady step — fed the bootstrap's
    norms on the same batch — reproduces the flat oracle too (the lagged
    norms coincide with the current ones)."""
    from repro.core import ClipPolicy, DPConfig, PrivacyEngine

    apply_fn, params, batch = conv_plus_head_model(dtype, CONV_GEOMS[1],
                                                   B=8, seed=7)
    mesh = make_auto_mesh((8,), ("data",))
    C = 0.1
    engine = PrivacyEngine(
        apply_fn, params, batch,
        dp=DPConfig(l2_clip=C, clipping=ClipPolicy(mode="stale")),
        optimizer=_grad_extracting_optimizer, mesh=mesh)
    opt0 = {"step": jnp.zeros(())}
    B = batch["x"].shape[0]
    want = _oracle_clipped_sum(apply_fn, params, batch, C)
    want_grad = jax.tree.map(lambda g: g / B, want)
    scale = max(max(float(jnp.abs(w).max())
                    for w in jax.tree.leaves(want_grad)), 1e-3)
    boot_grad, _, _, aux = engine.private_step(params, opt0, batch)
    steady_grad, _, _, aux2 = engine.private_step(params, opt0, batch)
    assert "clip_fraction_lagged" in aux and "clip_fraction_lagged" in aux2
    for got in (boot_grad, steady_grad):
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want_grad)):
            np.testing.assert_allclose(np.asarray(g, np.float32),
                                       np.asarray(w, np.float32),
                                       **_sum_tol(dtype, scale))


# ---------------------------------------------------------------------------
# 2D (data x model) meshes: tensor-sharded layers pass the same oracle

_CONV_2D_AXES = {"c": {"w": ("mlp", None, None, "conv_k"), "b": ("mlp",)}}
_CONV_HEAD_2D_AXES = {"c": {"w": ("mlp", None, None, "conv_k"),
                            "b": ("mlp",)},
                      "head": {"w": ("embed", "mlp")}}


@pytest.mark.multidevice
@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=8")
@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
def test_sharded_2d_engine_passes_oracle(dtype):
    """data:4,model:2 — conv params partitioned over the model axis
    (out-channels), batch over data.  GSPMD psums the partial-Gram norm
    contributions over ``model`` and the (B,) norms over ``data``; the
    tensor-sharded step's clipped mean gradient must still match the
    naive Jacobian oracle exactly."""
    from repro.core import DPConfig, PrivacyEngine

    apply_fn, params, batch = conv_model(dtype, CONV_GEOMS[1], B=8, seed=7)
    mesh = make_auto_mesh((4, 2), ("data", "model"))
    C = 0.1
    engine = PrivacyEngine(apply_fn, params, batch, dp=DPConfig(l2_clip=C),
                           optimizer=_grad_extracting_optimizer, mesh=mesh,
                           param_axes=_CONV_2D_AXES, calibration="analytic")
    got_grad, _, _, _ = engine.private_step(params, {"step": jnp.zeros(())},
                                            batch)
    # the step really is tensor-sharded: conv weight partitioned on its
    # out-channel dim, not replicated
    w_spec = got_grad["c"]["w"].sharding.spec
    assert tuple(w_spec)[:1] == ("model",), w_spec
    B = batch["x"].shape[0]
    want = _oracle_clipped_sum(apply_fn, params, batch, C)
    want_grad = jax.tree.map(lambda g: g / B, want)
    scale = max(max(float(jnp.abs(w).max())
                    for w in jax.tree.leaves(want_grad)), 1e-3)
    for g, w in zip(jax.tree.leaves(got_grad), jax.tree.leaves(want_grad)):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   **_sum_tol(dtype, scale))


@pytest.mark.multidevice
@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=8")
@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
def test_sharded_2d_per_layer_passes_oracle(dtype):
    """Per-layer clipping on the 2D mesh: each group's per-example norm
    is psum'd over both axes exactly once (model partials + data
    examples) before the coefficients, matching the per-layer oracle.
    The 3-wide head does not divide the model axis and stays replicated
    — the mixed sharded/replicated layout is the production case."""
    from repro.core import ClipPolicy, DPConfig, PrivacyEngine

    apply_fn, params, batch = conv_plus_head_model(dtype, CONV_GEOMS[1],
                                                   B=8, seed=7)
    mesh = make_auto_mesh((4, 2), ("data", "model"))
    C = 0.1
    engine = PrivacyEngine(
        apply_fn, params, batch,
        dp=DPConfig(l2_clip=C, clipping=ClipPolicy(mode="per_layer")),
        optimizer=_grad_extracting_optimizer, mesh=mesh,
        param_axes=_CONV_HEAD_2D_AXES, calibration="analytic")
    got_grad, _, _, aux = engine.private_step(
        params, {"step": jnp.zeros(())}, batch)
    assert tuple(got_grad["c"]["w"].sharding.spec)[:1] == ("model",)
    assert got_grad["head"]["w"].sharding.is_fully_replicated
    B = batch["x"].shape[0]
    want = _oracle_per_layer_clipped_sum(apply_fn, params, batch, C)
    want_grad = jax.tree.map(lambda g: g / B, want)
    assert aux["per_layer_clip_fraction"].shape == (2,)
    scale = max(max(float(jnp.abs(w).max())
                    for w in jax.tree.leaves(want_grad)), 1e-3)
    for g, w in zip(jax.tree.leaves(got_grad), jax.tree.leaves(want_grad)):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   **_sum_tol(dtype, scale))


@pytest.mark.multidevice
@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=8")
@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
def test_sharded_2d_stale_passes_oracle(dtype):
    """Stale clipping on the 2D mesh: bootstrap and steady step (lagged
    norms == current norms on a repeated batch) both match the flat
    oracle with tensor-sharded params."""
    from repro.core import ClipPolicy, DPConfig, PrivacyEngine

    apply_fn, params, batch = conv_plus_head_model(dtype, CONV_GEOMS[1],
                                                   B=8, seed=7)
    mesh = make_auto_mesh((4, 2), ("data", "model"))
    C = 0.1
    engine = PrivacyEngine(
        apply_fn, params, batch,
        dp=DPConfig(l2_clip=C, clipping=ClipPolicy(mode="stale")),
        optimizer=_grad_extracting_optimizer, mesh=mesh,
        param_axes=_CONV_HEAD_2D_AXES, calibration="analytic")
    opt0 = {"step": jnp.zeros(())}
    B = batch["x"].shape[0]
    want = _oracle_clipped_sum(apply_fn, params, batch, C)
    want_grad = jax.tree.map(lambda g: g / B, want)
    scale = max(max(float(jnp.abs(w).max())
                    for w in jax.tree.leaves(want_grad)), 1e-3)
    boot_grad, _, _, _ = engine.private_step(params, opt0, batch)
    steady_grad, _, _, _ = engine.private_step(params, opt0, batch)
    for got in (boot_grad, steady_grad):
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want_grad)):
            np.testing.assert_allclose(np.asarray(g, np.float32),
                                       np.asarray(w, np.float32),
                                       **_sum_tol(dtype, scale))


# ---------------------------------------------------------------------------
# Block-level attention realization ("attn" kind): the whole GQA/MLA
# block tapped as one unit, per-example norms from a layer-local
# recompute (ghost) or materialized per-example grads (pe), vs the
# naive Jacobian oracle.


def gqa_attn_plus_head_model(dtype, B=4, T=8, D=16, H=4, KV=2, hd=4,
                             seed=15, qk_norm=False):
    from repro.models import attention as attn_mod
    from repro.models import common as cm
    tree = attn_mod.gqa_init(jax.random.PRNGKey(seed), D, H, KV, hd,
                             qk_norm=qk_norm, dtype=dtype)
    rng = np.random.RandomState(seed)
    params = {"attn": cm.split_tree(tree)[0],
              "head": _head_params(rng, D, dtype)}

    def apply_fn(p, batch, tp):
        y, _ = attn_mod.gqa_apply(tp, "attn", p["attn"], batch["x"],
                                  n_heads=H, n_kv=KV, head_dim=hd,
                                  qk_norm=qk_norm, dp_attn=True)
        return _head_loss(tp, p, jnp.tanh(y.astype(jnp.float32)).mean(1))

    return apply_fn, params, {"x": jnp.asarray(rng.randn(B, T, D) * 0.5,
                                               dtype)}


_MLA_KW = dict(q_lora_rank=8, kv_lora_rank=8, qk_nope_dim=4,
               qk_rope_dim=4, v_head_dim=4)


def mla_attn_plus_head_model(dtype, B=4, T=6, D=16, H=2, seed=16):
    from repro.models import attention as attn_mod
    from repro.models import common as cm
    tree = attn_mod.mla_init(jax.random.PRNGKey(seed), D, H, dtype=dtype,
                             **_MLA_KW)
    rng = np.random.RandomState(seed)
    params = {"attn": cm.split_tree(tree)[0],
              "head": _head_params(rng, D, dtype)}

    def apply_fn(p, batch, tp):
        y, _ = attn_mod.mla_apply(tp, "attn", p["attn"], batch["x"],
                                  n_heads=H, dp_attn=True, **_MLA_KW)
        return _head_loss(tp, p, jnp.tanh(y.astype(jnp.float32)).mean(1))

    return apply_fn, params, {"x": jnp.asarray(rng.randn(B, T, D) * 0.5,
                                               dtype)}


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
@pytest.mark.parametrize("method", ("ghost", "pe"))
@pytest.mark.parametrize("qk_norm", (False, True), ids=("plain", "qknorm"))
def test_attn_gqa_norms_match_oracle(qk_norm, method, dtype):
    apply_fn, params, batch = gqa_attn_plus_head_model(dtype,
                                                       qk_norm=qk_norm)
    _assert_norms_match(apply_fn, params, batch, dtype, attn_norm=method)


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
@pytest.mark.parametrize("method", ("ghost", "pe"))
def test_attn_mla_norms_match_oracle(method, dtype):
    apply_fn, params, batch = mla_attn_plus_head_model(dtype)
    _assert_norms_match(apply_fn, params, batch, dtype, attn_norm=method)


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
@pytest.mark.parametrize("strategy", ("ghost", "auto"))
def test_attn_clipped_sum_matches_oracle(strategy, dtype):
    apply_fn, params, batch = gqa_attn_plus_head_model(dtype)
    _assert_clipped_sum_matches(apply_fn, params, batch, dtype,
                                strategy=strategy)


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
def test_attn_mla_clipped_sum_matches_oracle(dtype):
    apply_fn, params, batch = mla_attn_plus_head_model(dtype)
    _assert_clipped_sum_matches(apply_fn, params, batch, dtype,
                                strategy="auto")


def test_attn_planner_selects_realization():
    """Acceptance: the planner prices the block tap as its own "attn"
    kind and picks a non-materializing norm realization for it."""
    from repro.core import costmodel
    apply_fn, params, batch = gqa_attn_plus_head_model(jnp.float32)
    costmodel.clear_plan_cache()
    plan = costmodel.get_plan(apply_fn, params, batch)
    lp = plan.layers["attn"]
    assert lp.kind == "attn"
    assert lp.norm_method == "ghost"
    assert "attn" in plan.explain()


@pytest.mark.multidevice
@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=8")
@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
def test_sharded_attn_engine_passes_oracle(dtype):
    """The planned, explicitly sharded private step over the attn
    realization matches the naive oracle's clipped mean gradient on an
    8-device data mesh — same bar as the dense/conv lanes above."""
    from repro.core import DPConfig, PrivacyEngine, costmodel

    apply_fn, params, batch = gqa_attn_plus_head_model(dtype, B=8)
    mesh = make_auto_mesh((8,), ("data",))
    C = 0.1
    costmodel.clear_plan_cache()
    engine = PrivacyEngine(apply_fn, params, batch, dp=DPConfig(l2_clip=C),
                           optimizer=_grad_extracting_optimizer, mesh=mesh)
    got_grad, _, _, _ = engine.private_step(params, {"step": jnp.zeros(())},
                                            batch)
    B = batch["x"].shape[0]
    want = _oracle_clipped_sum(apply_fn, params, batch, C)
    want_grad = jax.tree.map(lambda g: g / B, want)
    scale = max(max(float(jnp.abs(w).max())
                    for w in jax.tree.leaves(want_grad)), 1e-3)
    for g, w in zip(jax.tree.leaves(got_grad), jax.tree.leaves(want_grad)):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   **_sum_tol(dtype, scale))
