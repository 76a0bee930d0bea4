"""The per-layer execution planner: cost-model crossovers, plan caching,
the one-forward/one-backward steady state, and auto == naive exactness on
a CNN config and a tied-embedding LM config."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import tree_maxdiff, true_norms_sq
from repro.configs import get_config
from repro.core import clipped_grad_sum, costmodel, ghost_norms, kinds, \
    per_example_grads
from repro.core.tapper import STATS, LayerMeta
from repro.kernels import ops as kops
from repro.models.convops import conv_output_spatial
from repro.models.registry import build_model

TOL = 1e-4


# ---------------------------------------------------------------------------
# Cost-model crossovers (pinned: these are the paper's empirical regimes)


def test_dense_gram_stream_crossover():
    # Long sequence, modest width: streaming the per-example grads wins.
    assert costmodel.dense_norm_method(4096, 256, 256, 8) == "stream"
    # Short sequence, wide layer: the T² Gram trick wins.
    assert costmodel.dense_norm_method(64, 1024, 1024, 8) == "gram"
    # No sequence axis: exact rank-1 factorization.
    assert costmodel.dense_norm_method(1, 4096, 4096, 8) == "rank1"
    # Streaming is vetoed when the (B, Din, Dout) scratch blows the budget.
    assert costmodel.dense_norm_method(4096, 256, 256, 8,
                                       mem_budget=1 << 20) == "gram"


def _planned_conv_norm(side, C, D, k, B, **opts):
    """The norm method the planner gives one stride-1 'same' conv whose
    output is side x side."""
    meta = LayerMeta("conv", ("conv",), static={
        "stride": 1, "dilation": 1, "padding": k // 2, "groups": 1,
        "kernel_shape": (D, C, k, k)})
    f32 = jnp.float32
    cap = {"x": jax.ShapeDtypeStruct((B, C, side, side), f32)}
    dy = jax.ShapeDtypeStruct((B, D, side, side), f32)
    plan = costmodel.plan_execution({"conv": meta}, {"conv": cap},
                                    {"conv": dy}, lambda: {}, **opts)
    return plan.layers["conv"].norm_method


def test_conv_ghost_pe_crossover():
    # Early conv layer: large spatial output, few channels -> materialize
    # (the paper's Algorithm 2 regime).
    assert _planned_conv_norm(64, C=3, D=64, k=11, B=8) == "pe"
    # Late conv layer: tiny spatial output, wide channels -> im2col ghost
    # norm (the mixed-clipping regime of Bu et al.).
    assert _planned_conv_norm(4, C=512, D=512, k=3, B=8) == "ghost"
    # Memory veto: pe scratch over budget falls back to the chunked ghost.
    assert _planned_conv_norm(64, C=256, D=512, k=3, B=64,
                              mem_budget=1 << 20) == "ghost"


def test_plan_is_mixed_on_toy_model(toy_model):
    apply_fn, params, batch = toy_model
    plan = costmodel.get_plan(apply_fn, params, batch)
    methods = {n: lp.norm_method for n, lp in plan.layers.items()}
    # conv1 is an early layer (large T, 3 channels): materialized.
    assert methods["conv1"] == "pe"
    # the T=1 head is the exact rank-1 factorization.
    assert methods["head"] == "rank1"
    # at least two distinct norm realizations -> genuinely mixed.
    assert len(set(methods.values())) >= 2
    assert not plan.needs_backward


def test_plan_cache_roundtrip(toy_model):
    apply_fn, params, batch = toy_model
    costmodel.clear_plan_cache()
    p1 = costmodel.get_plan(apply_fn, params, batch)
    p2 = costmodel.get_plan(apply_fn, params, batch)
    assert p1 is p2
    assert costmodel.plan_cache_info()["size"] == 1
    # A different batch shape is a different plan.
    smaller = jax.tree.map(lambda a: a[:2], batch)
    p3 = costmodel.get_plan(apply_fn, params, smaller)
    assert p3 is not p1
    assert costmodel.plan_cache_info()["size"] == 2


# ---------------------------------------------------------------------------
# Steady-state execution counts: auto is 1 forward + 1 backward; ghost 2+2


def test_auto_single_forward_backward(toy_model):
    apply_fn, params, batch = toy_model
    costmodel.clear_plan_cache()
    STATS.reset()
    clipped_grad_sum(apply_fn, params, batch, l2_clip=0.1, strategy="auto")
    assert STATS.snapshot() == {"forwards": 1, "backwards": 1, "probes": 1}
    STATS.reset()
    clipped_grad_sum(apply_fn, params, batch, l2_clip=0.1, strategy="auto")
    # warm: the cached plan removes the probe; exactly one fwd + one bwd.
    assert STATS.snapshot() == {"forwards": 1, "backwards": 1, "probes": 0}
    STATS.reset()
    clipped_grad_sum(apply_fn, params, batch, l2_clip=0.1, strategy="ghost")
    assert STATS.forwards == 2 and STATS.backwards == 2


# ---------------------------------------------------------------------------
# auto == naive oracle


def test_auto_matches_naive_toy(toy_model):
    apply_fn, params, batch = toy_model
    C = 0.05
    _, ref, nref = clipped_grad_sum(apply_fn, params, batch, l2_clip=C,
                                    strategy="naive")
    _, got, ngot = clipped_grad_sum(apply_fn, params, batch, l2_clip=C,
                                    strategy="auto", check=True)
    assert tree_maxdiff(got, ref) < TOL
    np.testing.assert_allclose(np.asarray(ngot), np.asarray(nref), rtol=1e-4)


def test_auto_matches_naive_cnn():
    cfg = get_config("alexnet").replace(img_size=64, n_classes=10)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    batch = {"img": jnp.array(rng.randn(2, 3, 64, 64), jnp.float32),
             "label": jnp.array(rng.randint(0, 10, (2,)))}
    _, ref, _ = clipped_grad_sum(model.apply, params, batch, l2_clip=1.0,
                                 strategy="naive")
    _, got, _ = clipped_grad_sum(model.apply, params, batch, l2_clip=1.0,
                                 strategy="auto", check=True)
    scale = max(float(jnp.abs(x).max()) for x in jax.tree.leaves(ref))
    assert tree_maxdiff(got, ref) < TOL * max(scale, 1.0)
    # AlexNet spans both conv regimes: the plan must actually mix.
    plan = costmodel.get_plan(model.apply, params, batch)
    conv_methods = {lp.norm_method for lp in plan.layers.values()
                    if lp.kind == "conv"}
    assert conv_methods == {"pe", "ghost"}


def _alexnet_pe_plan(B, **opts):
    cfg = get_config("alexnet").replace(img_size=67, n_classes=10)
    model = build_model(cfg)
    params = jax.eval_shape(lambda k: model.init(k)[0],
                            jax.random.PRNGKey(0))
    batch = {"img": jax.ShapeDtypeStruct((B, 3, 67, 67), jnp.float32),
             "label": jax.ShapeDtypeStruct((B,), jnp.int32)}
    costmodel.clear_plan_cache()   # the route depends on the platform
    return costmodel.get_plan(model.apply, params, batch, conv_norm="pe",
                              **opts)


def test_strided_conv_pe_priced_at_space_to_depth_taps(monkeypatch):
    """On a TPU AlexNet conv0 (11x11, stride 4) forms its per-example
    gradients through space to depth, with 12x12 taps cropped to 11x11:
    its ``pe`` norm is priced at 144 taps, a stride-1 conv at its own 25."""
    from repro.kernels import ops as kops
    monkeypatch.setattr(kops, "on_tpu", lambda: True)
    B = 4
    plan = _alexnet_pe_plan(B)
    conv0, conv1 = plan.layers["conv0"], plan.layers["conv1"]
    assert conv0.norm_method == conv1.norm_method == "pe"
    assert conv0.norm_flops == 4.0 * B * 16 * 16 * 3 * 144 * 64
    assert conv1.norm_flops == 4.0 * B * 7 * 7 * 64 * 25 * 192
    # the sum phase contracts the true kernel
    assert conv0.contrib_flops == 2.0 * B * 16 * 16 * 3 * 121 * 64


@pytest.mark.parametrize("tpu,conv_impl", [(False, "auto"), (True, "fgc")],
                         ids=["auto_off_tpu", "explicit_fgc"])
def test_strided_conv_pe_priced_at_the_taps_fgc_runs(monkeypatch, tpu,
                                                     conv_impl):
    """Where conv0's per-example gradients take ``fgc`` (off the TPU, or
    asked for), its ``pe`` norm is priced at its own 121 taps."""
    from repro.kernels import ops as kops
    monkeypatch.setattr(kops, "on_tpu", lambda: tpu)
    B = 4
    plan = _alexnet_pe_plan(B, conv_impl=conv_impl)
    assert plan.layers["conv0"].norm_flops == 4.0 * B * 16 * 16 * 3 * 121 * 64


def test_auto_matches_naive_lm_tied():
    cfg = get_config("llama3.2-1b").reduced()
    assert cfg.tie_embeddings
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(1)
    batch = {"tokens": jnp.array(rng.randint(0, cfg.vocab, (3, 8))),
             "labels": jnp.array(rng.randint(0, cfg.vocab, (3, 8)))}
    _, pe = per_example_grads(model.apply, params, batch, "naive")
    want = true_norms_sq(pe)
    _, ref, _ = clipped_grad_sum(model.apply, params, batch, l2_clip=1.0,
                                 strategy="naive")
    _, got, ngot = clipped_grad_sum(model.apply, params, batch, l2_clip=1.0,
                                    strategy="auto", check=True)
    scale = max(float(jnp.abs(x).max()) for x in jax.tree.leaves(ref))
    assert tree_maxdiff(got, ref) < TOL * max(scale, 1.0)
    np.testing.assert_allclose(np.asarray(ngot), np.asarray(want), rtol=3e-4)


def test_auto_under_jit_and_microbatches(toy_model):
    from repro.core import DPConfig
    from repro.core.clipping import dp_gradient
    apply_fn, params, batch = toy_model
    ref = dp_gradient(apply_fn, params, batch,
                      cfg=DPConfig(l2_clip=0.1, strategy="multi"))
    dpc = DPConfig(l2_clip=0.1, strategy="auto", microbatches=2)
    loss, grad, aux = jax.jit(
        lambda p, b: dp_gradient(apply_fn, p, b, cfg=dpc))(params, batch)
    assert np.isfinite(float(loss))
    assert tree_maxdiff(grad, ref[1]) < TOL


# ---------------------------------------------------------------------------
# Conv ghost norm (im2col Gram) against the materializing oracle


@pytest.mark.parametrize("C,D,HW,K,s,p,dil,g", [
    (6, 8, 10, 3, 2, 1, 1, 1),    # strided + padded
    (8, 12, 9, 3, 1, 2, 2, 1),    # dilated
    (8, 12, 8, 3, 1, 1, 1, 4),    # grouped
])
def test_conv_ghost_norm_exact(C, D, HW, K, s, p, dil, g):
    rng = np.random.RandomState(2)
    B = 3
    x = jnp.array(rng.randn(B, C, HW, HW), jnp.float32)
    out_sp = conv_output_spatial((HW, HW), (K, K), s, dil, p)
    dy = jnp.array(rng.randn(B, D, *out_sp), jnp.float32)
    meta = LayerMeta("conv", ("c",), bias_key="b",
                     static={"stride": s, "dilation": dil, "padding": p,
                             "groups": g, "kernel_shape": (D, C // g, K, K)})
    n_pe = kinds.conv_norm_sq(meta, {"x": x}, dy, method="pe")
    n_gh = kinds.conv_norm_sq(meta, {"x": x}, dy, method="ghost")
    np.testing.assert_allclose(np.asarray(n_gh), np.asarray(n_pe), rtol=1e-4)


def test_ghost_norms_conv_ghost_mode(toy_model):
    apply_fn, params, batch = toy_model
    _, pe = per_example_grads(apply_fn, params, batch, "naive")
    want = true_norms_sq(pe)
    _, got, _ = ghost_norms(apply_fn, params, batch, conv_norm="ghost")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4)


# ---------------------------------------------------------------------------
# Fused Pallas kernel: norm + weighted contribution in one pass


def test_gram_norm_fused_kernel():
    rng = np.random.RandomState(3)
    B, T, Di, Do = 3, 20, 7, 9
    x = jnp.array(rng.randn(B, T, Di), jnp.float32)
    dy = jnp.array(rng.randn(B, T, Do), jnp.float32)
    w = jnp.array(rng.rand(B), jnp.float32)
    meta = LayerMeta("dense", ("p",), bias_key="b")
    n_ref = kinds.dense_norm_sq(meta, {"x": x}, dy, method="gram")
    c_ref = kinds.dense_contrib(meta, {"x": x}, dy, w)
    n, cw, cb = kops.gram_norm_fused(x, dy, w, has_bias=True, bt=8)
    np.testing.assert_allclose(np.asarray(n), np.asarray(n_ref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(cw), np.asarray(c_ref["w"]),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(cb), np.asarray(c_ref["b"]),
                               atol=1e-4)


def test_dense_norm_and_contrib_methods():
    rng = np.random.RandomState(4)
    B, T, Di, Do = 2, 12, 5, 6
    x = jnp.array(rng.randn(B, T, Di), jnp.float32)
    dy = jnp.array(rng.randn(B, T, Do), jnp.float32)
    w = jnp.array(rng.rand(B), jnp.float32)
    meta = LayerMeta("dense", ("p",))
    c_ref = kinds.dense_contrib(meta, {"x": x}, dy, w)
    for method in ("pallas", "stream"):
        n, c = kinds.dense_norm_and_contrib(meta, {"x": x}, dy, w,
                                            method=method)
        np.testing.assert_allclose(np.asarray(c["w"]),
                                   np.asarray(c_ref["w"]), atol=1e-4)


# ---------------------------------------------------------------------------
# Row-tile autotuning for the per-example conv-grad kernel


def test_pe_conv_bd_autotune():
    """The kernel's tile: input rows per grid step, the fewest row tiles
    that fit the VMEM budget, balanced (its channel tiles are fixed at 128
    lanes); the last tile may reach past the image."""
    from repro.kernels import pe_conv_grad as pc
    budget = kops.VMEM_BUDGET
    for H, K, W in ((30, 3, 32), (31, 5, 31), (256, 3, 256), (64, 3, 64)):
        for eb in (8, 16):
            th = pc.row_tile(H, K, K, W, budget, eb)
            tiles = -(-H // th)
            # the tile fits the budget
            assert pc.vmem_bytes(th, K, K, W, eb) <= budget
            # no smaller tile count fits: each tile of fewer is too large
            if tiles > 1:
                assert pc.vmem_bytes(-(-H // (tiles - 1)), K, K, W,
                                     eb) > budget
            # balanced: the last tile reaches fewer rows past H than
            # there are tiles
            assert 0 <= tiles * th - H < tiles
            # a smaller budget never gives a larger tile
            assert pc.row_tile(H, K, K, W, 16 << 20, eb) <= th
    # AlexNet conv1 (31 rows, prime) in two tiles, one row past the image
    assert pc.row_tile(31, 5, 5, 31, budget, 16) == 16
    # VGG16 at 256 px: conv1 in 86 tiles of 3, conv2-9 as before
    assert pc.row_tile(256, 3, 3, 256, budget, 16) == 3
    assert pc.row_tile(128, 3, 3, 128, budget, 16) == 8
    assert pc.row_tile(64, 3, 3, 64, budget, 16) == 16
    assert pc.row_tile(32, 3, 3, 32, budget, 16) == 32
    # a budget nothing fits falls back to one row per step
    assert pc.row_tile(30, 3, 3, 32, 1 << 10) == 1


def test_pe_conv_row_tiles_tallied():
    """Tracing an AlexNet-conv1-shaped call (5x5, padding 2, 31x31)
    tallies its row tiling: two tiles of 16 rows, the last one row past
    the image."""
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16)
            for s in ((16, 64, 31, 31), (16, 192, 31, 31))]
    STATS.reset()
    jax.eval_shape(lambda x, dy: kops.pe_conv_grad(
        x, dy, kernel_spatial=(5, 5), padding=2), *args)
    assert STATS.row_tiles == {(31, 16, 2): 1}


def test_planner_backward_sum_phase_reachable():
    """A local_vjp layer whose per-example-grad stash blows the budget is
    charged the vmapped-VJP premium on its contraction; when it dominates
    the model, the planner routes its sum through one shared weighted
    backward."""
    from repro.core.tapper import LayerMeta

    B, T, D = 8, 128, 256
    metas = {
        "ssm": LayerMeta("local_vjp", ("ssm",), fn=lambda p, x: x),
        "head": LayerMeta("dense", ("head",)),
    }
    cap_shapes = {
        "ssm": {"inputs": (jax.ShapeDtypeStruct((B, T, D), jnp.float32),)},
        "head": {"x": jax.ShapeDtypeStruct((B, 1, 8), jnp.float32)},
    }
    tap_shapes = {
        "ssm": jax.ShapeDtypeStruct((B, T, D), jnp.float32),
        "head": jax.ShapeDtypeStruct((B, 1, 4), jnp.float32),
    }
    params = {"ssm": {"A": jnp.zeros((4096, 4096))},
              "head": {"w": jnp.zeros((8, 4))}}
    plan = costmodel.plan_execution(
        metas, cap_shapes, tap_shapes, lambda: {}, params,
        mem_budget=B * 4096 * 4096 * 4 // 2)  # stash over budget
    assert not plan.layers["ssm"].stash
    assert plan.needs_backward
    sums = {g.path: g.sum_method for g in plan.groups}
    assert sums[("ssm",)] == "backward"
    assert sums[("head",)] != "backward"


def test_executor_backward_sum_phase_exact(toy_model):
    """Force a group onto the weighted-backward sum path and check the
    executor still reproduces the naive clipped sum (and pays the extra
    forward+backward)."""
    import dataclasses

    apply_fn, params, batch = toy_model
    C = 0.05
    plan = costmodel.get_plan(apply_fn, params, batch)
    groups = tuple(
        dataclasses.replace(g, sum_method="backward")
        if g.path == ("head",) else g for g in plan.groups)
    forced = dataclasses.replace(plan, groups=groups, needs_backward=True)
    _, ref, _ = clipped_grad_sum(apply_fn, params, batch, l2_clip=C,
                                 strategy="naive")
    STATS.reset()
    from repro.core.strategies import planned_clipped_sum
    _, got, _, _ = planned_clipped_sum(apply_fn, params, batch, forced,
                                       l2_clip=C, check=True)
    assert STATS.forwards == 2 and STATS.backwards == 2
    assert tree_maxdiff(got, ref) < TOL


def test_planner_cumulative_stash_budget(toy_model):
    """Stashes live together until the sum phase, so the budget must be
    charged across groups: with a budget big enough for each layer but
    not all of them, later groups fall back to contrib — and the plan
    still executes exactly."""
    apply_fn, params, batch = toy_model
    plan_big = costmodel.get_plan(apply_fn, params, batch)
    stashed = [g for g in plan_big.groups if g.sum_method == "stash"]
    assert len(stashed) >= 2
    per_group = [max(plan_big.layers[n].stash_bytes for n in g.members)
                 for g in stashed]
    budget = int(max(per_group) + min(per_group) / 2)  # fits 1, not all
    plan_small = costmodel.get_plan(apply_fn, params, batch,
                                    mem_budget=budget)
    kinds_small = [g.sum_method for g in plan_small.groups]
    assert "contrib" in kinds_small            # something got flipped
    running = 0.0
    for g in plan_small.groups:
        if g.sum_method == "stash":
            running += max(plan_small.layers[n].stash_bytes
                           for n in g.members)
    assert running <= budget
    from repro.core.strategies import planned_clipped_sum
    C = 0.05
    _, ref, _ = clipped_grad_sum(apply_fn, params, batch, l2_clip=C,
                                 strategy="naive")
    _, got, _, _ = planned_clipped_sum(apply_fn, params, batch, plan_small,
                                       l2_clip=C, check=True)
    assert tree_maxdiff(got, ref) < TOL


def test_planner_stash_memory_respects_stack():
    """A scanned stack of dense layers multiplies the stashed per-example
    grad scratch; the planner must veto the stash (falling back to the
    layer-at-a-time stream norm or the Gram) instead of holding the whole
    stack."""
    from repro.core.tapper import LayerMeta
    import jax.numpy as jnp

    L, B, T, D = 32, 8, 2048, 1024
    meta = LayerMeta("dense", ("blocks", "fc"), scanned=1)
    cap = {"x": jax.ShapeDtypeStruct((L, B, T, D), jnp.float32)}
    dy = jax.ShapeDtypeStruct((L, B, T, D), jnp.float32)
    budget = 2 * B * D * D * 4  # two layers' worth: per-layer ok, stack not
    lp = costmodel._plan_layer("fc", meta, cap, dy, norm_method="auto",
                               embed_method="auto", conv_norm="auto",
                               mem_budget=budget)
    assert not lp.stash
    # with room for the whole stack, stashing is back on
    lp2 = costmodel._plan_layer("fc", meta, cap, dy, norm_method="auto",
                                embed_method="auto", conv_norm="auto",
                                mem_budget=L * B * D * D * 4)
    assert lp2.stash and lp2.norm_method == "stream"
