"""PrivacyEngine equivalence suite: engine vs legacy dp_gradient on a CNN
and a tied-embedding LM, plan JSON round-trip, probe-free execution from a
deserialized plan, plan-driven auto-microbatching, and the restructured
DPConfig (NormCfg nesting, per-layer overrides, legacy-kwarg shims)."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import tree_maxdiff
from repro.core import (DPConfig, ExecPlan, NormCfg, PrivacyEngine,
                        clipped_grad_sum, costmodel)
from repro.core.clipping import dp_gradient
from repro.core.tapper import STATS
from repro.configs import get_config
from repro.models.registry import build_model

TOL = 1e-5


def _bitwise_equal(a, b):
    return all(bool(jnp.all(x == y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# Engine == legacy dp_gradient


def test_engine_matches_dp_gradient_toy(toy_model):
    apply_fn, params, batch = toy_model
    dp = DPConfig(l2_clip=0.1)
    loss_l, grad_l, aux_l = dp_gradient(apply_fn, params, batch, cfg=dp)
    engine = PrivacyEngine(apply_fn, params, batch, dp=dp)
    loss_e, grad_e, aux_e = engine.noisy_grad(params, batch)
    assert float(loss_l) == float(loss_e)
    assert _bitwise_equal(grad_l, grad_e)
    np.testing.assert_array_equal(np.asarray(aux_l["per_example_norms"]),
                                  np.asarray(aux_e["per_example_norms"]))


def test_engine_matches_dp_gradient_cnn():
    cfg = get_config("alexnet").replace(img_size=64, n_classes=10)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    batch = {"img": jnp.array(rng.randn(2, 3, 64, 64), jnp.float32),
             "label": jnp.array(rng.randint(0, 10, (2,)))}
    dp = DPConfig(l2_clip=1.0)
    _, grad_l, _ = dp_gradient(model.apply, params, batch, cfg=dp)
    engine = PrivacyEngine(model.apply, params, batch, dp=dp)
    _, grad_e, _ = engine.noisy_grad(params, batch)
    assert _bitwise_equal(grad_l, grad_e)


def test_engine_matches_dp_gradient_lm_tied():
    cfg = get_config("llama3.2-1b").reduced()
    assert cfg.tie_embeddings
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(1)
    batch = {"tokens": jnp.array(rng.randint(0, cfg.vocab, (2, 8))),
             "labels": jnp.array(rng.randint(0, cfg.vocab, (2, 8)))}
    dp = DPConfig(l2_clip=1.0)
    _, grad_l, _ = dp_gradient(model.apply, params, batch, cfg=dp)
    engine = PrivacyEngine(model.apply, params, batch, dp=dp)
    _, grad_e, _ = engine.noisy_grad(params, batch)
    assert _bitwise_equal(grad_l, grad_e)
    # and both match the naive oracle
    _, gsum, _ = clipped_grad_sum(model.apply, params, batch, l2_clip=1.0,
                                  strategy="naive")
    B = batch["tokens"].shape[0]
    ref = jax.tree.map(lambda g: g / B, gsum)
    scale = max(float(jnp.abs(x).max()) for x in jax.tree.leaves(ref))
    assert tree_maxdiff(grad_e, ref) < 1e-4 * max(scale, 1.0)


def test_engine_steady_state_one_forward_one_backward(toy_model):
    apply_fn, params, batch = toy_model
    engine = PrivacyEngine(apply_fn, params, batch, dp=DPConfig(l2_clip=0.1))
    engine.noisy_grad(params, batch)      # warm the plan cache
    STATS.reset()
    engine.noisy_grad(params, batch)
    assert STATS.snapshot() == {"forwards": 1, "backwards": 1, "probes": 0}


# ---------------------------------------------------------------------------
# Plan serialization


def test_plan_json_roundtrip(toy_model):
    apply_fn, params, batch = toy_model
    plan = PrivacyEngine(apply_fn, params, batch).plan()
    restored = ExecPlan.from_json(plan.to_json())
    assert restored == plan
    assert restored.fingerprint == plan.fingerprint
    assert restored.tap_shapes == plan.tap_shapes
    # tampering breaks equality
    bad = dataclasses.replace(restored, needs_backward=True)
    assert bad != plan


def test_deserialized_plan_executes_probe_free(toy_model):
    apply_fn, params, batch = toy_model
    dp = DPConfig(l2_clip=0.1)
    engine = PrivacyEngine(apply_fn, params, batch, dp=dp)
    _, grad_ref, _ = engine.noisy_grad(params, batch)
    restored = ExecPlan.from_json(engine.plan().to_json())
    costmodel.clear_plan_cache()
    engine2 = PrivacyEngine(apply_fn, params, batch, dp=dp, plan=restored)
    STATS.reset()
    _, grad, _ = engine2.noisy_grad(params, batch)
    assert STATS.snapshot() == {"forwards": 1, "backwards": 1, "probes": 0}
    assert _bitwise_equal(grad, grad_ref)


def test_plan_store_hit_skips_probe(toy_model, tmp_path):
    apply_fn, params, batch = toy_model
    engine = PrivacyEngine(apply_fn, params, batch)
    path = str(tmp_path / "plans.json")
    engine.save_plan(path)
    costmodel.clear_plan_cache()
    costmodel.clear_plan_store()
    try:
        assert costmodel.load_plan_store(path) == 1
        STATS.reset()
        engine2 = PrivacyEngine(apply_fn, params, batch)
        engine2.plan()
        assert STATS.probes == 0
    finally:
        costmodel.clear_plan_store()


def test_stale_plan_fails_loudly(toy_model):
    apply_fn, params, batch = toy_model
    plan = PrivacyEngine(apply_fn, params, batch).plan()
    renamed = {("other_" + n): lp for n, lp in plan.layers.items()}
    stale = dataclasses.replace(plan, layers=renamed)
    engine = PrivacyEngine(apply_fn, params, batch, plan=stale)
    with pytest.raises(ValueError, match="does not match"):
        engine.noisy_grad(params, batch)


# ---------------------------------------------------------------------------
# Plan-driven auto-microbatching


def test_auto_microbatches_matches_explicit(toy_model):
    apply_fn, params, batch = toy_model
    norm = NormCfg(mem_budget=1 << 14)   # tiny: forces a split
    engine = PrivacyEngine(apply_fn, params, batch,
                           dp=DPConfig(l2_clip=0.1, microbatches="auto",
                                       norm=norm))
    m = engine.microbatches()
    assert m > 1
    B = batch["label"].shape[0]
    assert B % m == 0
    _, grad_auto, _ = engine.noisy_grad(params, batch)
    explicit = PrivacyEngine(apply_fn, params, batch,
                             dp=DPConfig(l2_clip=0.1, microbatches=m,
                                         norm=norm))
    _, grad_exp, _ = explicit.noisy_grad(params, batch)
    assert _bitwise_equal(grad_auto, grad_exp)
    # and the split changes nothing vs the unsplit gradient
    _, grad_one, _ = PrivacyEngine(apply_fn, params, batch,
                                   dp=DPConfig(l2_clip=0.1)).noisy_grad(
        params, batch)
    assert tree_maxdiff(grad_auto, grad_one) < TOL


def test_auto_microbatches_defaults_to_one(toy_model):
    apply_fn, params, batch = toy_model
    engine = PrivacyEngine(apply_fn, params, batch,
                           dp=DPConfig(microbatches="auto"))
    assert engine.microbatches() == 1   # toy model fits the default budget


def test_auto_microbatches_divisor_selection():
    plan = type("P", (), {})()          # duck-typed plan stub
    plan.capture_bytes = 900.0
    plan.peak_stash_bytes = lambda: 100.0
    assert costmodel.auto_microbatches(plan, 8, mem_budget=1000) == 1
    assert costmodel.auto_microbatches(plan, 8, mem_budget=500) == 2
    assert costmodel.auto_microbatches(plan, 8, mem_budget=300) == 4
    assert costmodel.auto_microbatches(plan, 6, mem_budget=400) == 3
    assert costmodel.auto_microbatches(plan, 8, mem_budget=1) == 8


@pytest.mark.parametrize("strategy,sum_method", [("ghost", "backward"),
                                                 ("bk", "contrib")])
def test_ghost_and_bk_run_as_plans(strategy, sum_method):
    """ghost and bk execute plans: no stash, every sum from the weighted
    backward (ghost) or contracted (bk); the verifier checks the plan and
    microbatches="auto" resolves through it."""
    cfg = get_config("vgg16").replace(
        cnn_arch="toy", cnn_channels=(4, 8), cnn_kernel=3, img_size=16,
        n_classes=10)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    B = 4
    batch = {"img": jnp.asarray(rng.randn(B, 3, 16, 16), jnp.float32),
             "label": jnp.asarray(rng.randint(0, 10, (B,)), jnp.int32)}
    engine = PrivacyEngine(model.apply, params, batch, run_seed=0,
                           dp=DPConfig(l2_clip=1.0, noise_multiplier=1.0,
                                       strategy=strategy))
    plan = engine.plan()
    assert set(plan.sum_methods().values()) == {sum_method}
    assert not any(lp.stash for lp in plan.layers.values())
    assert plan.needs_backward == (strategy == "ghost")
    report = engine.verify()
    assert report.ok, report.summary()
    assert f"{len(plan.groups)} group realizations" in report.checked["plan"]

    norm = NormCfg(mem_budget=1 << 12)   # tiny: forces a split
    split = PrivacyEngine(model.apply, params, batch,
                          dp=DPConfig(l2_clip=1.0, strategy=strategy,
                                      microbatches="auto", norm=norm))
    m = split.microbatches()
    assert 1 < m == costmodel.auto_microbatches(split.plan(), B, 1 << 12)
    _, grad, _ = split.noisy_grad(params, batch)
    _, ref, _ = dp_gradient(model.apply, params, batch,
                            cfg=DPConfig(l2_clip=1.0, strategy="multi"))
    assert tree_maxdiff(grad, ref) < 1e-4


# ---------------------------------------------------------------------------
# Private steps


def test_private_step_updates_and_accounts(toy_model):
    from repro.optim import adamw_init
    apply_fn, params, batch = toy_model
    engine = PrivacyEngine(apply_fn, params, batch,
                           dp=DPConfig(l2_clip=0.1, noise_multiplier=0.7),
                           sampling_rate=4 / 1024, lr=1e-2)
    opt = adamw_init(params)
    p, opt, loss, aux = engine.private_step(params, opt, batch,
                                            jax.random.PRNGKey(0))
    assert np.isfinite(float(loss))
    assert tree_maxdiff(p, params) > 0.0
    assert engine.accountant.steps == 1
    assert np.isfinite(engine.epsilon())
    p, opt, loss, aux = engine.private_step(p, opt, batch,
                                            jax.random.PRNGKey(1))
    assert engine.accountant.steps == 2


def test_private_step_requires_key_when_noisy(toy_model):
    from repro.optim import adamw_init
    apply_fn, params, batch = toy_model
    engine = PrivacyEngine(apply_fn, params, batch,
                           dp=DPConfig(noise_multiplier=1.0))
    with pytest.raises(ValueError, match="requires a PRNG key"):
        engine.private_step(params, adamw_init(params), batch)


# ---------------------------------------------------------------------------
# DPConfig restructure


def test_dpconfig_legacy_kwargs_map_to_normcfg():
    with pytest.warns(DeprecationWarning):
        cfg = DPConfig(norm_method="gram", embed_norm="segsum",
                       conv_impl="bgc", conv_norm=None)
    assert cfg.norm == NormCfg(dense="gram", embed="segsum", conv="auto",
                               conv_impl="bgc")
    # read-only legacy views
    assert cfg.norm_method == "gram"
    assert cfg.embed_norm == "segsum"
    assert cfg.conv_impl == "bgc"
    assert cfg.conv_norm == "auto"   # the None sentinel is gone


def test_dpconfig_validates_microbatches():
    with pytest.raises(ValueError, match="microbatches"):
        DPConfig(microbatches=0)
    with pytest.raises(ValueError, match="microbatches"):
        DPConfig(microbatches="many")
    assert DPConfig(microbatches="auto").microbatches == "auto"


def test_dpconfig_is_hashable_and_frozen():
    cfg = DPConfig(overrides={"conv1": "ghost"})
    hash(cfg)
    assert cfg.overrides == (("conv1", "ghost"),)
    with pytest.raises(dataclasses.FrozenInstanceError):
        cfg.l2_clip = 2.0


def test_per_layer_overrides_respected(toy_model):
    apply_fn, params, batch = toy_model
    dp = DPConfig(l2_clip=0.05, overrides={"conv1": "ghost"})
    engine = PrivacyEngine(apply_fn, params, batch, dp=dp)
    plan = engine.plan()
    assert plan.layers["conv1"].norm_method == "ghost"   # auto picks pe
    _, gsum, _ = clipped_grad_sum(apply_fn, params, batch, l2_clip=0.05,
                                  strategy="naive")
    B = batch["label"].shape[0]
    ref = jax.tree.map(lambda g: g / B, gsum)
    _, grad, _ = engine.noisy_grad(params, batch)
    assert tree_maxdiff(grad, ref) < 1e-4


def test_override_glob_skips_non_overridable_kinds(toy_model):
    """A block-level glob sweeps up scale/local_vjp taps; those must be
    ignored (they have no norm vocabulary), not rejected — and the
    override still lands on the block's dense layers."""
    apply_fn, params, batch = toy_model
    engine = PrivacyEngine(apply_fn, params, batch,
                           dp=DPConfig(l2_clip=0.05,
                                       overrides={"blocks/*": "gram"}))
    plan = engine.plan()   # blocks/nrm is a scale tap — must not raise
    assert plan.layers["blocks/fc"].norm_method == "gram"
    assert plan.layers["blocks/nrm"].norm_method == "pe"   # untouched
    _, gsum, _ = clipped_grad_sum(apply_fn, params, batch, l2_clip=0.05,
                                  strategy="naive")
    B = batch["label"].shape[0]
    ref = jax.tree.map(lambda g: g / B, gsum)
    _, grad, _ = engine.noisy_grad(params, batch)
    assert tree_maxdiff(grad, ref) < 1e-4


def test_override_first_match_wins_in_given_order():
    """Dict insertion order is the priority order: a specific pattern
    listed before a broad glob must win even when sorting would reorder
    them."""
    ov = costmodel.normalize_overrides(
        {"blocks/attn": "gram", "blocks/*": "stream"})
    assert ov == (("blocks/attn", "gram"), ("blocks/*", "stream"))
    assert costmodel._override_for("blocks/attn", "dense", ov) == "gram"
    assert costmodel._override_for("blocks/mlp", "dense", ov) == "stream"


def test_override_invalid_method_raises(toy_model):
    apply_fn, params, batch = toy_model
    engine = PrivacyEngine(apply_fn, params, batch,
                           dp=DPConfig(overrides={"conv1": "stream"}))
    with pytest.raises(ValueError, match="invalid for conv"):
        engine.plan()


def test_engine_explain_mentions_every_layer(toy_model):
    apply_fn, params, batch = toy_model
    engine = PrivacyEngine(apply_fn, params, batch)
    text = engine.explain()
    for name in engine.plan().layers:
        assert name in text
    assert "1 fwd + 1 bwd" in text
