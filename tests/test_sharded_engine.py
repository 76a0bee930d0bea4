"""Sharding-aware DP execution.

Two groups of tests:

* Mesh-aware *planning* (no devices needed — a mesh spec plans for a
  topology this host doesn't have): collective-bytes cost terms flip
  per-layer decisions, the mesh is folded into fingerprints and cache
  keys, and stale plans fail loudly with the offending field named.
* ``multidevice``-marked *execution* equivalence: on a forced 8-device
  host (``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — the CI
  multi-device lane), the sharded ``private_step`` must equal the
  single-device engine on the same batch, including the noise (one
  replicated draw, not per-shard).
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import tree_maxdiff
from repro.core import DPConfig, ExecPlan, PrivacyEngine, costmodel
from repro.launch.mesh import make_auto_mesh
from repro.optim import adamw_init

needs_8_devices = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _batch8(batch):
    return jax.tree.map(lambda a: jnp.concatenate([a, a], axis=0), batch)


# ---------------------------------------------------------------------------
# Mesh normalization + planning (device-free)


def test_mesh_axes_normalization():
    assert costmodel.mesh_axes(None) == ()
    assert costmodel.mesh_axes("data:8") == (("data", 8),)
    assert costmodel.mesh_axes("data:4, model:2") == (("data", 4),
                                                      ("model", 2))
    assert costmodel.mesh_axes({"data": 8}) == (("data", 8),)
    assert costmodel.mesh_axes((("pod", 2), ("data", 4))) == (("pod", 2),
                                                              ("data", 4))
    with pytest.raises(ValueError, match="bad mesh spec"):
        costmodel.mesh_axes("data=8")
    assert costmodel.mesh_data_size((("data", 8), ("model", 2))) == 8
    assert costmodel.mesh_data_size((("pod", 2), ("data", 4))) == 8


def test_mesh_flips_planner_decisions(toy_model):
    """The collective-bytes terms must actually change the plan: a stash
    whose per-example grads would cross the ring loses its free sum."""
    apply_fn, params, batch = toy_model
    p0 = costmodel.get_plan(apply_fn, params, batch)
    p8 = costmodel.get_plan(apply_fn, params, batch, mesh="data:8")
    d0 = {n: (lp.norm_method, p0.sum_methods()[n])
          for n, lp in p0.layers.items()}
    d8 = {n: (lp.norm_method, p8.sum_methods()[n])
          for n, lp in p8.layers.items()}
    assert d0 != d8, "mesh-aware costs changed no per-layer decision"
    assert p8.total_coll_bytes > 0
    assert p0.total_coll_bytes == 0
    assert p8.mesh == (("data", 8),)


def test_mesh_explain_has_collective_column(toy_model):
    apply_fn, params, batch = toy_model
    engine = PrivacyEngine(apply_fn, params, batch, mesh="data:8")
    text = engine.explain()
    assert "coll MB" in text
    assert "mesh=data=8" in text
    assert "mesh: data=8" in text
    # and the per-layer column is populated (grad sync is never free)
    plan = engine.plan()
    assert all(lp.coll_bytes > 0 for lp in plan.layers.values()
               if lp.param_bytes > 0)


def test_mesh_in_fingerprint_and_cache_key(toy_model):
    apply_fn, params, batch = toy_model
    fp0 = costmodel.plan_fingerprint(apply_fn, params, batch)
    fp8 = costmodel.plan_fingerprint(apply_fn, params, batch, mesh="data:8")
    fp8b = costmodel.plan_fingerprint(apply_fn, params, batch,
                                      mesh={"data": 8})
    assert fp0 != fp8
    assert fp8 == fp8b          # spec string and axes dict key identically
    p0 = costmodel.get_plan(apply_fn, params, batch)
    p8 = costmodel.get_plan(apply_fn, params, batch, mesh="data:8")
    assert p0.fingerprint == fp0 and p8.fingerprint == fp8


def test_mesh_survives_json_roundtrip(toy_model):
    apply_fn, params, batch = toy_model
    plan = costmodel.get_plan(apply_fn, params, batch, mesh="data:8")
    restored = ExecPlan.from_json(plan.to_json())
    assert restored == plan
    assert tuple(restored.mesh) == (("data", 8),)
    assert restored.batch_sig == plan.batch_sig
    assert restored.total_coll_bytes == plan.total_coll_bytes


# ---------------------------------------------------------------------------
# Stale-plan validation names the offending field


def test_stale_plan_mesh_mismatch_named(toy_model):
    apply_fn, params, batch = toy_model
    plan = costmodel.get_plan(apply_fn, params, batch, mesh="data:8")
    restored = ExecPlan.from_json(plan.to_json())
    with pytest.raises(ValueError,
                       match=r"mesh shape mismatch.*data=8.*data=4"):
        costmodel.check_plan_matches(restored, mesh="data:4")
    with pytest.raises(ValueError,
                       match=r"mesh shape mismatch.*data=8.*\(no mesh\)"):
        costmodel.check_plan_matches(restored, mesh=())


def test_stale_plan_batch_mismatch_named(toy_model):
    apply_fn, params, batch = toy_model
    plan = costmodel.get_plan(apply_fn, params, batch)
    bigger = _batch8(batch)
    with pytest.raises(ValueError, match=r"batch shape mismatch.*4, 3, 12"):
        costmodel.check_plan_matches(
            plan, batch_sig=costmodel._shape_sig(bigger))


def test_stale_plan_fingerprint_mismatch_named(toy_model):
    apply_fn, params, batch = toy_model
    plan = costmodel.get_plan(apply_fn, params, batch)
    with pytest.raises(ValueError,
                       match=rf"fingerprint mismatch.*{plan.fingerprint}"):
        costmodel.check_plan_matches(plan, fingerprint="deadbeefdeadbeef")


def test_engine_rejects_mesh_mismatched_plan_up_front(toy_model):
    """Injecting a deserialized plan built for another topology fails at
    engine construction, before any execution."""
    apply_fn, params, batch = toy_model
    plan = costmodel.get_plan(apply_fn, params, batch, mesh="data:8")
    restored = ExecPlan.from_json(plan.to_json())
    with pytest.raises(ValueError, match="mesh shape mismatch"):
        PrivacyEngine(apply_fn, params, batch, plan=restored)


def test_plan_store_cross_topology_load_fails_loudly(toy_model, tmp_path):
    """A plan store written on one topology, loaded on another: the
    planner refuses to silently re-plan over the stale layout."""
    apply_fn, params, batch = toy_model
    plan = costmodel.get_plan(apply_fn, params, batch, mesh="data:8")
    path = str(tmp_path / "plans.json")
    costmodel.save_plan_store(path, [plan])
    costmodel.clear_plan_cache()
    costmodel.clear_plan_store()
    try:
        costmodel.load_plan_store(path)
        with pytest.raises(ValueError, match="mesh shape mismatch"):
            costmodel.get_plan(apply_fn, params, batch, mesh="data:4")
    finally:
        costmodel.clear_plan_store()
        costmodel.clear_plan_cache()


def test_plan_store_ignores_unrelated_model_with_same_batch(toy_model,
                                                            tmp_path):
    """The cross-topology guard must key on *this* model's fingerprint:
    a stored plan for a different model (or knobs) that merely shares the
    batch shape must not block planning."""
    apply_fn, params, batch = toy_model
    # same model+batch but different planner knobs -> different fingerprint
    other = costmodel.get_plan(apply_fn, params, batch, mesh="data:8",
                               norm_method="gram")
    path = str(tmp_path / "plans.json")
    costmodel.save_plan_store(path, [other])
    costmodel.clear_plan_cache()
    costmodel.clear_plan_store()
    try:
        costmodel.load_plan_store(path)
        plan = costmodel.get_plan(apply_fn, params, batch)   # must not raise
        assert plan.mesh == ()
    finally:
        costmodel.clear_plan_store()
        costmodel.clear_plan_cache()


def test_shared_param_sync_charged_once():
    """Taps sharing one parameter (tied embedding + LM head) sync one
    gradient, not one each: the group's grad-sync bytes are split across
    members instead of double-counted."""
    from repro.configs import get_config
    from repro.models.registry import build_model

    cfg = get_config("llama3.2-1b").reduced()
    model = build_model(cfg)
    params = jax.eval_shape(lambda k: model.init(k)[0],
                            jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((8, 16), jnp.int32),
             "labels": jnp.zeros((8, 16), jnp.int32)}
    plan = costmodel.get_plan(model.apply, params, batch, mesh="data:8")
    tied = [g for g in plan.groups if len(g.members) > 1]
    assert tied, "reduced llama must have a tied embed/head group"
    g = tied[0]
    ring = 2.0 * 7 / 8
    pb = max(plan.layers[n].param_bytes for n in g.members)
    norm_parts = sum(
        (plan.layers[n].stash_bytes if plan.layers[n].stash
         else plan.layers[n].ex_per_dev * 8 * 4) * ring
        for n in g.members)
    got = sum(plan.layers[n].coll_bytes for n in g.members)
    assert got == pytest.approx(norm_parts + pb * ring)   # ONE table sync


def test_batch_sharding_requires_a_data_axis():
    """The executor and the cost model agree on the data-axis vocabulary;
    a model-parallel-only mesh is rejected up front, not with an obscure
    IndexError inside jit setup."""
    from repro.launch.sharding import batch_sharding
    mesh = make_auto_mesh((1,), ("model",))
    with pytest.raises(ValueError, match="no data-parallel axis"):
        batch_sharding({"x": jnp.zeros((4, 2))}, mesh)
    # a 'batch'-named axis counts as data parallelism, like the planner
    mesh_b = make_auto_mesh((1,), ("batch",))
    sh = batch_sharding({"x": jnp.zeros((4, 2))}, mesh_b)
    assert jax.tree.leaves(sh)[0].spec == jax.sharding.PartitionSpec("batch")


# ---------------------------------------------------------------------------
# Sharded execution equivalence (the multi-device CI lane)


@pytest.mark.multidevice
@needs_8_devices
def test_sharded_private_step_matches_single_device(toy_model):
    apply_fn, params, batch4 = toy_model
    batch = _batch8(batch4)
    mesh = make_auto_mesh((8,), ("data",))
    dp = DPConfig(l2_clip=0.1)
    e1 = PrivacyEngine(apply_fn, params, batch, dp=dp, lr=1e-2)
    e8 = PrivacyEngine(apply_fn, params, batch, dp=dp, lr=1e-2, mesh=mesh)
    p1, o1 = params, adamw_init(params)
    p8, o8 = params, adamw_init(params)
    for step in range(2):
        p1, o1, l1, _ = e1.private_step(p1, o1, batch)
        p8, o8, l8, _ = e8.private_step(p8, o8, batch)
        assert abs(float(l1) - float(l8)) < 1e-5
    assert tree_maxdiff(p1, p8) < 1e-6


@pytest.mark.multidevice
@needs_8_devices
def test_sharded_noise_is_replicated_not_per_shard(toy_model):
    """With a noise multiplier, the sharded step must add the *same* draw
    on every device (one replicated key), so it still equals the
    single-device noisy step bit-for-bit up to reduction order."""
    apply_fn, params, batch4 = toy_model
    batch = _batch8(batch4)
    mesh = make_auto_mesh((8,), ("data",))
    dp = DPConfig(l2_clip=0.1, noise_multiplier=1.3)
    key = jax.random.key_data(jax.random.PRNGKey(7))
    e1 = PrivacyEngine(apply_fn, params, batch, dp=dp, lr=1e-2)
    e8 = PrivacyEngine(apply_fn, params, batch, dp=dp, lr=1e-2, mesh=mesh)
    p1, _, _, _ = e1.private_step(params, adamw_init(params), batch, key)
    p8, _, _, _ = e8.private_step(params, adamw_init(params), batch, key)
    assert tree_maxdiff(p1, p8) < 1e-6


@pytest.mark.multidevice
@needs_8_devices
def test_engine_rejects_indivisible_batch_up_front(toy_model):
    """A live mesh whose data degree does not divide the batch fails at
    engine construction with a named error, not inside XLA."""
    apply_fn, params, batch4 = toy_model   # B=4 on an 8-way data mesh
    mesh = make_auto_mesh((8,), ("data",))
    with pytest.raises(ValueError, match="not divisible.*degree 8"):
        PrivacyEngine(apply_fn, params, batch4,
                      dp=DPConfig(l2_clip=0.1), mesh=mesh)


@pytest.mark.multidevice
@needs_8_devices
def test_sharded_step_places_batch_on_data_axis(toy_model):
    apply_fn, params, batch4 = toy_model
    batch = _batch8(batch4)
    mesh = make_auto_mesh((8,), ("data",))
    engine = PrivacyEngine(apply_fn, params, batch,
                           dp=DPConfig(l2_clip=0.1), mesh=mesh)
    p, _, _, _ = engine.private_step(params, adamw_init(params), batch)
    # outputs are replicated; the jitted step carries explicit shardings
    for leaf in jax.tree.leaves(p):
        assert leaf.sharding.is_fully_replicated
    # the plan the engine executed is the mesh-keyed one
    assert tuple(engine.plan().mesh) == (("data", 8),)


@pytest.mark.multidevice
@needs_8_devices
def test_live_mesh_and_spec_plan_identically(toy_model):
    """A live Mesh and its spec string produce the same fingerprint, so
    plans serialized on a devices-attached host load on a planning-only
    host and vice versa."""
    apply_fn, params, batch4 = toy_model
    batch = _batch8(batch4)
    mesh = make_auto_mesh((8,), ("data",))
    fp_live = costmodel.plan_fingerprint(apply_fn, params, batch, mesh=mesh)
    fp_spec = costmodel.plan_fingerprint(apply_fn, params, batch,
                                         mesh="data:8")
    assert fp_live == fp_spec


# ---------------------------------------------------------------------------
# 2D (data x model) meshes: per-axis pricing + tensor-sharded execution


def test_mesh_axes_drop_unit_axes():
    """Size-1 axes execute identically to their absence; they must not
    make a stored plan fail safe spuriously."""
    assert costmodel.mesh_axes("data:8,model:1") == (("data", 8),)
    assert costmodel.mesh_axes((("data", 8), ("model", 1))) == (("data", 8),)
    assert costmodel.mesh_axes({"data": 8, "model": 1}) == (("data", 8),)
    assert costmodel.mesh_axes("data:1") == ()


def test_check_plan_matches_ignores_unit_axes(toy_model):
    apply_fn, params, batch = toy_model
    plan = costmodel.get_plan(apply_fn, params, batch, mesh="data:8")
    # identical topology spelled with a trivial model axis: no error
    costmodel.check_plan_matches(plan, mesh="data:8,model:1")
    with pytest.raises(ValueError, match="mesh shape mismatch"):
        costmodel.check_plan_matches(plan, mesh="data:8,model:2")


def test_mesh_model_axis_helpers():
    axes = (("data", 4), ("model", 2))
    assert costmodel.mesh_data_axes(axes) == (("data", 4),)
    assert costmodel.mesh_model_axes(axes) == (("model", 2),)
    assert costmodel.mesh_model_size(axes) == 2
    assert costmodel.mesh_model_axes((("pod", 2), ("data", 4))) == ()


def test_axisless_pricing_warns_on_multi_axis_calibration():
    import warnings as _w
    from repro import calibrate
    c = calibrate.injected(
        mesh="data:4,model:2", flops_per_second=1e12,
        collective_bytes_per_second={"data": 16e9, "model": 2e9})
    with pytest.warns(calibrate.CalibrationAxisFallbackWarning):
        v = c.collective_flops_per_byte()
    assert v == pytest.approx(1e12 / 2e9)        # slowest axis
    assert c.collective_flops_per_byte("data") == pytest.approx(1e12 / 16e9)
    # legacy single-axis calibrations keep the silent fallback
    c1 = calibrate.injected(mesh="data:8", flops_per_second=1e12,
                            collective_bytes_per_second=16e9)
    with _w.catch_warnings():
        _w.simplefilter("error")
        assert c1.collective_flops_per_byte() == pytest.approx(1e12 / 16e9)


def test_2d_per_axis_collective_pricing_hand_computed():
    """Acceptance: with data/model bandwidths 8x apart, the planned
    collective cost of tensor-sharded llama32_1b layers is the per-axis
    sum — scalar norms priced on the data ring, partial-Gram psums on
    the model ring — never the slowest-axis scalar, and planning never
    takes the axis-less fallback."""
    import dataclasses as _dc
    import warnings as _w
    from repro import calibrate
    from repro.configs import get_config
    from repro.models.registry import build_model

    cfg = get_config("llama3.2-1b").reduced()
    model = build_model(cfg)
    params = jax.eval_shape(lambda k: model.init(k)[0],
                            jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((8, 16), jnp.int32),
             "labels": jnp.zeros((8, 16), jnp.int32)}
    calib = calibrate.injected(
        mesh="data:4,model:2", flops_per_second=1e12,
        collective_bytes_per_second={"data": 16e9, "model": 2e9})
    with _w.catch_warnings():
        _w.simplefilter("error", calibrate.CalibrationAxisFallbackWarning)
        plan = costmodel.get_plan(model.apply, params, batch,
                                  mesh="data:4,model:2", calibration=calib)
    sharded = {n: lp for n, lp in plan.layers.items()
               if lp.model_shards > 1}
    assert sharded, "no tensor-sharded layer planned for llama32_1b"
    d = 4                 # data-parallel degree: B = ex_per_dev * d
    ring_d = 2.0 * (4 - 1) / 4              # data:4 ring factor
    ring_m = 2.0 * (2 - 1) / 2              # model:2 ring factor
    by_group = {m: g for g in plan.groups for m in g.members}
    for name, lp in sharded.items():
        g = by_group[name]
        group_pb = max(plan.layers[m].param_bytes for m in g.members)
        sync = group_pb * (2.0 if g.sum_method == "backward" else 1.0) \
            / len(g.members)
        norm_bytes = (lp.stash_bytes if lp.stash
                      else lp.ex_per_dev * d * 4)
        want = {"data": (norm_bytes + sync) * ring_d,
                "model": lp.ex_per_dev * d * 4 * ring_m}
        assert dict(lp.coll_bytes_by_axis) == pytest.approx(want), name
        assert lp.coll_bytes == pytest.approx(sum(want.values())), name
    # the predicted cost prices each axis at its own bandwidth
    cc = costmodel.resolve_cost_constants(calib, plan.mesh)
    assert cc.coll_price("data") == pytest.approx(1e12 / 16e9)
    assert cc.coll_price("model") == pytest.approx(1e12 / 2e9)
    no_coll = _dc.replace(plan, total_coll_bytes=0.0,
                          total_coll_bytes_by_axis=())
    coll_flops = costmodel.predicted_step_flops(plan, cc) \
        - costmodel.predicted_step_flops(no_coll, cc)
    want_flops = sum(cc.coll_price(a) * b
                     for a, b in plan.total_coll_bytes_by_axis)
    assert coll_flops == pytest.approx(want_flops)
    # slowest-axis pricing (the old bug) would overcharge the data traffic
    slowest_flops = cc.collective_flops_per_byte * plan.total_coll_bytes
    assert want_flops < slowest_flops


def test_2d_plan_payload_roundtrips_per_axis_bytes(toy_model):
    apply_fn, params, batch = toy_model
    plan = costmodel.get_plan(apply_fn, params, batch,
                              mesh="data:4,model:2")
    assert plan.total_coll_bytes_by_axis
    assert dict(plan.total_coll_bytes_by_axis)["data"] > 0
    restored = ExecPlan.from_json(plan.to_json())
    assert restored == plan
    assert restored.total_coll_bytes_by_axis == plan.total_coll_bytes_by_axis
    for n, lp in plan.layers.items():
        assert restored.layers[n].coll_bytes_by_axis == lp.coll_bytes_by_axis
        assert restored.layers[n].model_shards == lp.model_shards
    # explain() surfaces the per-axis breakdown
    assert "per axis:" in plan.explain()


def test_planning_only_2d_mesh_never_auto_measures(toy_model, monkeypatch):
    """A mesh *spec* plans for a topology this host doesn't have — it
    must not try to measure it; 'analytic' is the explicit opt-out on a
    live mesh too."""
    from repro import calibrate

    def boom(*a, **k):
        raise AssertionError("measure() ran for a planning-only engine")

    monkeypatch.setattr(calibrate, "measure", boom)
    apply_fn, params, batch = toy_model
    eng = PrivacyEngine(apply_fn, params, batch, mesh="data:4,model:2")
    assert eng.calibration is None
    eng2 = PrivacyEngine(apply_fn, params, batch, mesh="data:4,model:2",
                         calibration="analytic")
    assert eng2.calibration is None


@pytest.mark.multidevice
@needs_8_devices
def test_2d_engine_auto_calibrates_by_default(toy_model, monkeypatch):
    """PR-8 follow-up: a fresh engine on a live 2D mesh must not price
    the model axis from ANALYTIC_FALLBACK — absent a registered
    calibration it measures once per (hardware, mesh) per process."""
    from repro import calibrate

    apply_fn, params, batch4 = toy_model
    batch = _batch8(batch4)
    mesh = make_auto_mesh((4, 2), ("data", "model"))
    calls = []
    fake = calibrate.injected(
        mesh="data:4,model:2",
        collective_bytes_per_second={"data": 8e9, "model": 2e9})

    def fake_measure(mesh=None, quick=True):
        calls.append(costmodel.mesh_axes(mesh))
        return fake

    monkeypatch.setattr(calibrate, "measure", fake_measure)
    calibrate.clear_registry()
    try:
        costmodel.clear_plan_cache()
        eng = PrivacyEngine(apply_fn, params, batch, mesh=mesh)
        assert eng.calibration is fake
        assert calls == [(("data", 4), ("model", 2))]
        # second engine: registry hit, no re-measure
        eng2 = PrivacyEngine(apply_fn, params, batch, mesh=mesh)
        assert eng2.calibration is fake and len(calls) == 1
        # explicit opt-out
        eng3 = PrivacyEngine(apply_fn, params, batch, mesh=mesh,
                             calibration="analytic")
        assert eng3.calibration is None
    finally:
        calibrate.clear_registry()
        costmodel.clear_plan_cache()


@pytest.mark.multidevice
@needs_8_devices
@pytest.mark.parametrize("arch", ("alexnet", "llama3.2-1b"))
def test_sharded_2d_private_step_matches_single_device(arch):
    """Acceptance: private_step on data:4,model:2 with tensor-sharded
    params equals the single-device reference — noise included (the one
    replicated key; partitionable threefry makes the sharded draw
    value-identical) — for a CNN and llama32_1b."""
    from repro.configs import get_config
    from repro.launch.train import make_batch_fn
    from repro.models.registry import build_model
    from repro.optim import sgdm_init

    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    batch_fn = make_batch_fn(cfg, 8, 32)
    params, axes = model.init(jax.random.PRNGKey(0))
    dp = DPConfig(l2_clip=1.0, noise_multiplier=0.8)
    costmodel.clear_plan_cache()
    e1 = PrivacyEngine(model.apply, params, batch_fn(0), dp=dp,
                       optimizer="sgdm", lr=1e-2, run_seed=7,
                       sampling_rate=0.01, calibration="analytic")
    mesh = make_auto_mesh((4, 2), ("data", "model"))
    costmodel.clear_plan_cache()
    e2 = PrivacyEngine(model.apply, params, batch_fn(0), dp=dp,
                       optimizer="sgdm", lr=1e-2, mesh=mesh,
                       param_axes=axes, run_seed=7, sampling_rate=0.01,
                       calibration="analytic")
    p1, o1 = params, sgdm_init(params)
    p2, o2 = params, sgdm_init(params)
    for step in range(2):
        p1, o1, l1, _ = e1.private_step(p1, o1, batch_fn(step), step=step)
        p2, o2, l2, _ = e2.private_step(p2, o2, batch_fn(step), step=step)
        assert abs(float(l1) - float(l2)) < 1e-5
    assert tree_maxdiff(p1, p2) < 1e-6
    # identical accountant ledgers
    assert e1.accountant.steps == e2.accountant.steps
    assert e1.epsilon(1e-5) == e2.epsilon(1e-5)
    # params really partitioned over the model axis
    assert any(not leaf.sharding.is_fully_replicated
               for leaf in jax.tree.leaves(p2))
    # all analysis lanes pass on the 2D mesh
    report = e2.verify()
    assert not report.errors, report.errors
    assert "partitioned over model" in report.checked["sharding"]


@pytest.mark.multidevice
@needs_8_devices
def test_sharded_2d_custom_optimizer_state_inherits_param_layout():
    """Regression: a custom optimizer callable's state used to stay
    replicated on a tensor-sharded mesh (the sharding table only knew
    adamw/sgdm by name), silently forfeiting the ZeRO-style moment
    partitioning.  The engine now derives the layout from the recorded
    state pytree — moment-like leaves (shaped like a param whose layout
    is unambiguous) inherit the param sharding, scalars stay replicated
    — and the step still matches the single-device reference."""
    from repro.configs import get_config
    from repro.launch.sharding import param_sharding
    from repro.launch.train import make_batch_fn
    from repro.models.registry import build_model

    def momentum(grad, opt, params, *, lr, weight_decay):
        mom = jax.tree.map(lambda m, g: 0.9 * m + g, opt["mom"], grad)
        new = jax.tree.map(lambda p, m: p - lr * m, params, mom)
        return new, {"mom": mom, "step": opt["step"] + 1}

    cfg = get_config("llama3.2-1b").reduced()
    model = build_model(cfg)
    batch_fn = make_batch_fn(cfg, 8, 32)
    params, axes = model.init(jax.random.PRNGKey(0))
    dp = DPConfig(l2_clip=1.0, noise_multiplier=0.8)

    def opt0():
        return {"mom": jax.tree.map(jnp.zeros_like, params),
                "step": jnp.zeros((), jnp.int32)}

    costmodel.clear_plan_cache()
    e1 = PrivacyEngine(model.apply, params, batch_fn(0), dp=dp,
                       optimizer=momentum, lr=1e-2, run_seed=7,
                       calibration="analytic")
    mesh = make_auto_mesh((4, 2), ("data", "model"))
    costmodel.clear_plan_cache()
    e2 = PrivacyEngine(model.apply, params, batch_fn(0), dp=dp,
                       optimizer=momentum, lr=1e-2, mesh=mesh,
                       param_axes=axes, run_seed=7, calibration="analytic")
    p1, o1 = params, opt0()
    p2, o2 = params, opt0()
    for step in range(2):
        p1, o1, l1, _ = e1.private_step(p1, o1, batch_fn(step), step=step)
        p2, o2, l2, _ = e2.private_step(p2, o2, batch_fn(step), step=step)
        assert abs(float(l1) - float(l2)) < 1e-5
    assert tree_maxdiff(p1, p2) < 1e-6
    assert tree_maxdiff(o1["mom"], o2["mom"]) < 1e-6
    # the regression: moment leaves are actually partitioned now
    assert any(not leaf.sharding.is_fully_replicated
               for leaf in jax.tree.leaves(o2["mom"])), \
        "custom optimizer moments stayed replicated"
    # ... and mirror the param layout wherever it is unambiguous
    in_sh, _ = e2._step_shardings()
    psh = param_sharding(axes, mesh, shapes_tree=e2._params_spec)
    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    for got, want in zip(jax.tree.leaves(in_sh[1]["mom"]),
                         jax.tree.leaves(psh)):
        assert got == want or got == repl
    assert in_sh[1]["step"] == repl
