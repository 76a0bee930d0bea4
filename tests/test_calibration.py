"""Measured-cost calibration: the differential proof that the planner
trusts the hardware, not constants.

Five groups:

* **Round-trip** — a :class:`Calibration` survives the JSON file format
  and the plan store bit-identically (same digest, same payload), and a
  loading process resolves a calibrated plan with no model probe.
* **Plan flip** — a synthetic calibration (``injected``) with a fast
  measured wire flips the ``alexnet@data:8`` plan the analytic constants
  refuse (conv0's stash comes back), while a slow measured wire keeps it
  off and :func:`costmodel.planner_verdict` proves unsharded right —
  the planner either fixes the plan or proves the fixed-constant
  "regression" was priced fiction.
* **Mispredict loop** — feeding a step time that diverges from the
  calibrated prediction beyond the threshold triggers *exactly one*
  re-plan, and the re-planned run's params, optimizer state, and
  accountant ledger are bit-identical to an undisturbed run (the
  test_resume_equivalence.py differential pattern): re-planning is a
  performance decision, never a semantics change.
* **Fail-safe** — absent or corrupt calibration degrades to the analytic
  constants with a named :class:`CalibrationFallbackWarning`, never a
  crash; stale constants fail safe because the calibration digest is
  folded into plan fingerprints and named by ``check_plan_matches``.
* **Mutation harness** — the test_dpcheck.py pattern: each test tampers
  a persisted blob (wrong hardware signature, wrong mesh, truncated
  payload, NaN bandwidth, missing field, foreign format) and asserts the
  *named* rejection.  A loader that accepts any of these plans against
  garbage bandwidths.
"""
import json
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import calibrate
from repro.core import DPConfig, PrivacyAccountant, PrivacyEngine, costmodel
from repro.kernels import ops as kops
from repro.launch.mesh import make_auto_mesh
from repro.optim import adamw_init
from repro.runtime.monitor import StepMonitor

needs_8_devices = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

RUN_SEED = 7
NOISE = 0.9
STEPS = 5


@pytest.fixture(autouse=True)
def _fresh_calibration_state():
    # Registered calibrations are process-global and folded into plan
    # fingerprints; leakage across tests would silently re-price every
    # subsequent plan.
    calibrate.clear_registry()
    costmodel.clear_plan_cache()
    costmodel.clear_plan_store()
    yield
    calibrate.clear_registry()
    costmodel.clear_plan_cache()
    costmodel.clear_plan_store()


def _bitwise_equal(a, b):
    return all(bool(jnp.all(x == y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _batch_fn(batch):
    def fn(step):
        return jax.tree.map(lambda a: jnp.roll(a, step, axis=0), batch)
    return fn


def _engine(toy, *, calibration=None, mesh=None, batch=None,
            threshold=0.5, monitor=None):
    apply_fn, params, batch0 = toy
    dp = DPConfig(l2_clip=0.1, noise_multiplier=NOISE)
    acct = PrivacyAccountant(sampling_rate=1 / 128, noise_multiplier=NOISE)
    return PrivacyEngine(apply_fn, params,
                         batch0 if batch is None else batch, dp=dp,
                         lr=1e-2, accountant=acct, run_seed=RUN_SEED,
                         mesh=mesh, calibration=calibration,
                         mispredict_threshold=threshold, monitor=monitor)


def _drive(engine, params0, batch_fn, steps=STEPS, feed_seconds=None):
    """Step to ``steps`` on the deterministic noise stream, optionally
    feeding a fixed measured step time into the mispredict loop."""
    params, opt = params0, adamw_init(params0)
    engine.accountant.reset()
    for step in range(steps):
        params, opt, _, _ = engine.private_step(params, opt,
                                                batch_fn(step), step=step)
        if feed_seconds is not None:
            engine.observe_step_time(feed_seconds, step=step)
    return params, opt


# ---------------------------------------------------------------------------
# Round-trip: file format and plan store, bit-identical.


def test_calibration_file_round_trip_bit_identical(tmp_path):
    calib = calibrate.injected(
        mesh="data:2", collective_bytes_per_second=3.5e9,
        kernels={"pe_conv_grad": {"vmem_budget": 1 << 20, "th": 16}})
    path = str(tmp_path / "c.json")
    calibrate.save_calibration(path, calib)
    got = calibrate.load_calibration(path, expect_mesh="data:2")
    assert got == calib                      # every field, bit-identical
    assert got.digest() == calib.digest()
    # digest is content identity: it ignores the measurement timestamp
    import dataclasses
    assert dataclasses.replace(calib, measured_at=0.0).digest() \
        == calib.digest()


def test_plan_store_round_trips_calibration(toy_model, tmp_path):
    apply_fn, params, batch = toy_model
    calib = calibrate.injected()
    eng = _engine(toy_model, calibration=calib)
    plan = eng.plan()
    assert plan.calibration == calib.digest()
    path = str(tmp_path / "plans.json")
    eng.save_plan(path)

    # a fresh process: nothing registered, nothing cached
    calibrate.clear_registry()
    costmodel.clear_plan_cache()
    costmodel.clear_plan_store()
    assert costmodel.load_plan_store(path) >= 1
    # the persisted calibration came back bit-identically and registered
    assert calibrate.lookup(()) == calib
    # a fresh engine resolves the stored plan by fingerprint — same plan,
    # bit-identical payload, no re-probe needed
    eng2 = _engine(toy_model)
    assert eng2.calibration == calib
    assert eng2.plan().to_payload() == plan.to_payload()


def test_store_written_under_calibration_misses_analytic_process(
        toy_model, tmp_path):
    """The fail-safe direction: a store written under measured constants
    does not resolve for a process planning under *different* constants —
    the digest is folded into the fingerprint, so stale constants miss
    (and re-plan) instead of silently executing a stale costing."""
    apply_fn, params, batch = toy_model
    calib = calibrate.injected(flops_per_second=2e12)
    fp_cal = costmodel.plan_fingerprint(apply_fn, params, batch,
                                        calibration=calib)
    fp_analytic = costmodel.plan_fingerprint(apply_fn, params, batch)
    other = calibrate.injected(flops_per_second=3e12)
    fp_other = costmodel.plan_fingerprint(apply_fn, params, batch,
                                          calibration=other)
    assert len({fp_cal, fp_analytic, fp_other}) == 3


# ---------------------------------------------------------------------------
# The plan flip: injected measurements change what the planner builds.


@pytest.fixture(scope="module")
def alexnet():
    from repro.configs import get_config
    from repro.models.registry import build_model
    cfg = get_config("alexnet").replace(img_size=64, n_classes=10)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    batch = {"img": jnp.array(rng.randn(8, 3, 64, 64), jnp.float32),
             "label": jnp.array(rng.randint(0, 10, (8,)))}
    return model, params, batch


def test_injected_calibration_flips_alexnet_data8_plan(alexnet):
    """The BENCH_strategies.json ``alexnet@data:8`` lane, reproduced with
    synthetic measurements: under the analytic wire constant the mesh
    plan withholds conv0's stash; a measured *fast* wire flips it back on
    (plan fixed), a measured *slow* wire keeps it off and the calibrated
    verdict proves the unsharded plan right — either way the apparent
    auto-vs-fixed regression disappears."""
    model, params, batch = alexnet
    mesh = "data:8"
    p_base = costmodel.get_plan(model.apply, params, batch)
    p_analytic = costmodel.get_plan(model.apply, params, batch, mesh=mesh)
    fast = calibrate.injected(mesh=mesh, collective_bytes_per_second=1e15)
    slow = calibrate.injected(mesh=mesh, collective_bytes_per_second=1e7)
    p_fast = costmodel.get_plan(model.apply, params, batch, mesh=mesh,
                                calibration=fast)
    p_slow = costmodel.get_plan(model.apply, params, batch, mesh=mesh,
                                calibration=slow)
    assert p_analytic.sum_methods()["conv0"] == "contrib"
    assert p_fast.sum_methods()["conv0"] == "stash"      # the flip
    assert p_slow.sum_methods()["conv0"] == "contrib"
    assert costmodel.planner_verdict(p_fast, p_base, fast) == "sharded"
    assert costmodel.planner_verdict(p_slow, p_base, slow) == "unsharded"
    # three different costings, three distinct fingerprints — they
    # coexist in the cache/store instead of shadowing each other
    assert len({p_analytic.fingerprint, p_fast.fingerprint,
                p_slow.fingerprint}) == 3
    assert p_fast.calibration == fast.digest()
    assert p_slow.calibration == slow.digest()
    assert p_analytic.calibration == ""


# ---------------------------------------------------------------------------
# The mispredict loop: exactly one re-plan, bitwise-equal training.


def test_mispredict_triggers_exactly_one_replan_bitwise_equal(toy_model):
    params0, batch_fn = toy_model[1], _batch_fn(toy_model[2])
    calib = calibrate.injected()
    mon = StepMonitor()

    ref = _engine(toy_model, calibration=calib)
    ref_p, ref_o = _drive(ref, params0, batch_fn)

    eng = _engine(toy_model, calibration=calib, monitor=mon)
    bad = eng.predicted_step_seconds() * 10        # constant 10x miss
    got_p, got_o = _drive(eng, params0, batch_fn, feed_seconds=bad)

    # exactly one re-plan: the retimed calibration *closes* the gap, so
    # the same divergence does not re-fire every step
    assert len(eng.replan_events) == 1
    ev = eng.replan_events[0]
    assert ev.ratio == pytest.approx(10.0, rel=1e-6)
    assert ev.old_calibration == calib.digest()
    assert ev.new_calibration != calib.digest()
    # the constants changed, so the fingerprint changed (fail-safe key)…
    assert ev.new_fingerprint != ev.old_fingerprint
    # …but the realization did not: re-planning here is pure re-pricing
    assert ev.plan_changed is False
    # after the re-plan the prediction matches what was measured
    assert eng.predicted_step_seconds() == pytest.approx(bad, rel=1e-6)
    # the retimed calibration is registered for the next process/engine
    assert calibrate.lookup(()) is not None
    assert calibrate.lookup(()).source == "replan"

    # the differential core: params, optimizer state, and ledger are
    # bit-identical to the run that never re-planned
    assert _bitwise_equal(ref_p, got_p)
    assert _bitwise_equal(ref_o, got_o)
    assert eng.accountant.state_dict() == ref.accountant.state_dict()
    assert eng.accountant.steps == STEPS

    # the monitor saw it and reset its EMA baseline
    assert mon.replans == [(ev.step, pytest.approx(ev.ratio))]
    state = mon.state_dict()
    assert StepMonitor.from_state(state).replans == mon.replans


def test_accurate_prediction_never_replans(toy_model):
    params0, batch_fn = toy_model[1], _batch_fn(toy_model[2])
    eng = _engine(toy_model, calibration=calibrate.injected())
    _drive(eng, params0, batch_fn,
           feed_seconds=eng.predicted_step_seconds() * 1.2)   # within ±50%
    assert eng.replan_events == []


def test_observe_is_inert_without_calibration(toy_model):
    params0, batch_fn = toy_model[1], _batch_fn(toy_model[2])
    eng = _engine(toy_model)                       # analytic constants
    assert eng.calibration is None
    _drive(eng, params0, batch_fn, feed_seconds=1e3)
    assert eng.replan_events == []
    eng2 = _engine(toy_model, calibration=calibrate.injected(),
                   threshold=None)                 # loop disabled
    _drive(eng2, params0, batch_fn, feed_seconds=1e3)
    assert eng2.replan_events == []


def test_single_observation_cannot_replan(toy_model):
    """One compile-tainted step must not fire the loop."""
    eng = _engine(toy_model, calibration=calibrate.injected())
    assert eng.observe_step_time(eng.predicted_step_seconds() * 100,
                                 step=0) is None
    assert eng.replan_events == []


def test_explain_surfaces_calibration_and_replans(toy_model):
    # the analytic engine names its constants (nothing registered yet)
    assert "analytic fallback" in _engine(toy_model).explain()
    calib = calibrate.injected()
    eng = _engine(toy_model, calibration=calib)
    text = eng.explain()
    assert f"calibration: {calib.digest()}" in text
    assert "source=injected" in text
    assert "mispredict threshold" in text
    bad = eng.predicted_step_seconds() * 10
    eng.observe_step_time(bad, step=0)
    eng.observe_step_time(bad, step=1)
    assert "re-plan @ step 1" in eng.explain()


# ---------------------------------------------------------------------------
# Fail-safe: absent/corrupt blobs degrade with a named warning.


def test_absent_calibration_warns_and_falls_back(tmp_path):
    with pytest.warns(calibrate.CalibrationFallbackWarning,
                      match="FileNotFoundError"):
        assert calibrate.load_or_fallback(
            str(tmp_path / "nope.json")) is None


def test_corrupt_calibration_warns_and_engine_plans_analytic(
        toy_model, tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text('{"format": 1, "hardware"')    # truncated mid-key
    with pytest.warns(calibrate.CalibrationFallbackWarning,
                      match="CalibrationFormatError"):
        eng = _engine(toy_model, calibration=str(bad))
    assert eng.calibration is None
    assert eng.plan().calibration == ""           # analytic constants
    # and the engine still trains
    params0, batch_fn = toy_model[1], _batch_fn(toy_model[2])
    _drive(eng, params0, batch_fn, steps=1)


def test_check_plan_matches_names_calibration_field(toy_model):
    apply_fn, params, batch = toy_model
    plan = costmodel.get_plan(apply_fn, params, batch)   # analytic
    calib = calibrate.injected()
    with pytest.raises(ValueError, match="calibration mismatch"):
        costmodel.check_plan_matches(plan, calibration=calib)
    costmodel.check_plan_matches(plan, calibration="")   # clean
    cal_plan = costmodel.get_plan(apply_fn, params, batch,
                                  calibration=calib)
    costmodel.check_plan_matches(cal_plan, calibration=calib)
    with pytest.raises(ValueError, match="calibration mismatch"):
        costmodel.check_plan_matches(cal_plan, calibration="")


def test_injecting_plan_from_other_constants_fails_at_init(toy_model):
    """An ExecPlan priced under measured constants injected into an
    analytic engine is stale the moment it is handed over — named at
    construction, not at step time."""
    apply_fn, params, batch = toy_model
    calib = calibrate.injected()
    plan = costmodel.get_plan(apply_fn, params, batch, calibration=calib)
    with pytest.raises(ValueError, match="calibration mismatch"):
        PrivacyEngine(apply_fn, params, batch,
                      dp=DPConfig(l2_clip=0.1), plan=plan)


# ---------------------------------------------------------------------------
# Mutation harness: every tampered blob is rejected by name.


def _valid_payload(**kw):
    return calibrate.injected(**kw).to_payload()


def test_mutation_wrong_hardware_signature(tmp_path):
    calib = calibrate.injected(hardware="tpu:TPU v9:4096")
    path = str(tmp_path / "c.json")
    calibrate.save_calibration(path, calib)
    with pytest.raises(calibrate.CalibrationHardwareMismatch,
                       match="tpu:TPU v9:4096"):
        calibrate.load_calibration(path)
    # …and only the hardware check was waived, nothing else
    assert calibrate.load_calibration(path, expect_hardware=False) == calib


def test_mutation_wrong_mesh(tmp_path):
    calib = calibrate.injected(mesh="data:4",
                               collective_bytes_per_second=1e9)
    path = str(tmp_path / "c.json")
    calibrate.save_calibration(path, calib)
    with pytest.raises(calibrate.CalibrationMeshMismatch, match="data=8"):
        calibrate.load_calibration(path, expect_mesh="data:8")


def test_mutation_truncated_payload(tmp_path):
    calib = calibrate.injected()
    blob = calib.to_json()
    path = tmp_path / "c.json"
    path.write_text(blob[: len(blob) // 2])
    with pytest.raises(calibrate.CalibrationFormatError,
                       match="not valid JSON"):
        calibrate.load_calibration(str(path))


def test_mutation_nan_bandwidth(tmp_path):
    p = _valid_payload(mesh="data:2", collective_bytes_per_second=1e9)
    p["collective_bytes_per_second"]["data"] = float("nan")
    path = tmp_path / "c.json"
    path.write_text(json.dumps(p))
    with pytest.raises(calibrate.CalibrationValueError,
                       match="finite positive"):
        calibrate.load_calibration(str(path))


@pytest.mark.parametrize("value", [0.0, -1.0, float("inf")])
def test_mutation_nonpositive_flop_rate(tmp_path, value):
    p = _valid_payload()
    p["flops_per_second"] = value
    path = tmp_path / "c.json"
    path.write_text(json.dumps(p))
    with pytest.raises(calibrate.CalibrationValueError,
                       match="flops_per_second"):
        calibrate.load_calibration(str(path))


def test_mutation_missing_field(tmp_path):
    p = _valid_payload()
    del p["hbm_bytes_per_second"]
    path = tmp_path / "c.json"
    path.write_text(json.dumps(p))
    with pytest.raises(calibrate.CalibrationFormatError,
                       match="hbm_bytes_per_second"):
        calibrate.load_calibration(str(path))


def test_mutation_foreign_format_version(tmp_path):
    p = _valid_payload()
    p["format"] = 99
    path = tmp_path / "c.json"
    path.write_text(json.dumps(p))
    with pytest.raises(calibrate.CalibrationFormatError,
                       match="format 99"):
        calibrate.load_calibration(str(path))


def test_mutation_tampered_plan_store_calibration(toy_model, tmp_path):
    """A plan store whose embedded calibration was tampered (NaN rate)
    must refuse whole — plans priced under garbage constants must not
    load, let alone execute."""
    eng = _engine(toy_model, calibration=calibrate.injected())
    path = str(tmp_path / "plans.json")
    eng.save_plan(path)
    doc = json.load(open(path))
    assert doc["calibrations"], "store must persist its calibration"
    doc["calibrations"][0]["flops_per_second"] = float("nan")
    json.dump(doc, open(path, "w"))
    costmodel.clear_plan_store()
    calibrate.clear_registry()
    with pytest.raises(calibrate.CalibrationValueError):
        costmodel.load_plan_store(path)
    assert costmodel.plan_cache_info()["store"] == 0   # nothing half-loaded


def test_mutation_every_error_is_a_named_calibration_error():
    """The soft consumers catch CalibrationError; every named rejection
    must be a subclass or the fallback silently turns into a crash."""
    for cls in (calibrate.CalibrationFormatError,
                calibrate.CalibrationValueError,
                calibrate.CalibrationHardwareMismatch,
                calibrate.CalibrationMeshMismatch):
        assert issubclass(cls, calibrate.CalibrationError)
    assert issubclass(calibrate.CalibrationFallbackWarning, UserWarning)
    # the warning must never be caught (and swallowed) as a rejection
    assert not issubclass(calibrate.CalibrationFallbackWarning,
                          calibrate.CalibrationError)


# ---------------------------------------------------------------------------
# Kernel sweep plumbing: the measured VMEM budget reaches the autotuner.


def test_vmem_budget_precedence(monkeypatch):
    assert kops.vmem_budget() == kops.VMEM_BUDGET      # analytic default
    calib = calibrate.injected(
        kernels={"pe_conv_grad": {"vmem_budget": 4 << 20, "th": 8}})
    calibrate.register(calib)
    assert kops.vmem_budget() == 4 << 20               # measured winner
    monkeypatch.setenv("REPRO_VMEM_BUDGET", str(1 << 20))
    assert kops.vmem_budget() == 1 << 20               # env overrides both


def test_quick_harness_measures_live_hardware():
    """The harness end-to-end on this host: finite positive rates, the
    live hardware signature, and a pe_conv_grad sweep winner that is a
    real budget from the sweep grid."""
    calib = calibrate.measure(quick=True)
    assert calib.hardware == calibrate.hardware_signature()
    assert math.isfinite(calib.flops_per_second)
    assert calib.flops_per_second > 0
    assert calib.hbm_bytes_per_second > 0
    pe = calib.kernels["pe_conv_grad"]
    assert str(pe["vmem_budget"]) in pe["sweep"]       # winner from grid
    assert pe["th"] >= 1
    # round-trips through its own serialization
    assert calibrate.Calibration.from_json(calib.to_json()) == calib


# ---------------------------------------------------------------------------
# Sharded lane (the 8-device CI job).


@needs_8_devices
@pytest.mark.multidevice
def test_sharded_replan_continues_training(toy_model):
    """The mispredict loop under a real data:8 mesh: a re-plan retimes
    the *wire* (the mesh plan moves collective bytes), rebuilds the
    sharded jitted step, and training continues on the same noise stream
    with the ledger intact."""
    batch = jax.tree.map(lambda a: jnp.concatenate([a, a], axis=0),
                         toy_model[2])
    params0, batch_fn = toy_model[1], _batch_fn(batch)
    mesh = make_auto_mesh((8,), ("data",))
    calib = calibrate.injected(mesh="data:8",
                               collective_bytes_per_second=1e9)
    mon = StepMonitor()
    eng = _engine(toy_model, calibration=calib, mesh=mesh, batch=batch,
                  monitor=mon)
    bad = eng.predicted_step_seconds() * 10
    got_p, _ = _drive(eng, params0, batch_fn, feed_seconds=bad)
    assert len(eng.replan_events) == 1
    ev = eng.replan_events[0]
    # the divergence was attributed to the wire, not the FLOP rate
    new = eng.calibration
    assert new.source == "replan"
    assert new.flops_per_second == calib.flops_per_second
    assert new.collective_bytes_per_second["data"] \
        < calib.collective_bytes_per_second["data"]
    assert mon.replans == [(ev.step, pytest.approx(ev.ratio))]
    assert eng.accountant.steps == STEPS
    assert all(bool(jnp.all(jnp.isfinite(x)))
               for x in jax.tree.leaves(got_p))


# ---------------------------------------------------------------------------
# Per-axis retiming (2D meshes)


def test_retimed_prices_old_wire_share_per_axis():
    """With a per-axis byte breakdown, retiming computes the old wire
    share on the axes the traffic actually crossed and rescales every
    measured bandwidth so the new prediction closes the gap exactly."""
    calib = calibrate.injected(
        mesh="data:4,model:2", flops_per_second=1e12,
        collective_bytes_per_second={"data": 16e9, "model": 2e9})
    by_axis = (("data", 64 * 2**20), ("model", 8 * 2**20))
    total = sum(b for _, b in by_axis)
    wire_old = sum(b / {"data": 16e9, "model": 2e9}[a] for a, b in by_axis)
    predicted = wire_old + 2e-3          # 2 ms of compute
    measured = 2.0 * wire_old + 2e-3     # wire twice as slow as measured
    new = calib.retimed(predicted_s=predicted, measured_s=measured,
                        coll_bytes=total, coll_bytes_by_axis=by_axis)
    # both axes rescaled by the same factor (the observed wire slowdown)
    assert new.collective_bytes_per_second["data"] == pytest.approx(8e9)
    assert new.collective_bytes_per_second["model"] == pytest.approx(1e9)
    # the compute rate is untouched — the wire absorbed the whole gap
    assert new.flops_per_second == calib.flops_per_second
    assert new.source == "replan"


def test_retimed_per_axis_emits_no_axisless_fallback_warning():
    import warnings as _w
    calib = calibrate.injected(
        mesh="data:4,model:2", flops_per_second=1e12,
        collective_bytes_per_second={"data": 16e9, "model": 2e9})
    by_axis = (("data", 2**20), ("model", 2**18))
    with _w.catch_warnings():
        _w.simplefilter("error",
                        calibrate.CalibrationAxisFallbackWarning)
        calib.retimed(predicted_s=1e-3, measured_s=2e-3,
                      coll_bytes=2**20 + 2**18, coll_bytes_by_axis=by_axis)


def test_retimed_without_wire_share_falls_back_to_flop_rate():
    """Zero collective traffic: nothing to attribute to the wire — the
    FLOP rate absorbs the divergence (also the legacy axis-less path)."""
    calib = calibrate.injected(
        mesh="data:4,model:2", flops_per_second=1e12,
        collective_bytes_per_second={"data": 16e9, "model": 2e9})
    new = calib.retimed(predicted_s=1e-3, measured_s=2e-3, coll_bytes=0.0)
    assert new.flops_per_second == pytest.approx(5e11)
    assert new.collective_bytes_per_second \
        == calib.collective_bytes_per_second
