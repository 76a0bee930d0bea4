"""Kill-and-resume equivalence: the differential proof of preemption-safe
DP training.

A DP run that restarts sloppily is a *privacy* bug, not just a training
bug: replayed noise draws, a double-counted accountant ledger, or a
stale-clip bootstrap re-run with the wrong coefficients all change the
(ε, δ) guarantee silently.  The contract under test: with a
deterministic noise stream (``fold_in(PRNGKey(run_seed), step)``) and a
checkpointed :class:`DPTrainState` (params, optimizer, cross-step clip
state, ledger, plan fingerprint), a run killed at *any* step — including
mid-checkpoint-write and during the stale-clip bootstrap — resumes to
bit-identical params, optimizer state, noise draws, and ledger versus a
run that never died.  The ``multidevice`` lane proves the same for the
sharded step, and the elastic lane proves a shrunken mesh re-plans and
continues the ledger without a gap.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import Checkpointer, CheckpointCorrupt, DPTrainState
from repro.core import (ClipPolicy, DPConfig, PrivacyAccountant,
                        PrivacyEngine, costmodel)
from repro.launch.mesh import make_auto_mesh
from repro.optim import adamw_init
from repro.runtime import (ChaosMonkey, WorkerFailure, elastic_mesh_axes,
                           run_with_restarts)

needs_8_devices = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

RUN_SEED = 7
NOISE = 0.9
STEPS = 5


class KillSignal(Exception):
    """A process death: deliberately NOT in run_with_restarts' catch set,
    so it unwinds the whole 'process' like a preemption would."""


def _bitwise_equal(a, b):
    return all(bool(jnp.all(x == y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _batch_fn(batch):
    """Deterministic per-step batch stream (pure function of step, like a
    seeded data loader): restart replay must see identical data."""
    def fn(step):
        return jax.tree.map(lambda a: jnp.roll(a, step, axis=0), batch)
    return fn


def _engine(toy, clip_mode="flat", mesh=None, batch=None):
    apply_fn, params, batch0 = toy
    clip = (ClipPolicy(mode="per_layer", budgets="auto")
            if clip_mode == "per_layer_auto" else ClipPolicy(mode=clip_mode))
    dp = DPConfig(l2_clip=0.1, noise_multiplier=NOISE, clipping=clip)
    acct = PrivacyAccountant(sampling_rate=1 / 128, noise_multiplier=NOISE)
    return PrivacyEngine(apply_fn, params,
                         batch0 if batch is None else batch, dp=dp,
                         lr=1e-2, accountant=acct, run_seed=RUN_SEED,
                         mesh=mesh)


def _drive(engine, params0, batch_fn, steps=STEPS, ckpt=None, kill_at=None,
           chaos=None, ckpt_every=1):
    """One process lifetime: restore DPTrainState if a checkpoint exists,
    then step to ``steps`` on the deterministic noise stream, dying with
    KillSignal just before executing ``kill_at``."""
    params, opt, start = params0, adamw_init(params0), 0
    if ckpt is not None and ckpt.latest_step() is not None:
        st, at = ckpt.restore_state(params, opt)
        params, opt = st.params, st.opt
        engine.load_clip_state(st.clip_state)
        engine.accountant.load_state_dict(st.ledger)
        start = at + 1
    else:
        engine.reset_clip_state()
        engine.accountant.reset()
    for step in range(start, steps):
        if kill_at is not None and step == kill_at:
            raise KillSignal(f"killed before step {step}")
        if chaos is not None:
            chaos.maybe_fail(step)
        params, opt, _, _ = engine.private_step(params, opt, batch_fn(step),
                                                step=step)
        if ckpt is not None and (step + 1) % ckpt_every == 0:
            ckpt.save_state(step, DPTrainState(
                params=params, opt=opt,
                clip_state=engine.clip_state_dict(),
                ledger=engine.accountant.state_dict(),
                plan_fingerprint=engine.fingerprint(),
                run_seed=RUN_SEED,
                mesh_axes=costmodel.mesh_axes(engine.mesh)))
    return params, opt


# ---------------------------------------------------------------------------
# The core differential lane: killed-at-step-k == never killed, bitwise


@pytest.mark.parametrize("clip_mode,kill_at", [
    ("flat", 1),
    ("flat", 3),
    ("stale", 0),            # killed during the stale-clip bootstrap step
    ("stale", 1),            # killed right after it (lagged norms live)
    ("per_layer_auto", 2),   # killed with tracked budget quantiles live
])
def test_kill_and_resume_bit_identical(toy_model, tmp_path, clip_mode,
                                       kill_at):
    params0, batch_fn = toy_model[1], _batch_fn(toy_model[2])
    ref_engine = _engine(toy_model, clip_mode)
    ref_p, ref_o = _drive(ref_engine, params0, batch_fn)
    ck = Checkpointer(str(tmp_path))
    with pytest.raises(KillSignal):
        _drive(_engine(toy_model, clip_mode), params0, batch_fn, ckpt=ck,
               kill_at=kill_at)
    res_engine = _engine(toy_model, clip_mode)
    got_p, got_o = _drive(res_engine, params0, batch_fn, ckpt=ck)
    assert _bitwise_equal(ref_p, got_p)
    assert _bitwise_equal(ref_o, got_o)
    # the ledger continued without a gap — replayed steps are the *same*
    # mechanism outputs, so they must not be re-counted
    assert res_engine.accountant.state_dict() == \
        ref_engine.accountant.state_dict()
    assert res_engine.accountant.steps == STEPS


def test_noise_stream_is_pure_function_of_seed_and_step(toy_model):
    e1, e2 = _engine(toy_model), _engine(toy_model)
    for step in (0, 3, 1 << 20):
        np.testing.assert_array_equal(e1.noise_key(step), e2.noise_key(step))
    assert not np.array_equal(e1.noise_key(3), e1.noise_key(4))
    # a different run seed is a different stream
    e3 = PrivacyEngine(toy_model[0], toy_model[1], toy_model[2],
                       dp=DPConfig(l2_clip=0.1), run_seed=RUN_SEED + 1)
    assert not np.array_equal(e1.noise_key(3), e3.noise_key(3))


@pytest.mark.parametrize("torn", ["payload", "pointer"])
def test_kill_mid_checkpoint_write(toy_model, tmp_path, monkeypatch, torn):
    """Die inside Checkpointer.save itself — before the atomic payload
    rename ('payload': the step directory must stay invisible) or before
    the LATEST pointer rename ('pointer': the completed directory must
    still be found).  Either way the resumed run is bit-identical."""
    params0, batch_fn = toy_model[1], _batch_fn(toy_model[2])
    ref_p, ref_o = _drive(_engine(toy_model), params0, batch_fn)
    ck = Checkpointer(str(tmp_path))
    import repro.checkpoint.checkpointer as ckpt_mod
    real_rename = os.rename

    def dying_rename(src, dst):
        if "step_000000002" in src and torn == "payload" \
                and src.endswith(".tmp"):
            raise KillSignal("killed before the payload rename")
        if torn == "pointer" and src.endswith("LATEST.tmp") \
                and open(src).read().strip() == "step_000000002":
            raise KillSignal("killed before the LATEST pointer rename")
        return real_rename(src, dst)

    monkeypatch.setattr(ckpt_mod.os, "rename", dying_rename)
    with pytest.raises(KillSignal):
        _drive(_engine(toy_model), params0, batch_fn, ckpt=ck)
    monkeypatch.undo()
    expect = 1 if torn == "payload" else 2
    assert ck.available_steps()[0] == expect
    got_p, got_o = _drive(_engine(toy_model), params0, batch_fn, ckpt=ck)
    assert _bitwise_equal(ref_p, got_p)
    assert _bitwise_equal(ref_o, got_o)


def test_resume_falls_back_past_corrupt_checkpoint(toy_model, tmp_path):
    """A torn/corrupt newest checkpoint must not strand the run: restore
    falls back to the previous keep-k step and replays forward to the
    same bits."""
    params0, batch_fn = toy_model[1], _batch_fn(toy_model[2])
    ref_p, _ = _drive(_engine(toy_model), params0, batch_fn)
    ck = Checkpointer(str(tmp_path))
    with pytest.raises(KillSignal):
        _drive(_engine(toy_model), params0, batch_fn, ckpt=ck, kill_at=4)
    # truncate the newest checkpoint's arrays file (steps 1..3 remain)
    f = os.path.join(str(tmp_path), "step_000000003", "arrays.npz")
    data = open(f, "rb").read()
    open(f, "wb").write(data[: len(data) // 2])
    # with fallback disabled the corruption is loud...
    with pytest.raises(CheckpointCorrupt):
        ck.restore_state(params0, adamw_init(params0), fallback=False)
    # ...and with it (the default) the resumed run replays from step 2
    got_p, _ = _drive(_engine(toy_model), params0, batch_fn, ckpt=ck)
    assert _bitwise_equal(ref_p, got_p)


def test_orchestrated_chaos_run_matches_reference(toy_model, tmp_path):
    """The full fault.py orchestration: ChaosMonkey trips recoverable
    WorkerFailures, run_with_restarts re-enters the segment, the segment
    restores DPTrainState — the surviving run equals the undisturbed one
    bit for bit, and the ledger is not double-counted."""
    params0, batch_fn = toy_model[1], _batch_fn(toy_model[2])
    ref_engine = _engine(toy_model, "stale")
    ref_p, _ = _drive(ref_engine, params0, batch_fn)
    ck = Checkpointer(str(tmp_path))
    engine = _engine(toy_model, "stale")
    chaos = ChaosMonkey(fail_at_steps=[1, 3])

    def segment(restart_count):
        return _drive(engine, params0, batch_fn, ckpt=ck, chaos=chaos)

    (got_p, _), restarts = run_with_restarts(segment, max_restarts=5)
    assert restarts == 2 and chaos.tripped == 2
    assert _bitwise_equal(ref_p, got_p)
    assert engine.accountant.state_dict() == \
        ref_engine.accountant.state_dict()


def test_resume_refuses_foreign_ledger(toy_model, tmp_path):
    """A checkpoint accounted under a different mechanism (σ) must not
    graft onto this run's accountant."""
    from repro.core.privacy import LedgerMismatch
    params0, batch_fn = toy_model[1], _batch_fn(toy_model[2])
    ck = Checkpointer(str(tmp_path))
    with pytest.raises(KillSignal):
        _drive(_engine(toy_model), params0, batch_fn, ckpt=ck, kill_at=3)
    engine = _engine(toy_model)
    engine.accountant.sigma = NOISE * 2  # simulate a changed mechanism
    with pytest.raises(LedgerMismatch, match="sigma"):
        _drive(engine, params0, batch_fn, ckpt=ck)


# ---------------------------------------------------------------------------
# Sharded lanes (the 8-device CI job)


def _batch8(batch):
    return jax.tree.map(lambda a: jnp.concatenate([a, a], axis=0), batch)


@pytest.mark.multidevice
@needs_8_devices
@pytest.mark.parametrize("kill_at", [0, 2])
def test_kill_and_resume_bit_identical_sharded(toy_model, tmp_path,
                                               kill_at):
    batch = _batch8(toy_model[2])
    params0, batch_fn = toy_model[1], _batch_fn(batch)
    mesh = make_auto_mesh((8,), ("data",))
    ref_p, ref_o = _drive(_engine(toy_model, mesh=mesh, batch=batch),
                          params0, batch_fn)
    ck = Checkpointer(str(tmp_path))
    with pytest.raises(KillSignal):
        _drive(_engine(toy_model, mesh=mesh, batch=batch), params0,
               batch_fn, ckpt=ck, kill_at=kill_at)
    res_engine = _engine(toy_model, mesh=mesh, batch=batch)
    got_p, got_o = _drive(res_engine, params0, batch_fn, ckpt=ck)
    assert _bitwise_equal(ref_p, got_p)
    assert _bitwise_equal(ref_o, got_o)
    assert res_engine.accountant.steps == STEPS


@pytest.mark.multidevice
@needs_8_devices
def test_elastic_resume_replans_onto_smaller_mesh(toy_model, tmp_path):
    """Kill a data:8 run, 'lose' half the devices, resume on data:4: the
    fingerprint mismatch is recognized as a mesh change (not a model
    change), the plan is rebuilt for the surviving topology, and the
    ledger + noise stream continue without a gap.  Params match up to
    reduction order (bitwise is only guaranteed mesh-to-same-mesh)."""
    batch = _batch8(toy_model[2])
    params0, batch_fn = toy_model[1], _batch_fn(batch)
    mesh8 = make_auto_mesh((8,), ("data",))
    ref_engine = _engine(toy_model, mesh=mesh8, batch=batch)
    ref_p, _ = _drive(ref_engine, params0, batch_fn)
    ck = Checkpointer(str(tmp_path))
    with pytest.raises(KillSignal):
        _drive(_engine(toy_model, mesh=mesh8, batch=batch), params0,
               batch_fn, ckpt=ck, kill_at=3)
    # the surviving-mesh computation the launcher runs
    surv = elastic_mesh_axes((("data", 8),), 4, jax.tree.leaves(batch)[0]
                             .shape[0])
    assert surv == (("data", 4),)
    mesh4 = make_auto_mesh((4,), ("data",), devices=jax.devices()[:4])
    res_engine = _engine(toy_model, mesh=mesh4, batch=batch)
    st, _ = ck.restore_state(params0, adamw_init(params0))
    # the elastic cross-check: mismatch vanishes when re-keyed under the
    # checkpoint's mesh — so this is a resumable mesh change
    assert st.plan_fingerprint != res_engine.fingerprint()
    assert st.plan_fingerprint == res_engine.fingerprint(mesh=st.mesh_axes)
    got_p, _ = _drive(res_engine, params0, batch_fn, ckpt=ck)
    assert res_engine.accountant.steps == STEPS          # no ledger gap
    # host-side compare: the two param trees live on different meshes
    diff = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
               for a, b in zip(jax.tree.leaves(ref_p),
                               jax.tree.leaves(got_p)))
    assert diff < 1e-6
