"""Checkpointer: atomic roundtrip, corruption detection, keep-k, async."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import Checkpointer, CheckpointCorrupt, DPTrainState
from repro.launch.mesh import make_auto_mesh


@pytest.fixture
def tree():
    return {"a": {"w": jnp.arange(12.0).reshape(3, 4)},
            "b": jnp.ones((5,), jnp.int32),
            "step": jnp.zeros((), jnp.int32)}


def test_roundtrip(tmp_path, tree):
    ck = Checkpointer(str(tmp_path))
    ck.save(3, tree)
    got, step = ck.restore(tree)
    assert step == 3
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(a, b)


def test_latest_pointer_and_keep(tmp_path, tree):
    ck = Checkpointer(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        ck.save(s, tree)
    assert ck.latest_step() == 4
    dirs = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert len(dirs) == 2


def test_corruption_detected(tmp_path, tree):
    ck = Checkpointer(str(tmp_path))
    path = ck.save(1, tree)
    # corrupt the arrays file
    f = os.path.join(path, "arrays.npz")
    data = dict(np.load(f))
    key = sorted(data)[0]
    data[key] = data[key] + 1
    np.savez(f, **data)
    with pytest.raises(IOError):
        ck.restore(tree)
    got, _ = ck.restore(tree, verify=False)  # opt-out works


def test_async_save(tmp_path, tree):
    ck = Checkpointer(str(tmp_path))
    ck.save_async(7, tree)
    ck.wait()
    assert ck.latest_step() == 7


def test_restore_with_shardings(tmp_path, tree):
    """Elastic path: restore places leaves onto given shardings."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_auto_mesh((1,), ("data",))
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)
    ck = Checkpointer(str(tmp_path))
    ck.save(0, tree)
    got, _ = ck.restore(tree, shardings=sh)
    assert all(g.sharding == NamedSharding(mesh, P())
               for g in jax.tree.leaves(got))


def test_interrupted_write_is_invisible(tmp_path, tree):
    ck = Checkpointer(str(tmp_path))
    ck.save(1, tree)
    # simulate a crash mid-write: a .tmp dir that never got renamed
    os.makedirs(os.path.join(tmp_path, "step_000000002.tmp"))
    assert ck.latest_step() == 1
    got, step = ck.restore(tree)
    assert step == 1


def test_corruption_raises_named_exception(tmp_path, tree):
    ck = Checkpointer(str(tmp_path))
    path = ck.save(1, tree)
    f = os.path.join(path, "arrays.npz")
    data = dict(np.load(f))
    key = sorted(data)[0]
    data[key] = data[key] + 1
    np.savez(f, **data)
    with pytest.raises(CheckpointCorrupt, match="CRC"):
        ck.restore(tree)


def test_truncated_arrays_falls_back_to_previous(tmp_path, tree):
    """A torn write (truncated arrays.npz) on the newest step must not
    strand the run: fallback restore lands on the previous keep-k step."""
    ck = Checkpointer(str(tmp_path), keep=3)
    ck.save(1, tree)
    ck.save(2, jax.tree.map(lambda x: x + 1, tree))
    f = os.path.join(tmp_path, "step_000000002", "arrays.npz")
    raw = open(f, "rb").read()
    with open(f, "wb") as fh:
        fh.write(raw[: len(raw) // 2])
    with pytest.raises(CheckpointCorrupt):
        ck.restore(tree, fallback=False)
    got, step = ck.restore(tree, fallback=True)
    assert step == 1
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(a, b)
    # every checkpoint corrupt -> the last error still surfaces
    f1 = os.path.join(tmp_path, "step_000000001", "arrays.npz")
    with open(f1, "wb") as fh:
        fh.write(b"not a zip")
    with pytest.raises(CheckpointCorrupt):
        ck.restore(tree, fallback=True)


def test_train_state_roundtrip(tmp_path, tree):
    """DPTrainState persists everything a DP resume needs: clip arrays
    restored verbatim, ledger/monitor/fingerprint via the CRC'd meta."""
    ck = Checkpointer(str(tmp_path))
    opt = {"m": jnp.zeros((3, 4)), "step": jnp.asarray(5, jnp.int32)}
    clip = {"prev_norms_sq": np.arange(4.0), "budget_q": np.float32(0.7)}
    st = DPTrainState(
        params=tree, opt=opt, clip_state=clip,
        ledger={"steps": 42, "q": 0.01, "sigma": 1.1,
                "orders": [2.0, 4.0]},
        plan_fingerprint="abc123", monitor={"ema": 0.2},
        run_seed=7, mesh_axes=(("data", 8),))
    ck.save_state(3, st)
    got, step = ck.restore_state(tree, opt)
    assert step == 3
    np.testing.assert_array_equal(got.clip_state["prev_norms_sq"],
                                  clip["prev_norms_sq"])
    np.testing.assert_array_equal(got.clip_state["budget_q"],
                                  clip["budget_q"])
    assert got.ledger == st.ledger
    assert got.plan_fingerprint == "abc123"
    assert got.monitor == {"ema": 0.2}
    assert got.run_seed == 7
    assert got.mesh_axes == (("data", 8),)
    for a, b in zip(jax.tree.leaves(got.params), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(jax.tree.leaves(got.opt), jax.tree.leaves(opt)):
        np.testing.assert_array_equal(a, b)


def test_corrupt_meta_detected_and_fallback(tmp_path, tree):
    """Tampered meta.json (the privacy ledger lives there) fails the
    manifest CRC; restore_state falls back to the previous step."""
    ck = Checkpointer(str(tmp_path))
    opt = {"v": jnp.zeros(2)}
    good = DPTrainState(params=tree, opt=opt,
                        ledger={"steps": 1, "q": 0.1, "sigma": 1.0,
                                "orders": [2.0]})
    ck.save_state(1, good)
    ck.save_state(2, DPTrainState(params=tree, opt=opt,
                                  ledger={"steps": 2, "q": 0.1,
                                          "sigma": 1.0, "orders": [2.0]}))
    mf = os.path.join(tmp_path, "step_000000002", "meta.json")
    meta = json.load(open(mf))
    meta["ledger"]["steps"] = 0  # an adversarial/bitrot ledger edit
    with open(mf, "w") as fh:
        json.dump(meta, fh)
    with pytest.raises(CheckpointCorrupt, match="meta"):
        ck.read_meta(2)
    with pytest.raises(CheckpointCorrupt):
        ck.restore_state(tree, opt, fallback=False)
    got, step = ck.restore_state(tree, opt, fallback=True)
    assert step == 1 and got.ledger["steps"] == 1


def test_state_async_save(tmp_path, tree):
    ck = Checkpointer(str(tmp_path))
    st = DPTrainState(params=tree, opt={"v": jnp.ones(3)},
                      clip_state={"budgets": np.ones(2)}, run_seed=0)
    ck.save_state_async(4, st)
    ck.wait()
    got, step = ck.restore_state(tree, {"v": jnp.ones(3)})
    assert step == 4 and got.run_seed == 0
    np.testing.assert_array_equal(got.clip_state["budgets"], np.ones(2))
