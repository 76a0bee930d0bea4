"""``correct`` holds for a sound run and fails for the control and for each
fault of the timed path, at a size a CPU holds, under the committed limits
of the cells the tiny cells stand for.

The control is the reference computed in bfloat16 and put in the program's
place.  The faults are planted in the program under the harness: a step
that returns its state unchanged, half of the batch left out with the mean
taken over the rest, and on four devices the exchange between them left
out."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from bench import run, spec
from repro.core import engine as engine_mod


def _run(root, cell_name, seed=2**31 + 11, trace=False):
    cell = spec.load_cell(cell_name, root)
    return run.run(cell, seed, 0.3, trace, require_tpu=False, root=root)


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("cell", ["tiny.flat.b8", "tiny.data4.flat.b16"])
def test_sound_run_is_correct(tiny_root, cell, trace):
    result = _run(tiny_root, cell, trace=trace)
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"
    assert result["failed"] == 0 and result["attempted"] >= 2
    if trace:
        # The CPU's trace has no TPU planes: only the host-clock metric
        # is read, and no device number is made up from the CPU.
        assert set(result["metrics"]) == {"loop.host_ms"}
        assert result["device"]["busy_s"] == 0
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        # The CPU keeps no memory stats: no peak is reported.
        assert set(result["metrics"]) == {"samples_per_s", "step_ms_p90",
                                          "setup_s"}


def test_control_is_not_correct(tiny_root, monkeypatch):
    import jax.numpy as jnp

    def bf16_steps(self, n):
        ref = spec.load_reference(self.cell.config, tiny_root).Reference(
            self.cell.config, self.cell.traffic,
            list(self.mesh.devices.flat), dtype=jnp.bfloat16)
        out = ref.run(self.params, self.ring[:n], self.seed, n)
        return {k: out[k] for k in ("losses", "m1", "params")}

    monkeypatch.setattr(run.Program, "checked_steps", bf16_steps)
    result = _run(tiny_root, "tiny.flat.b8")
    assert not result["correct"], result["checks"]


def test_unchanged_state_is_not_correct(tiny_root, monkeypatch):
    step = engine_mod.PrivacyEngine.private_step

    def unchanged(self, params, opt, batch, key=None, *, step_=None,
                  **kw):
        _, _, loss, aux = step(self, params, opt, batch, key, **kw)
        return params, opt, loss, aux

    monkeypatch.setattr(engine_mod.PrivacyEngine, "private_step", unchanged)
    result = _run(tiny_root, "tiny.flat.b8")
    assert not result["correct"]
    assert result["checks"]["update"]["value"] == pytest.approx(1.0)


def test_half_batch_is_not_correct(tiny_root, monkeypatch):
    dp_gradient = engine_mod.dp_gradient

    def half(apply_fn, params, batch, *, cfg, key=None, denom=None,
             plan=None, clip_state=None):
        B = jax.tree.leaves(batch)[0].shape[0]
        rest = jax.tree.map(lambda a: a[: B // 2], batch)
        return dp_gradient(apply_fn, params, rest, cfg=cfg, key=key,
                           denom=B // 2, clip_state=clip_state)

    monkeypatch.setattr(engine_mod, "dp_gradient", half)
    result = _run(tiny_root, "tiny.flat.b8")
    assert not result["correct"], result["checks"]


def test_no_exchange_between_chips_is_not_correct(tiny_root, monkeypatch):
    from repro.launch.mesh import make_mesh_from_spec
    dp_gradient = engine_mod.dp_gradient
    mesh = make_mesh_from_spec("data:4")

    def local(apply_fn, params, batch, *, cfg, key=None, denom=None,
              plan=None, clip_state=None):
        B = jax.tree.leaves(batch)[0].shape[0]

        def shard(p, b, k):
            return dp_gradient(apply_fn, p, b, cfg=cfg, key=k, denom=B,
                               clip_state=clip_state)

        return jax.shard_map(shard, mesh=mesh, in_specs=(P(), P("data"), P()),
                             out_specs=P(), check_vma=False)(params, batch,
                                                             key)

    monkeypatch.setattr(engine_mod, "dp_gradient", local)
    result = _run(tiny_root, "tiny.data4.flat.b16")
    assert not result["correct"], result["checks"]
    assert np.isfinite(result["checks"]["grad"]["value"])
