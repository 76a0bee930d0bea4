"""Miniature multi-device dry-run in a subprocess (8 virtual devices), so
the 512-device production path is exercised without polluting this test
process's device count."""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.core import DPConfig
from repro.core.clipping import dp_gradient
from repro.launch import sharding as shd
from repro.launch.mesh import make_auto_mesh
from repro.launch.dryrun import abstract_params, cache_sharding, \
    cost_analysis_dict, parse_collectives
from repro.models.registry import build_model
from repro.optim import adamw_init, adamw_update

mesh = make_auto_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = get_config("llama3.2-1b").reduced().replace(dtype="bfloat16")
model = build_model(cfg)

with shd.mesh_rules(mesh):
    params_sds, axes = abstract_params(model)
    pshard = shd.param_sharding(axes, mesh, shapes_tree=params_sds)
    params_in = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        params_sds, pshard)
    dpc = DPConfig(l2_clip=1.0, noise_multiplier=1.0, strategy="ghost",
                   microbatches=2)

    def train_step(params, opt, batch, key):
        loss, grad, aux = dp_gradient(model.apply, params, batch, cfg=dpc,
                                      key=key)
        params, opt = adamw_update(grad, opt, params)
        return params, opt, loss

    opt_sds = jax.eval_shape(adamw_init, params_sds)
    repl = NamedSharding(mesh, P())
    opt_in = {
        "m": jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sh), opt_sds["m"], pshard),
        "v": jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sh), opt_sds["v"], pshard),
        "step": jax.ShapeDtypeStruct((), jnp.int32, sharding=repl),
    }
    bspec = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32),
             "labels": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
    bshard = shd.batch_sharding(bspec, mesh)
    batch_in = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        bspec, bshard)
    key_in = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl)

    lowered = jax.jit(train_step).lower(params_in, opt_in, batch_in, key_in)
compiled = lowered.compile()
ca = cost_analysis_dict(compiled)
coll = parse_collectives(compiled.as_text())
ma = compiled.memory_analysis()
print(json.dumps({
    "flops": ca.get("flops"),
    "collective_bytes": coll["total_bytes"],
    "all_reduce_count": coll["all-reduce"]["count"],
    "temp_bytes": ma.temp_size_in_bytes,
}))
"""


@pytest.mark.slow
def test_small_multipod_dryrun(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["flops"] and rec["flops"] > 0
    assert rec["collective_bytes"] > 0        # DP grad sync must exist
    assert rec["all_reduce_count"] > 0
    assert rec["temp_bytes"] > 0
