"""Every phase of the private step is named in the profiler's trace: the
device operations of the compiled step carry their phase's
``jax.named_scope`` in their metadata (``dp.capture``,
``dp.norm/<method>/<group>``, ``dp.clip``, ``dp.contrib/<method>/<group>``,
``dp.noise``, ``dp.update``), each Pallas kernel has a stable name, and
``private_step`` writes the host spans ``engine.*``."""
import contextlib
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import ClipPolicy, DPConfig, PrivacyEngine, costmodel
from repro.core.strategies import (clipped_grad_sum_detailed, group_key_of,
                                   phase_scope)
from repro.models.registry import build_model
from repro.optim import adamw_init

# A plan that puts each norm realization of the toy CNN on one layer.
OVERRIDES = {"conv0": "pe", "conv1": "ghost", "fc0": "rank1"}
B = 4


@pytest.fixture(scope="module")
def toy():
    cfg = get_config("vgg16").replace(
        cnn_arch="toy", cnn_channels=(4, 8), cnn_kernel=3, img_size=16,
        n_classes=10)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    batch = {"img": jnp.asarray(rng.randn(B, 3, 16, 16), jnp.float32),
             "label": jnp.asarray(rng.randint(0, 10, (B,)), jnp.int32)}
    return model.apply, params, batch


def _engine(toy, mode="flat"):
    apply_fn, params, batch = toy
    return PrivacyEngine(
        apply_fn, params, batch, run_seed=0,
        dp=DPConfig(l2_clip=1.0, noise_multiplier=1.0, overrides=OVERRIDES,
                    clipping=ClipPolicy(mode=mode)))


def scope_of(op_name: str):
    """The ``dp.*`` scope path of an operation's op name, with JAX's
    transform wrappers (``transpose(jvp(...))``) taken off; ``None``
    outside every scope."""
    parts = re.sub(r"[A-Za-z_][\w.]*\(|\)", "", op_name).split("/")
    for i, part in enumerate(parts):
        if part.startswith("dp."):
            n = 3 if part in ("dp.norm", "dp.contrib") else 1
            if parts[i + 1:i + 2] == ["backward"]:
                n = 2
            return "/".join(parts[i:i + n])
    return None


def _compiled_scopes(fn, *args) -> set:
    text = jax.jit(fn).lower(*args).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    return {scope_of(n) for n in names} - {None}


def _planned_scopes(plan, mode) -> set:
    """The norm and contribution scope of every planned group; under stale
    clipping a fused realization's one pass is all in the norm's."""
    want = set()
    for g in plan.groups:
        group = group_key_of(g.path).replace("/", ".")
        lp = plan.layers[g.members[0]]
        want.add(f"dp.norm/{lp.norm_method}/{group}")
        if g.sum_method != "backward" and not (mode == "stale" and lp.fused):
            want.add(f"dp.contrib/{g.sum_method}/{group}")
    return want


def test_scope_paths_parse_through_transform_wrappers():
    assert scope_of("jit(step)/dp.capture/transpose(jvp())/mul") == \
        "dp.capture"
    assert scope_of("jit(step)/transpose(jvp(dp.norm/pe/conv0))/mul") == \
        "dp.norm/pe/conv0"
    assert scope_of("jit(step)/dp.contrib/backward/transpose(jvp())/dot") \
        == "dp.contrib/backward"
    assert scope_of("jit(step)/while/body/dp.contrib/stash/a.b/dot") == \
        "dp.contrib/stash/a.b"
    assert scope_of("jit(step)/add") is None


def test_phase_scope_names_one_group_in_one_component():
    def fn(x):
        with phase_scope("norm", "gram", ("blocks", "attn", "q")):
            return jnp.sin(x) * x

    assert _compiled_scopes(fn, 1.0) == {"dp.norm/gram/blocks.attn.q"}


@pytest.mark.parametrize("mode", ["flat", "per_layer", "stale"])
def test_compiled_step_carries_every_phase_scope(toy, mode):
    apply_fn, params, batch = toy
    engine = _engine(toy, mode)
    plan = engine.plan()
    assert {n: lp.norm_method for n, lp in plan.layers.items()} == OVERRIDES
    clip_state = ({"prev_norms_sq": jnp.ones((B,), jnp.float32)}
                  if mode == "stale" else engine._clip_state())
    scopes = _compiled_scopes(engine._step_fn(), params, adamw_init(params),
                              batch, engine.noise_key(0), clip_state)
    want = {"dp.capture", "dp.clip", "dp.noise", "dp.update"} \
        | _planned_scopes(plan, mode)
    assert want <= scopes, sorted(want - scopes)
    assert all(s.split("/")[0] in ("dp.capture", "dp.norm", "dp.clip",
                                   "dp.contrib", "dp.noise", "dp.update")
               for s in scopes), sorted(scopes)


@pytest.mark.parametrize("strategy,contrib", [
    ("ghost", "dp.contrib/backward"),
    ("bk", "dp.contrib/contrib/conv1"),
])
def test_unplanned_strategies_name_their_phases(toy, strategy, contrib):
    """ghost and bk run as plans: each group's norm is scoped by its
    planned method, and its sum by the weighted backward (ghost) or by
    its contraction (bk)."""
    apply_fn, params, batch = toy

    def grad_sum(p, b):
        return clipped_grad_sum_detailed(apply_fn, p, b, l2_clip=1.0,
                                         strategy=strategy)[1]

    scopes = _compiled_scopes(grad_sum, params, batch)
    plan = costmodel.get_plan(apply_fn, params, batch, strategy=strategy)
    want = {"dp.capture", "dp.clip", contrib} | _planned_scopes(plan, "flat")
    assert want <= scopes, sorted(want - scopes)
    assert {"dp.norm/pe/conv0", "dp.norm/rank1/fc0"} <= scopes


def test_norm_scopes_agree_with_the_verifier_tags(toy):
    """Each group's ``dp_tag`` (kind=group_norm) and its ``dp.norm``
    scope name the same group, and the same method except where the tag
    says the per-example grads were stashed."""
    apply_fn, params, batch = toy
    engine = _engine(toy)
    jaxpr = jax.make_jaxpr(engine._step_fn())(
        params, adamw_init(params), batch, engine.noise_key(0), {})
    tags = {}

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "dp_tag" and \
                    eqn.params.get("kind") == "group_norm":
                tags[eqn.params["group"]] = eqn.params["method"]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    plan = engine.plan()
    assert set(tags) == {group_key_of(g.path) for g in plan.groups}
    for g in plan.groups:
        lp = plan.layers[g.members[0]]
        assert tags[group_key_of(g.path)] == \
            ("stash" if lp.stash else lp.norm_method)


def test_scopes_leave_the_step_bitwise_unchanged(toy, monkeypatch):
    """The names are metadata: the step computes the same bits with every
    scope taken away."""
    apply_fn, params, batch = toy

    def step():
        engine = _engine(toy)
        out = engine.private_step(params, adamw_init(params), batch, step=0)
        return jax.device_get(out[:3])

    scoped = step()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = step()
    assert all(np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(scoped), jax.tree.leaves(bare)))


def _kernel_names(fn, *args) -> list:
    names = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names


def test_every_pallas_kernel_has_a_stable_name():
    from repro.kernels.flash_attn import flash_attention
    from repro.kernels.gram_norm import gram_norm, gram_norm_fused
    from repro.kernels.pe_conv_grad import pe_conv_grad_2d

    x = jnp.ones((2, 8, 16), jnp.float32)
    dy = jnp.ones((2, 8, 4), jnp.float32)
    assert _kernel_names(lambda a, b: gram_norm(a, b), x, dy) == \
        ["gram_norm"]
    assert _kernel_names(
        lambda a, b, w: gram_norm_fused(a, b, w)[0], x, dy,
        jnp.ones((16, 4), jnp.float32)) == ["gram_norm_fused"]
    assert _kernel_names(
        lambda a, b: pe_conv_grad_2d(a, b, KH=3, KW=3),
        jnp.ones((2, 3, 8, 8), jnp.float32),
        jnp.ones((2, 4, 6, 6), jnp.float32)) == ["pe_conv_grad"]
    q = jnp.ones((1, 16, 2, 8), jnp.float32)
    names = _kernel_names(jax.grad(lambda a: jnp.sum(
        flash_attention(a, a, a, bq=8, bk=8))), q)
    assert sorted(names) == ["flash_dkv", "flash_dq", "flash_fwd"]


def _host_spans(trace_dir) -> list:
    from jax.profiler import ProfileData
    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    assert len(files) == 1, files
    spans = []
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans += [(ev.name, ev.start_ns, ev.end_ns) for ev in line.events
                      if ev.name.startswith("engine.")]
    return sorted(spans, key=lambda s: s[1])


def test_private_step_writes_engine_spans(toy, tmp_path):
    apply_fn, params, batch = toy
    engine = _engine(toy)
    opt = adamw_init(params)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for step in range(2):
            params, opt, loss, _ = engine.private_step(params, opt, batch,
                                                       step=step)
        loss.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    count = {}
    for name, _, _ in spans:
        count[name] = count.get(name, 0) + 1
    assert count == {"engine.private_step": 2, "engine.noise_key": 2,
                     "engine.dispatch": 2, "engine.absorb_clip_aux": 2,
                     "engine.trace": 1}
    steps = [s for s in spans if s[0] == "engine.private_step"]
    dispatches = [s for s in spans if s[0] == "engine.dispatch"]
    (_, t0, t1), = [s for s in spans if s[0] == "engine.trace"]
    # The step is traced once, inside the first call's dispatch.
    assert dispatches[0][1] <= t0 < t1 <= dispatches[0][2]
    for name, s, e in spans:
        if name != "engine.private_step":
            assert any(a <= s and e <= b for _, a, b in steps), name
